"""The decode program's share of its roofline: the least time the chip could
take for one decode step (the larger of bytes over the HBM rate and
operations over the bf16 rate, by benchmark/work.py from the configuration's
shapes, the resolved storage types, and the batch and live context the
server counted between the two scrapes around the trace) over the device
time of one step: the decode module's median run in the trace over the steps
of a run."""
from benchmark import prom, work

UNIT = "%"


def decode_module(trace):
    """The decode program among the trace's modules: the one with "decode"
    in its name that took most device time."""
    mods = (trace or {}).get("modules") or {}
    named = [(name, m) for name, m in mods.items() if "decode" in name]
    return (max(named, key=lambda nm: nm[1]["seconds"]) if named
            else (None, None))


def read(ctx):
    name, mod = decode_module(ctx.trace)
    if mod is None or ctx.live_tokens is None:
        return None
    n = prom.delta(ctx.trace_before, ctx.trace_after,
                   "tpu_model_dispatch_seconds_count", kind="decode")
    useful = prom.delta(ctx.trace_before, ctx.trace_after,
                        "tpu_model_useful_tokens_total", kind="decode")
    chunk = ctx.resolved.get("decode_chunk")
    if not n or not useful or not chunk:
        return None
    batch = useful / (n * chunk)
    per_step = work.decode_step(ctx.conf, batch, ctx.live_tokens,
                                ctx.resolved["weights"],
                                ctx.resolved["kv_dtype"])
    least = work.least_seconds(per_step, ctx.peaks)
    # one run of the module is one dispatch of `chunk` steps; the median run
    # leaves out the runs the trace's edges cut
    step_s = mod["median_run_s"] / chunk
    ctx.notes["decode_step_roofline"] = dict(
        module=name, runs=mod["runs"], batch=batch,
        live_tokens=ctx.live_tokens, bound=least["bound"],
        least_step_s=least["seconds"], device_step_s=step_s)
    return 100.0 * least["seconds"] / step_s
