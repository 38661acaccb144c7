"""The held experts' share of their roofline: the bytes of the experts one
decode step touches (each held expert that the batch's picks reach, once, all
layers: the configuration's ``work`` file, ``experts_bytes_step``) over the
HBM rate, over the device time of one step under ``moe.experts``. The shared
expert runs under the same scope and its bytes are not counted: the share
reads low by that much."""
from benchmark import ssm_spans, trace_spans, work

UNIT = "%"


def read(ctx):
    f_bytes = work.own(ctx.conf, "experts_bytes_step")
    if f_bytes is None or not ctx.peaks:
        return None
    parts = trace_spans.decode_step_parts(trace_spans.reduce(),
                                          ctx.resolved.get("decode_chunk"))
    batch = ssm_spans.decode_batch(ctx)
    if parts is None or not batch:
        return None
    experts_s = parts["by_scope_s"].get("moe.experts")
    if not experts_s:
        return None
    nbytes = f_bytes(ctx.conf, batch, ctx.resolved["weights"])
    least_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes["moe_experts_roofline"] = dict(
        batch=batch, experts_bytes=nbytes, least_ms=1e3 * least_s,
        experts_ms=1e3 * experts_s)
    return 100.0 * least_s / experts_s
