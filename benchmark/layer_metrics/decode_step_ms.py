"""Host-side time of one decode step: the decode dispatches' seconds (launch
to tokens on the host) over their count times the resolved decode chunk.
Dispatches are double-buffered, so this is not device time: PERF.md sets it
beside the decode module's device time from the trace."""
from benchmark import prom

UNIT = "ms"
NAME = "tpu_model_dispatch_seconds"


def read(ctx):
    s = prom.delta(ctx.before, ctx.after, NAME + "_sum", kind="decode")
    n = prom.delta(ctx.before, ctx.after, NAME + "_count", kind="decode")
    chunk = ctx.resolved.get("decode_chunk")
    return 1e3 * s / (n * chunk) if s and n and chunk else None
