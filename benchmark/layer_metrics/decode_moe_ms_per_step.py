"""Device time of one decode step under the expert layers' scopes
(``moe.route`` + ``moe.experts``): self time of the decode module's
operations in the trace, over the steps of its complete runs
(benchmark/trace_spans.py). None for a model without a router."""
from benchmark import trace_spans

UNIT = "ms"


def read(ctx):
    parts = trace_spans.decode_step_parts(trace_spans.reduce(),
                                          ctx.resolved.get("decode_chunk"))
    if parts is None:
        return None
    by = parts["by_scope_s"]
    if "moe.experts" not in by:
        return None
    return 1e3 * (by.get("moe.route", 0.0) + by["moe.experts"])
