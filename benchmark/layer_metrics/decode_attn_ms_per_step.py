"""Device time of one decode step under the ``attn.core`` scope (the paged
kernel or its fallback): self time of the decode module's operations in the
trace, over the steps of its complete runs (benchmark/trace_spans.py)."""
from benchmark import trace_spans

UNIT = "ms"


def read(ctx):
    return trace_spans.step_ms(ctx, "attn")
