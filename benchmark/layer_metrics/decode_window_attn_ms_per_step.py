"""Device time of one decode step under the window layers' scope
(``attn.window``: the ring's write and the attention over the ring): self
time of the decode module's operations in the trace, over the steps of its
complete runs (benchmark/window_spans.py). None for a program without it."""
from benchmark import window_spans

UNIT = "ms"


def read(ctx):
    return window_spans.step_ms(ctx)
