"""Device time of one decode step under the short-convolution mixers' scopes
(``conv.in_proj``, ``conv.conv``, ``conv.out``): self time of the decode
module's operations in the trace, over the steps of its complete runs
(benchmark/conv_spans.py). None for a program without them."""
from benchmark import conv_spans

UNIT = "ms"


def read(ctx):
    return conv_spans.step_ms(ctx)
