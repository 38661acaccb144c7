"""Device time of one decode step under the gated delta-rule mixers' scopes
(``delta.in_proj``, ``delta.conv``, ``delta.update``, ``delta.gate_norm``,
``delta.out``): self time of the decode module's operations in the trace, over
the steps of its complete runs (benchmark/delta_spans.py; the parts go to the
trace line's notes). None for a program without them."""
from benchmark import delta_spans

UNIT = "ms"


def read(ctx):
    return delta_spans.step_ms(ctx)
