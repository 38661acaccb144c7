"""Device time of one decode step under the Mamba-2 mixers' scopes
(``ssm.in_proj``, ``ssm.conv``, ``ssm.scan``, ``ssm.gate_norm``, ``ssm.out``):
self time of the decode module's operations in the trace, over the steps of
its complete runs (benchmark/ssm_spans.py). None for a program without them."""
from benchmark import ssm_spans

UNIT = "ms"


def read(ctx):
    return ssm_spans.step_ms(ctx)
