"""Device time of one decode step under the indexer's scope (``attn.index``:
its projections, its key's write, its scores and the top-k): self time of the
decode module's operations in the trace, over the steps of its complete runs
(benchmark/index_spans.py). None for a program without the scope."""
from benchmark import index_spans

UNIT = "ms"


def read(ctx):
    return index_spans.step_ms(ctx)
