"""Share of the decode positions dispatched inside the window that produced
a token someone asked for (useful over useful + padded)."""
from benchmark import prom

UNIT = "%"


def read(ctx):
    useful = prom.delta(ctx.before, ctx.after,
                        "tpu_model_useful_tokens_total", kind="decode")
    padded = prom.delta(ctx.before, ctx.after,
                        "tpu_model_padded_tokens_total", kind="decode")
    if not useful:
        return None
    return 100.0 * useful / (useful + (padded or 0.0))
