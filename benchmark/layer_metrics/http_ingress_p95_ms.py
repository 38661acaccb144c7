"""95th percentile of the time a request spent in the HTTP layer before the
scheduler had it (body read, template, tokenize): bucket deltas of the
program's stage histogram, stage "ingress"."""
from benchmark import prom

UNIT = "ms"
NAME = "tpu_model_request_stage_seconds"


def read(ctx):
    v = prom.hist_percentile(ctx.before, ctx.after, NAME, 0.95,
                             stage="ingress")
    return None if v is None else v * 1e3
