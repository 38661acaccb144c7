"""95th percentile of the time from a request's first token on the host to
its first text frame written to the socket: bucket deltas of the program's
stage histogram, stage "first_flush"."""
from benchmark import prom

UNIT = "ms"
NAME = "tpu_model_request_stage_seconds"


def read(ctx):
    v = prom.hist_percentile(ctx.before, ctx.after, NAME, 0.95,
                             stage="first_flush")
    return None if v is None else v * 1e3
