"""90th percentile of the time from the start of a request's first prefill
dispatch to its first token on the host: bucket deltas of the program's
stage histogram, stage "prefill"."""
from benchmark import prom

UNIT = "ms"
NAME = "tpu_model_request_stage_seconds"


def read(ctx):
    v = prom.hist_percentile(ctx.before, ctx.after, NAME, 0.90,
                             stage="prefill")
    return None if v is None else v * 1e3
