"""95th percentile of the time requests waited for admission inside the
window, from the bucket deltas of the server's queue-wait histogram
(interpolated inside a bucket)."""
from benchmark import prom

UNIT = "ms"


def read(ctx):
    v = prom.hist_percentile(ctx.before, ctx.after,
                             "tpu_model_queue_wait_seconds", 0.95)
    return None if v is None else v * 1e3
