"""90th percentile of the time a request waited for its slot (queued to the
start of its first prefill dispatch): bucket deltas of the program's stage
histogram, stage "queue". With ``ttft_prefill_p90_ms`` it splits the tail of
TTFT into waiting and prompt processing."""
from benchmark import prom

UNIT = "ms"
NAME = "tpu_model_request_stage_seconds"


def read(ctx):
    v = prom.hist_percentile(ctx.before, ctx.after, NAME, 0.90,
                             stage="queue")
    return None if v is None else v * 1e3
