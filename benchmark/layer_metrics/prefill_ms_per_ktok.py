"""Host-side time of prompt processing: seconds of the admit and extend
dispatches inside the window per thousand useful prefill tokens."""
from benchmark import prom

UNIT = "ms"
NAME = "tpu_model_dispatch_seconds_sum"


def read(ctx):
    s = sum(prom.delta(ctx.before, ctx.after, NAME, kind=k) or 0.0
            for k in ("admit", "extend"))
    tok = prom.delta(ctx.before, ctx.after, "tpu_model_useful_tokens_total",
                     kind="prefill")
    return 1e3 * s / (tok / 1e3) if s and tok else None
