"""Host time the scheduler spends handing one dispatch's tokens to their
requests (grammar walk, per-request queue put): seconds of the program's
``sched.fanout`` span inside the window over its count."""
from benchmark import prom

UNIT = "ms"
NAME = "tpu_model_span_seconds"


def read(ctx):
    s = prom.delta(ctx.before, ctx.after, NAME + "_sum", span="sched.fanout")
    n = prom.delta(ctx.before, ctx.after, NAME + "_count",
                   span="sched.fanout")
    return 1e3 * s / n if s is not None and n else None
