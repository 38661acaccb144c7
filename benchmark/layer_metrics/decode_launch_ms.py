"""Host time to launch one decode chunk (page growth, argument staging, the
jitted call's dispatch): seconds of the program's ``engine.decode_n`` span
inside the window over its count."""
from benchmark import prom

UNIT = "ms"
NAME = "tpu_model_span_seconds"


def read(ctx):
    s = prom.delta(ctx.before, ctx.after, NAME + "_sum",
                   span="engine.decode_n")
    n = prom.delta(ctx.before, ctx.after, NAME + "_count",
                   span="engine.decode_n")
    return 1e3 * s / n if s is not None and n else None
