"""Programs compiled inside the measured window (a signature the warm plan
did not cover). Expected 0: nothing compiles while measuring."""
from benchmark import prom

UNIT = "count"


def read(ctx):
    return prom.delta(ctx.before, ctx.after, "tpu_model_recompiles_total")
