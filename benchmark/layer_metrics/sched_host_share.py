"""Share of the scheduler's own wall time inside the window that it spent in
host work (not waiting on a dispatch, not idle), by its own account."""
from benchmark import prom

UNIT = "%"
NAME = "tpu_model_breakdown_seconds_total"


def read(ctx):
    host = prom.delta(ctx.before, ctx.after, NAME, phase="host")
    every = prom.delta(ctx.before, ctx.after, NAME)
    return 100.0 * host / every if host is not None and every else None
