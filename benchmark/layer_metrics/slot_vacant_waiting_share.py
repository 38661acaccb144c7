"""Share of the window's slot-seconds in which a decode slot stood free
while a request waited for admission: the scheduler's own integral, free
slots x time between its iterations, over max_slots x time."""
from benchmark import prom

UNIT = "%"


def read(ctx):
    vacant = prom.delta(ctx.before, ctx.after,
                        "tpu_model_slot_vacant_seconds_total",
                        queue="waiting")
    every = prom.delta(ctx.before, ctx.after, "tpu_model_slot_seconds_total")
    return 100.0 * vacant / every if vacant is not None and every else None
