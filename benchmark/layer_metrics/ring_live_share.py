"""Of the positions the window layers' rings were allocated, the share that
holds a live sequence's keys and values: ``tpu_model_ring_positions
{what="live"}`` over ``{what="allocated"}`` (min(a slot's length, the ring's
length) a slot a window layer, from the engine's host mirror of the lengths),
at the scrape that ends the trace, in the middle of the window (the scrape
after the window where there was no trace). What lies under 100 is what a
read of the whole ring would move for nothing, and what a ring no longer than
the contexts served would not allocate. None for a program without the
gauge."""
from benchmark import prom

UNIT = "%"
NAME = "tpu_model_ring_positions"


def read(ctx):
    for scrape in (ctx.trace_after, ctx.after):
        by = {d.get("what"): v for d, v in prom.select(scrape, NAME)}
        if by.get("allocated"):
            ctx.notes["ring_positions"] = by
            return 100.0 * by.get("live", 0.0) / by["allocated"]
    return None
