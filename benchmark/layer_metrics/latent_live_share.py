"""Of the positions latent attention's rows were allocated, the share that
holds a live sequence's row: ``tpu_model_latent_positions{what="live"}`` over
``{what="allocated"}`` (a slot's length a latent layer over slots x the served
context, from the engine's host mirror of the lengths), at the scrape that
ends the trace, in the middle of the window (the scrape after the window where
there was no trace). What lies under 100 is what a read of every slot to the
served context would move for nothing, and what the decode kernel's walk over
each slot's own rows does not read. None for a program without the gauge."""
from benchmark import prom

UNIT = "%"
NAME = "tpu_model_latent_positions"


def read(ctx):
    for scrape in (ctx.trace_after, ctx.after):
        by = {d.get("what"): v for d, v in prom.select(scrape, NAME)}
        if by.get("allocated"):
            ctx.notes["latent_positions"] = by
            return 100.0 * by.get("live", 0.0) / by["allocated"]
    return None
