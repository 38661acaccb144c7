"""Share of the pages the radix prefix cache evicted that reached the free
list AT ONCE: no slot had mapped the page since the last retired epoch, so
no program in flight could hold it in a block table and the epoch fence let
it go with a chunk in flight (the program's
``tpu_model_radix_evicted_pages_total{fence}``: free / (free + fenced), one
count a page). On a paged pool that the tree keeps full this is how often an
admission pass got its pages without stalling for them; what is left of the
stall reads in ``pass_stalled_share``. Nothing to read from a program
without the counter (a contiguous cache never counts), nor where the window
evicted nothing."""
from benchmark import prom

UNIT = "%"
EVICTED = "tpu_model_radix_evicted_pages_total"


def read(ctx):
    free = prom.delta(ctx.before, ctx.after, EVICTED, fence="free")
    fenced = prom.delta(ctx.before, ctx.after, EVICTED, fence="fenced")
    if free is None or fenced is None or not free + fenced:
        return None
    ctx.notes["evicted_pages"] = dict(free=free, fenced=fenced)
    return 100.0 * free / (free + fenced)
