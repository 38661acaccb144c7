"""Share of the decode chunks launched behind a chunk in flight that came
too late: everything queued before them (the chunk in flight, the pass's
prefills) had already run when the launch returned, so the device stood dry
until then (the program's ``tpu_model_decode_launches_total{timing}``:
late / (ahead + late), the scheduler asking the handle queued last, with no
sync of its own). Launches with no chunk in flight at all, timing="empty"
(the first chunk; every chunk of a paged cell, whose pass drains the
pipeline for pages), are left out. Nothing to read from a program without
the counter, nor where no chunk was launched behind another."""
from benchmark import prom

UNIT = "%"


def read(ctx):
    ahead = prom.delta(ctx.before, ctx.after,
                       "tpu_model_decode_launches_total", timing="ahead")
    late = prom.delta(ctx.before, ctx.after,
                      "tpu_model_decode_launches_total", timing="late")
    if ahead is None or late is None:
        return None
    behind = ahead + late
    return 100.0 * late / behind if behind else None
