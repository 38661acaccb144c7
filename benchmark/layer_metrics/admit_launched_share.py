"""Share of the requests admitted inside the window whose admission was
launched and not awaited: the prefill was dispatched without a host sync and
its first token collected behind the next decode chunk's launch, so the
device's queue never ran dry for it (the program's
``tpu_model_admissions_total{mode}``, counted by the scheduler a request).
100 where nothing needs the host between a prefill and the first decode step
(plain requests, the loop double-buffered, no speculation); nothing to read
from a program without the counter."""
from benchmark import prom

UNIT = "%"


def read(ctx):
    launched = prom.delta(ctx.before, ctx.after,
                          "tpu_model_admissions_total", mode="launched")
    awaited = prom.delta(ctx.before, ctx.after,
                         "tpu_model_admissions_total", mode="awaited")
    if launched is None:
        return None
    admitted = launched + (awaited or 0.0)
    return 100.0 * launched / admitted if admitted else None
