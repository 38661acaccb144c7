"""Share of the scheduler's own wall time inside the window that it spent
inside the runtime's launch call: seconds of the program's
``engine.enqueue`` span (one around the call of every compiled program: the
call's own time, and the wait where the runtime holds a launch because its
queue of programs in flight is full) over the scheduler's accounted time,
every phase of ``tpu_model_breakdown_seconds_total``: ``sched_host_share``'s
denominator, and that metric counts this time as host work, so the one
subtracts from the other. Nothing to read from a program without the
span."""
from benchmark import prom

UNIT = "%"
NAME = "tpu_model_span_seconds"


def read(ctx):
    enqueue_s = prom.delta(ctx.before, ctx.after, NAME + "_sum",
                           span="engine.enqueue")
    every = prom.delta(ctx.before, ctx.after,
                       "tpu_model_breakdown_seconds_total")
    if enqueue_s is None or not every:
        return None
    ctx.notes["sched_enqueue"] = dict(
        enqueue_s=enqueue_s, scheduler_s=every,
        calls=prom.delta(ctx.before, ctx.after, NAME + "_count",
                         span="engine.enqueue"))
    return 100.0 * enqueue_s / every
