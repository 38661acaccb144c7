"""Milliseconds an admission pass stood stalled for pages, on average over
every pass of the window, stalled or not: seconds of the program's
``sched.stall`` span (the chunk in flight drained, its fan-out, the fence's
quiesce) over the passes ``tpu_model_admission_passes_total`` counted. A
stall that a decode step raised (cause ``pool_dry_decode``) counts in the
span too; ``ctx.notes`` has the stalls by cause. Nothing to read from a
program without the span or the counter, nor where no pass admitted
anyone."""
from benchmark import admission_pass, prom

UNIT = "ms"
CAUSES = ("pool_dry_admit", "pool_dry_stitch", "pool_dry_decode")


def read(ctx):
    both = admission_pass.passes(ctx)
    stall_s = prom.delta(ctx.before, ctx.after,
                         "tpu_model_span_seconds_sum", span="sched.stall")
    if both is None or stall_s is None or not both[1]:
        return None
    ctx.notes["page_stalls"] = dict(
        stall_s=stall_s, passes=both[1],
        by_cause={c: prom.delta(ctx.before, ctx.after,
                                admission_pass.STALLS, cause=c)
                  for c in CAUSES})
    return 1e3 * stall_s / both[1]
