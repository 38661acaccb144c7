"""What the admission pass and its dispatches say of themselves, between two
scrapes of the program's ``/metrics``: the passes and how many of them
stalled for pages (``tpu_model_admission_passes_total{stalled}``) and an
admission dispatch by part (``tpu_model_admit_dispatch_seconds{part}``). The
per-layer readers that share these live in ``layer_metrics/``; each function
returns ``None`` where the program has no such family and never raises."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark import prom

PASSES = "tpu_model_admission_passes_total"
PARTS = "tpu_model_admit_dispatch_seconds"
STAGES = "tpu_model_request_stage_seconds"
STALLS = "tpu_model_page_stalls_total"


def passes(ctx) -> Optional[Tuple[float, float]]:
    """(passes that stalled for pages, all passes) inside the window."""
    stalled = prom.delta(ctx.before, ctx.after, PASSES, stalled="yes")
    free = prom.delta(ctx.before, ctx.after, PASSES, stalled="no")
    if stalled is None or free is None:
        return None
    return stalled, stalled + free


def part_ms(ctx, part: str) -> Optional[float]:
    """Mean milliseconds of one part of the window's admission dispatches
    (a mean, so that launch + behind + run is the mean dispatch); what it
    divided goes to ``ctx.notes``, with what the three readers share."""
    s = prom.delta(ctx.before, ctx.after, PARTS + "_sum", part=part)
    n = prom.delta(ctx.before, ctx.after, PARTS + "_count", part=part)
    if s is None or not n:
        return None
    if "admit_dispatch" not in ctx.notes:
        ctx.notes["admit_dispatch"] = _shared_note(ctx, n)
    ctx.notes["admit_dispatch"][part + "_s"] = s
    return 1e3 * s / n


def _shared_note(ctx, dispatches: float) -> dict:
    """Dispatches a pass, the passes that stalled and the stalls of every
    cause (0 and 0 on a contiguous cache, whose cells list no reader of
    them), and the mean of the stage the three parts are the parts of, a
    request at a time (an admit_many of m is one dispatch here and m
    requests there)."""
    note = {"dispatches": dispatches}
    both = passes(ctx)
    if both is not None and both[1]:
        note["passes"], note["stalled_passes"] = both[1], both[0]
        note["dispatches_per_pass"] = dispatches / both[1]
        note["page_stalls"] = prom.delta(ctx.before, ctx.after, STALLS)
    st = prom.delta(ctx.before, ctx.after, STAGES + "_sum", stage="prefill")
    sn = prom.delta(ctx.before, ctx.after, STAGES + "_count",
                    stage="prefill")
    if st is not None and sn:
        note["prefill_stage_mean_s"] = st / sn
    return note
