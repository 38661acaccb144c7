"""Share of the traced window in which the device stood idle BECAUSE the
scheduler had stalled for pages: the device's idle intervals (between the
busy stretches of ``trace_spans``' reduction) laid over the ``sched.stall``
spans of the scheduler's thread, on the trace's one clock, over the window
``device_idle_share`` divides by. A stall counts wherever it is open, whatever
span is innermost meanwhile (the wait for the drained chunk, its fan-out, a
release), unlike ``idle_attributed_share``'s innermost rule; so it is at most
``device_idle_share``, and what is left of that is idle the stall does not
explain. 0 where the program has the span and the trace holds none. Nothing to
read without a trace, from a program without the span, or where the trace
has no scheduler thread."""
from benchmark import prom, trace_spans

UNIT = "%"
SPAN = "sched.stall"


def overlap_ps(busy, stalls) -> int:
    """Picoseconds of the gaps between consecutive ``busy`` stretches that
    lie inside one of ``stalls`` (start, end; sorted, not nested)."""
    total, i = 0, 0
    for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
        while i < len(stalls) and stalls[i][1] <= e0:
            i += 1
        j = i
        while j < len(stalls) and stalls[j][0] < s1:
            total += max(min(stalls[j][1], s1) - max(stalls[j][0], e0), 0)
            j += 1
    return total


def read(ctx):
    t = ctx.trace or {}
    if not t.get("window_s") or prom.total(
            ctx.after, "tpu_model_span_seconds_count", span=SPAN) is None:
        return None
    path, red = trace_spans.find_trace(), trace_spans.reduce()
    if path is None or not red or red["scheduler_line"] is None:
        return None
    spans = trace_spans.host_spans(trace_spans.read_planes(path))
    stalls = sorted((s, e) for s, e, name in spans[red["scheduler_line"]]
                    if name == SPAN)
    idle_s = overlap_ps(red["device"]["busy"], stalls) * 1e-12
    ctx.notes["stall_idle"] = dict(
        idle_in_stall_s=idle_s, window_s=t["window_s"], stalls=len(stalls),
        stall_s=sum(e - s for s, e in stalls) * 1e-12)
    return 100.0 * idle_s / t["window_s"]
