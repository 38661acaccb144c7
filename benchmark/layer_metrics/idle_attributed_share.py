"""Share of the traced window's device idle time that lies inside a span of
the program on the scheduler's thread, each idle interval shared out over the
innermost spans open meanwhile (benchmark/trace_spans.py). ``ctx.notes`` gets
the idle seconds by span and the longest gaps, each with the span that covers
most of it."""
from benchmark import trace_spans

UNIT = "%"


def read(ctx):
    red = trace_spans.reduce()
    idle = red and red["idle"]
    if not idle or not idle["idle_ps"]:
        return None
    ctx.notes["idle_by_span"] = dict(
        idle_s=idle["idle_ps"] * 1e-12,
        by_span_s={k: v * 1e-12 for k, v in sorted(
            idle["by_span"].items(), key=lambda kv: -kv[1])},
        longest_gaps=[[name, g * 1e-12] for g, name, _sh in idle["longest"]],
        scheduler_line=red["scheduler_line"])
    return 100.0 * idle["attributed_ps"] / idle["idle_ps"]
