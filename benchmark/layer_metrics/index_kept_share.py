"""Of the cached positions the window's decode steps had before them, the
share their attention read: ``tpu_model_index_positions_total{what="kept"}``
over ``{what="seen"}`` between the window's two scrapes, in percent. 100 says
the selection slept (no sequence passed ``index_topk`` positions); what lies
under it is the share of the rows' bytes that ``work.py``'s live-position
count prices too high. None for a program without the counter."""
from benchmark import prom

UNIT = "%"
NAME = "tpu_model_index_positions_total"


def read(ctx):
    seen = prom.delta(ctx.before, ctx.after, NAME, what="seen")
    kept = prom.delta(ctx.before, ctx.after, NAME, what="kept")
    if not seen or kept is None:
        return None
    ctx.notes["index_positions"] = dict(seen=seen, kept=kept)
    return 100.0 * kept / seen
