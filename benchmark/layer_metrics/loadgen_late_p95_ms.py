"""How late the load generator sent what it had scheduled: 95th percentile of
(actual send time - due time) over the window's requests. A starved generator
must not read as a fast server."""
from benchmark import stats

UNIT = "ms"


def read(ctx):
    late = [(r.t_sent - r.t_due) * 1e3 for r in ctx.records if r.measured]
    return stats.percentile(late, 0.95) if late else None
