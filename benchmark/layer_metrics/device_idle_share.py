"""Share of the traced window in which no operation ran on the device: 1 -
the union of the device operations' intervals over the window."""

UNIT = "%"


def read(ctx):
    t = ctx.trace or {}
    if not t.get("window_s") or t.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
