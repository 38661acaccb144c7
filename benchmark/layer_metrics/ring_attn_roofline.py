"""The window layers' share of their roofline where a ring is read as deep as
the live contexts reach: every live position's keys and values in the window
layers' rings once (live tokens at the trace's middle x the configuration's
``work`` file, ``ring_bytes_per_live_position``) over the HBM rate, over the
device time of one step under ``attn.window`` (the ring's row write and the
attention over it). Exact while no served context passes the window: past it
a slot's live positions in a ring stop at the ring's length and the live
tokens price the step too high, so a cell whose contexts pass the window
needs a count of its own. The program reads the attended bucket of the
batch's deepest context for every slot, so the share is what a read of each
slot's own depth would leave. None for a configuration whose ``work`` file
has no such function (K-EXAONE's prices whole rings: ``window_attn_roofline``)
and for a program without the scope."""
from benchmark import window_spans, work

UNIT = "%"


def read(ctx):
    f_bytes = work.own(ctx.conf, "ring_bytes_per_live_position")
    if f_bytes is None or not ctx.peaks or ctx.live_tokens is None:
        return None
    window_ms = window_spans.step_ms(ctx)
    if not window_ms:
        return None
    nbytes = ctx.live_tokens * f_bytes(ctx.conf, ctx.resolved["kv_dtype"])
    least_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes["ring_attn_roofline"] = dict(
        live_tokens=ctx.live_tokens, ring_bytes=nbytes,
        least_ms=1e3 * least_s, window_ms=window_ms)
    return 100.0 * 1e3 * least_s / window_ms
