"""The window attention's share of its roofline: the rings one decode step has
to read (every decoding sequence's, every window layer's, keys and values at
the resolved cache's bytes a position: the configuration's ``work`` file,
``window_bytes_step``) over the HBM rate, over the device time of one step
under ``attn.window``."""
from benchmark import ssm_spans, window_spans, work

UNIT = "%"


def read(ctx):
    f_bytes = work.own(ctx.conf, "window_bytes_step")
    if f_bytes is None or not ctx.peaks:
        return None
    window_ms = window_spans.step_ms(ctx)
    batch = ssm_spans.decode_batch(ctx)
    if not window_ms or not batch:
        return None
    nbytes = f_bytes(ctx.conf, batch, ctx.resolved["kv_dtype"])
    least_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes["window_attn_roofline"] = dict(
        batch=batch, ring_bytes=nbytes, least_ms=1e3 * least_s,
        window_ms=window_ms)
    return 100.0 * 1e3 * least_s / window_ms
