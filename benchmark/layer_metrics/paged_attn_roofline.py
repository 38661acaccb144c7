"""The decode attention's share of its roofline: the keys and values one step
has to read (live tokens at the trace's middle x the resolved cache's bytes a
token, benchmark/work.py) over the HBM rate, over the device time of one step
under ``attn.core`` (``decode_attn_ms_per_step``)."""
from benchmark import trace_spans, work

UNIT = "%"


def read(ctx):
    if ctx.live_tokens is None or not ctx.peaks:
        return None
    attn_ms = trace_spans.step_ms(ctx, "attn")
    if not attn_ms:
        return None
    kv_bytes = ctx.live_tokens * work.kv_bytes_per_token(
        ctx.conf, ctx.resolved["kv_dtype"])
    least_s = kv_bytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes["paged_attn_roofline"] = dict(
        live_tokens=ctx.live_tokens, kv_bytes=kv_bytes, least_ms=1e3 * least_s,
        attn_ms=attn_ms)
    return 100.0 * 1e3 * least_s / attn_ms
