"""The indexer's share of its roofline: what the indexers of one decode step
have to move (their matrices, every live position's key once, the new keys:
the configuration's ``work`` file, ``index_bytes_step``, at the live tokens of
the trace's middle) over the HBM rate, over the device time of one step under
``attn.index``. The program scores the whole attended bucket of every slot
and sorts it, so the share is what a selection that read live keys alone
would leave."""
from benchmark import index_spans, ssm_spans, work

UNIT = "%"


def read(ctx):
    f_bytes = work.own(ctx.conf, "index_bytes_step")
    if f_bytes is None or not ctx.peaks or ctx.live_tokens is None:
        return None
    index_ms = index_spans.step_ms(ctx)
    batch = ssm_spans.decode_batch(ctx)
    if not index_ms or not batch:
        return None
    nbytes = f_bytes(ctx.conf, batch, ctx.live_tokens,
                     ctx.resolved["weights"], ctx.resolved["kv_dtype"])
    least_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes["index_select_roofline"] = dict(
        batch=batch, live_tokens=ctx.live_tokens, index_bytes=nbytes,
        least_ms=1e3 * least_s, index_ms=index_ms)
    return 100.0 * 1e3 * least_s / index_ms
