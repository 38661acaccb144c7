"""The latent decode kernel's share of its roofline: what a decode step's
absorbed attention has to do for the positions it reads (live tokens at the
trace's middle x the configuration's ``work`` file: the larger of
``latent_bytes_per_live_position`` over the HBM rate and
``latent_flops_per_live_position`` over the bfloat16 peak; at 64 heads a
row's 648 bytes meet 139,264 operations, 215 a byte, a tenth under the
chip's ridge, so either may bound the kernel and both are kept) over the device time of one step inside
the kernel ``latent_decode`` itself (``kernel_spans``: by the operation's
name, without the two einsums beside it under ``attn.core``). Exact for a
model that reads every live position (no indexer); where an indexer keeps a
part, the live tokens price the step too high. None for a configuration whose
``work`` file has neither function and for a program without the kernel."""
from benchmark import kernel_spans, work

UNIT = "%"
KERNEL = "latent_decode"


def read(ctx):
    f_bytes = work.own(ctx.conf, "latent_bytes_per_live_position")
    f_flops = work.own(ctx.conf, "latent_flops_per_live_position")
    if (f_bytes is None or f_flops is None or not ctx.peaks
            or ctx.live_tokens is None):
        return None
    kernel_ms = kernel_spans.step_ms(ctx, KERNEL)
    if not kernel_ms:
        return None
    nbytes = ctx.live_tokens * f_bytes(ctx.conf, ctx.resolved["kv_dtype"])
    flops = ctx.live_tokens * f_flops(ctx.conf)
    bytes_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    flops_s = flops / ctx.peaks["bf16_flops_per_s"]
    least_s = max(bytes_s, flops_s)
    ctx.notes["latent_attn_roofline"] = dict(
        live_tokens=ctx.live_tokens, row_bytes=nbytes, flops=flops,
        bytes_ms=1e3 * bytes_s, flops_ms=1e3 * flops_s,
        least_ms=1e3 * least_s, kernel_ms=kernel_ms)
    return 100.0 * 1e3 * least_s / kernel_ms
