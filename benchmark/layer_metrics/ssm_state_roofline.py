"""The state update's share of its roofline: the recurrent state one decode
step has to read and write (every decoding sequence's, once each way, in every
Mamba layer: the configuration's ``work`` file, ``ssm_state_bytes_step``) over
the HBM rate, over the device time of one step under ``ssm.scan``."""
from benchmark import ssm_spans, work

UNIT = "%"


def read(ctx):
    f_bytes = work.own(ctx.conf, "ssm_state_bytes_step")
    if f_bytes is None or not ctx.peaks:
        return None
    scan_ms = ssm_spans.step_ms(ctx, ("ssm.scan",))
    batch = ssm_spans.decode_batch(ctx)
    if not scan_ms or not batch:
        return None
    least_s = f_bytes(ctx.conf, batch) / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes["ssm_state_roofline"] = dict(
        batch=batch, state_bytes=f_bytes(ctx.conf, batch),
        least_ms=1e3 * least_s, scan_ms=scan_ms)
    return 100.0 * 1e3 * least_s / scan_ms
