"""The delta rule's state update's share of its roofline: the recurrent state
one decode step has to read and write (every decoding sequence's, once each
way, in every linear layer: the configuration's ``work`` file,
``delta_state_bytes_step``) over the HBM rate, over the device time of one step
under ``delta.update``."""
from benchmark import delta_spans, ssm_spans, work

UNIT = "%"


def read(ctx):
    f_bytes = work.own(ctx.conf, "delta_state_bytes_step")
    if f_bytes is None or not ctx.peaks:
        return None
    update_ms = delta_spans.step_ms(ctx, ("delta.update",))
    batch = ssm_spans.decode_batch(ctx)
    if not update_ms or not batch:
        return None
    least_s = f_bytes(ctx.conf, batch) / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes["delta_state_roofline"] = dict(
        batch=batch, state_bytes=f_bytes(ctx.conf, batch),
        least_ms=1e3 * least_s, update_ms=update_ms)
    return 100.0 * 1e3 * least_s / update_ms
