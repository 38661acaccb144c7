"""Recurrent state a slot holds on the device, in MB (1e6 bytes): what the
engine allocated beside keys and values for the stack's recurrent layers
(``tpu_model_cache_bytes{kind="state"}``: a delta layer's matrices and its
convolution's carried inputs, float32), over the resolved slots. A state kept
in another type, or a layer that lost its state, shows here. None for a
program without the gauge or a stack without such state."""
from benchmark import prom

UNIT = "MB"
NAME = "tpu_model_cache_bytes"


def read(ctx):
    by = {d["kind"]: v for d, v in prom.select(ctx.after, NAME)}
    slots = ctx.resolved.get("max_slots")
    if not by.get("state") or not slots:
        return None
    ctx.notes.setdefault("cache_bytes", by)
    return by["state"] / slots / 1e6
