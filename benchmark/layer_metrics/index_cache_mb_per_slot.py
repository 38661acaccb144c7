"""The indexer's keys a slot holds on the device, in MB (1e6 bytes): what the
engine allocated for them beside latent attention's rows
(``tpu_model_cache_bytes{kind="index"}``), over the resolved slots. Keys kept
in another type, or a layer that lost its indexer, show here. None for a
program without the gauge's kind."""
from benchmark import prom

UNIT = "MB"
NAME = "tpu_model_cache_bytes"


def read(ctx):
    by = {d["kind"]: v for d, v in prom.select(ctx.after, NAME)}
    slots = ctx.resolved.get("max_slots")
    if not by.get("index") or not slots:
        return None
    return by["index"] / slots / 1e6
