"""Keys and values a slot holds on the device, in MB (1e6 bytes): the
full-length rows (or pages) and the window layers' rings, as the engine
allocated them (``tpu_model_cache_bytes{kind="full"|"window"}``), over the
resolved slots. A window layer that gets a full-length row again shows here.
None for a program without the gauge."""
from benchmark import prom

UNIT = "MB"
NAME = "tpu_model_cache_bytes"


def read(ctx):
    by = {d["kind"]: v for d, v in prom.select(ctx.after, NAME)}
    slots = ctx.resolved.get("max_slots")
    if "full" not in by or not slots:
        return None
    ctx.notes["cache_bytes"] = by
    return (by["full"] + by.get("window", 0.0)) / slots / 1e6
