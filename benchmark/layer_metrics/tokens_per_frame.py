"""Tokens the HTTP layer put into one stream frame: generated tokens over
frames written, both counted by the server inside the window."""
from benchmark import prom

UNIT = "tokens"


def read(ctx):
    tok = prom.delta(ctx.before, ctx.after, "tpu_model_generated_tokens_total")
    frames = prom.delta(ctx.before, ctx.after, "tpu_model_stream_frames_total")
    return tok / frames if tok and frames else None
