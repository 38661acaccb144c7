"""Share of the decode steps launched inside the window whose sampler was an
argmax: no live slot sampled at a temperature above zero, so the step
skipped the top-1024 candidate sort of the vocabulary (the program's
``tpu_model_decode_steps_total{sampler}``, counted by the engine a chunk).
100 on greedy traffic; nothing to read from a program without the counter."""
from benchmark import prom

UNIT = "%"


def read(ctx):
    argmax = prom.delta(ctx.before, ctx.after,
                        "tpu_model_decode_steps_total", sampler="argmax")
    candidates = prom.delta(ctx.before, ctx.after,
                            "tpu_model_decode_steps_total",
                            sampler="candidates")
    if argmax is None:
        return None
    steps = argmax + (candidates or 0.0)
    return 100.0 * argmax / steps if steps else None
