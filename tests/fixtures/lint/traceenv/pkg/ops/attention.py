"""trace-env fixture: the sanctioned resolver, and a caller that goes
through it."""

import os

MODES = ("auto", "pallas", "xla")


def _kernels_override():
    env = os.environ.get("OLLAMA_TPU_KERNELS", "")
    if env and env not in MODES:
        raise ValueError(env)
    return env


def resolve_kernels(kernels):
    return _kernels_override() or kernels
