"""trace-env fixture: a second scoped package; a function that merely
shares the resolver's name elsewhere is not sanctioned."""

import os


def _kernels_override():
    return os.environ["TPU_FIX_FUSED"]
