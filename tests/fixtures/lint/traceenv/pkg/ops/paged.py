"""trace-env fixture: a kernel chosen by a variable at trace time (what
PR 31 removed), a bare-imported getter, and a suppressed read."""

import os
from os import getenv


def paged_decode_attention(q):
    if os.environ.get("TPU_FIX_KERNEL", "1") == "1":
        return q
    depth = int(getenv("TPU_FIX_DEPTH") or 2)
    return q * depth


def debug_dump(q):
    # lint: allow(trace-env): fixture exercises suppression
    return os.getenv("TPU_FIX_DUMP_DIR"), q
