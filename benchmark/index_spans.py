"""Device time of one decode step under the scope latent attention's indexer
brings (``attn.index``, runtime/trace.py DEVICE_SCOPES: the indexer's
projections, the write of its key, its scores over the attended positions and
the top-k that says which of them attention may read). ``trace_spans.py``'s
scope list is the dense cells', and each hybrid stack's mixer has the reader
that came with it (``ssm_spans``, ``conv_spans``, ``window_spans``,
``delta_spans``; tests/ holds the six lists to be one vocabulary together), so
an operation under ``attn.index`` reads in all five as no scope of theirs, and
latent attention's own ``attn.qkv``, ``attn.kv_write``, ``attn.core`` and
``attn.out`` stay what the accepted readers read. This reader keeps its own
scope and walks the trace with ``delta_spans.step_by_scope``. A trace of a
program without the scope (the parent's, another cell's) reads as None, never
as an error."""

from __future__ import annotations

import os
import sys
from typing import Dict, Optional, Tuple

from benchmark import delta_spans, trace_spans

SCOPES = ("attn.index",)

_CACHE: Dict[Tuple[str, float, int], Optional[dict]] = {}


def step_seconds(chunk: Optional[int]) -> Optional[Dict[str, float]]:
    """Seconds of one decode step by scope of ``SCOPES``; None where there
    is no trace, no decode module, or no operation under any."""
    path = trace_spans.find_trace()
    red = trace_spans.reduce()
    if path is None or red is None or not chunk:
        return None
    key = (path, os.path.getmtime(path), chunk)
    if key not in _CACHE:
        try:
            _CACHE[key] = delta_spans.step_by_scope(path, red, chunk, SCOPES)
        except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
            sys.stderr.write(f"index_spans: {path}: {e!r}\n")
            _CACHE[key] = None
    return _CACHE[key]


def step_ms(ctx) -> Optional[float]:
    """Milliseconds of one decode step under ``attn.index``."""
    by = step_seconds(ctx.resolved.get("decode_chunk"))
    if by is None:
        return None
    return 1e3 * sum(by.values())
