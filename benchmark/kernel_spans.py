"""Device time of one decode step inside a kernel, by the kernel's own name:
the ``name=`` of its ``pl.pallas_call`` site (runtime/trace.py KERNEL_NAMES),
which a device trace carries as a part of the operation's scope path
(``.../attn.core/latent_decode/pallas_call:``), under whichever scope it ran.
The scope readers (``trace_spans``, ``ssm_spans``, ``conv_spans``,
``window_spans``, ``delta_spans``, ``index_spans``) sum everything under a
scope; a kernel's roofline wants the kernel's time alone, without the einsums
beside it under the same scope. The walk is ``delta_spans.step_by_scope``, as
``index_spans`` uses it, with the kernel's name for the scope. A trace of a
program without the kernel (the parent's, another cell's) reads as None, never
as an error."""

from __future__ import annotations

import os
import sys
from typing import Dict, Optional, Tuple

from benchmark import delta_spans, trace_spans

_CACHE: Dict[Tuple[str, float, int, str], Optional[dict]] = {}


def step_seconds(chunk: Optional[int], kernel: str) -> Optional[float]:
    """Seconds of one decode step inside ``kernel``: self time of the decode
    module's operations whose path names it, over the steps of its complete
    runs. None where there is no trace, no decode module, or no such
    operation."""
    path = trace_spans.find_trace()
    red = trace_spans.reduce()
    if path is None or red is None or not chunk:
        return None
    key = (path, os.path.getmtime(path), chunk, kernel)
    if key not in _CACHE:
        try:
            _CACHE[key] = delta_spans.step_by_scope(path, red, chunk,
                                                    (kernel,))
        except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
            sys.stderr.write(f"kernel_spans: {path}: {e!r}\n")
            _CACHE[key] = None
    by = _CACHE[key]
    return None if by is None else by[kernel]


def step_ms(ctx, kernel: str) -> Optional[float]:
    """Milliseconds of one decode step inside ``kernel``."""
    s = step_seconds(ctx.resolved.get("decode_chunk"), kernel)
    return None if s is None else 1e3 * s
