"""Device time of one decode step under the scope a stack of window and full
attention brings (``attn.window``, runtime/trace.py DEVICE_SCOPES: a window
layer's ring write and its attention over the ring). ``trace_spans.py``'s
scope list is the dense cells', ``ssm_spans.py``'s the Mamba-2 mixers' and
``conv_spans.py``'s the short convolutions' (accepted files; tests/ holds the
four lists to be one vocabulary together), so an operation under
``attn.window`` reads in all three as no scope of theirs, and the full
layers' ``attn.core`` stays what ``decode_attn_ms_per_step`` reads. This
reader walks the same trace with the same pieces (``trace_spans.read_planes``,
``self_times``, the decode module and its complete runs) and keeps its own
scope. A trace of a program without it (the parent's, another cell's) reads as
None, never as an error."""

from __future__ import annotations

import os
import statistics
import sys
from typing import Dict, Optional, Tuple

from benchmark import trace_spans

SCOPES = ("attn.window",)

_CACHE: Dict[Tuple[str, float, int], Optional[dict]] = {}


def scope_of(tf_op: str) -> Optional[str]:
    for part in reversed(tf_op.split(";", 1)[0].split("/")):
        if part in SCOPES:
            return part
    return None


def step_seconds(chunk: Optional[int]) -> Optional[Dict[str, float]]:
    """Seconds of one decode step by scope of ``SCOPES``: self time of the
    decode module's operations under each, over the steps of its complete
    runs. None where there is no trace, no decode module, or no operation
    under any."""
    path = trace_spans.find_trace()
    red = trace_spans.reduce()
    if path is None or red is None or not chunk:
        return None
    key = (path, os.path.getmtime(path), chunk)
    if key not in _CACHE:
        try:
            _CACHE[key] = _reduce(path, red, chunk)
        except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
            sys.stderr.write(f"window_spans: {path}: {e!r}\n")
            _CACHE[key] = None
    return _CACHE[key]


def _reduce(path: str, red: dict, chunk: int) -> Optional[Dict[str, float]]:
    mod = trace_spans.decode_module(red)
    runs = red["device"]["runs"].get(mod) if mod else None
    if not runs:
        return None
    med = statistics.median(r["dur"] for r in runs)
    whole = sorted(r["start"] for r in runs if r["dur"] >= 0.9 * med)
    ends = {r["start"]: r["start"] + r["dur"] for r in runs}
    plane = next((p for p in trace_spans.read_planes(path)
                  if p["name"].startswith(trace_spans.DEVICE_PREFIX)
                  and any(ln["name"] == trace_spans.OPS_LINE and ln["events"]
                          for ln in p["lines"])), None)
    if plane is None:
        return None
    ops = next(ln["events"] for ln in plane["lines"]
               if ln["name"] == trace_spans.OPS_LINE)
    meta = plane["meta"]
    total: Dict[str, int] = {}
    wi = 0
    for s, _e, mid, self_ps in trace_spans.self_times(ops):
        while wi < len(whole) and ends[whole[wi]] <= s:
            wi += 1
        if wi == len(whole):
            break
        if whole[wi] > s:
            continue                    # between runs, or in one the edge cut
        sc = scope_of(meta.get(mid, ("?", ""))[1])
        if sc is not None:
            total[sc] = total.get(sc, 0) + self_ps
    if not total:
        return None
    per = 1e-12 / (len(whole) * chunk)
    return {sc: ps * per for sc, ps in total.items()}


def step_ms(ctx) -> Optional[float]:
    """Milliseconds of one decode step under ``attn.window``."""
    by = step_seconds(ctx.resolved.get("decode_chunk"))
    return None if by is None else 1e3 * sum(by.values())
