"""Device time of one decode step under the scopes a stack of gated delta-rule
layers brings (``delta.*``, runtime/trace.py DEVICE_SCOPES). ``trace_spans.py``'s
scope list is the dense cells', ``ssm_spans.py``'s the Mamba-2 mixers',
``conv_spans.py``'s the short convolutions' and ``window_spans.py``'s the
window layers' (accepted files; tests/ holds the five lists to be one
vocabulary together), so an operation under ``delta.update`` reads in all four
as no scope of theirs. This reader keeps its own scope list and walks the same
trace with the same pieces (``trace_spans.read_planes``, ``self_times``, the
decode module and its complete runs); ``step_by_scope`` takes the scope list
as an argument, so the next stack's reader can import it. A trace of a program
without the scopes (the parent's, another cell's) reads as None, never as an
error."""

from __future__ import annotations

import os
import statistics
import sys
from typing import Dict, Optional, Sequence, Tuple

from benchmark import trace_spans

SCOPES = ("delta.in_proj", "delta.conv", "delta.update", "delta.gate_norm",
          "delta.out")

_CACHE: Dict[Tuple[str, float, int], Optional[dict]] = {}


def scope_of(tf_op: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    """The innermost of ``scopes`` an operation's ``tf_op`` stat names."""
    for part in reversed(tf_op.split(";", 1)[0].split("/")):
        if part in scopes:
            return part
    return None


def step_seconds(chunk: Optional[int]) -> Optional[Dict[str, float]]:
    """Seconds of one decode step by ``delta.*`` scope: self time of the
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
            _CACHE[key] = step_by_scope(path, red, chunk, SCOPES)
        except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
            sys.stderr.write(f"delta_spans: {path}: {e!r}\n")
            _CACHE[key] = None
    return _CACHE[key]


def step_by_scope(path: str, red: dict, chunk: int, scopes: Sequence[str]
                  ) -> Optional[Dict[str, float]]:
    """Seconds a decode step by scope of ``scopes``, from the trace at
    ``path`` and its reduction: self time of the operations that lie inside a
    complete run of the decode module (at least 0.9 of the median run: the
    trace's edges cut the others), over those runs' steps."""
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
        sc = scope_of(meta.get(mid, ("?", ""))[1], scopes)
        if sc is not None:
            total[sc] = total.get(sc, 0) + self_ps
    if not total:
        return None
    per = 1e-12 / (len(whole) * chunk)
    return {sc: ps * per for sc, ps in total.items()}


def step_ms(ctx, scopes=SCOPES) -> Optional[float]:
    """Milliseconds of one decode step under ``scopes``; the whole split goes
    to ``ctx.notes``."""
    by = step_seconds(ctx.resolved.get("decode_chunk"))
    if by is None:
        return None
    ctx.notes.setdefault("decode_delta_parts_ms",
                         {k: 1e3 * v for k, v in sorted(by.items())})
    got = [by[s] for s in scopes if s in by]
    return 1e3 * sum(got) if got else None
