"""Arithmetic of the end-to-end metrics: percentiles with their support
rule, and the reduction of per-request records to the numbers reported.
Times are seconds on the load generator's ``time.perf_counter``."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

MIN_BEYOND = 10     # a percentile needs at least this many samples past it
MAX_SHORT_SHARE = 0.02  # of requests, with fewer text frames than chunks


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation between the order
    statistics, as ``numpy.percentile``'s default does."""
    if not values:
        raise ValueError("percentile of no samples")
    v = sorted(values)
    k = (len(v) - 1) * q
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def beyond(n: int, q: float) -> float:
    """How many of ``n`` samples lie past the ``q`` quantile."""
    return round(n * min(q, 1.0 - q), 9)    # 1 - 0.9 is not 0.1 in binary


def supported(n: int, q: float) -> bool:
    return beyond(n, q) >= MIN_BEYOND


@dataclass
class Record:
    """What the load generator saw of one request."""
    index: int
    measured: bool                  # due (open) or sent (closed) in the window
    t_due: float                    # when it was due; closed loop: when sent
    t_sent: float
    prompt_tokens: int
    output_tokens: int
    frames: List[float] = field(default_factory=list)   # text-bearing frames
    t_done: Optional[float] = None
    eval_count: int = 0
    prompt_eval_count: int = 0
    done_reason: str = ""
    status: int = 0
    error: str = ""
    text: str = ""                  # kept only for the repeat check

    @property
    def ok(self) -> bool:
        return (self.t_done is not None and not self.error
                and self.status == 200
                and self.eval_count == self.output_tokens)


def reduce_records(records: List[Record], t0: float, seconds: float,
                   want: Sequence[str]) -> Dict[str, Dict]:
    """The end-to-end metrics named in ``want`` from the window's records,
    each as {"value", "unit", "n"}. TTFT is from ``t_due`` to the first
    frame that carries text; a gap is between consecutive text frames of one
    request; ``out_tok_s`` is the ``eval_count`` of every request (ramp
    ones too) that finished inside the window, over the window."""
    t1 = t0 + seconds
    meas = [r for r in records if r.measured and r.ok]
    ttft = [(r.frames[0] - r.t_due) * 1e3 for r in meas if r.frames]
    gaps = [(b - a) * 1e3 for r in meas
            for a, b in zip(r.frames, r.frames[1:])]
    done_tok = sum(r.eval_count for r in records
                   if r.ok and t0 <= r.t_done < t1)
    table = {
        "ttft_p50_ms": (ttft, 0.50, "ms"),
        "ttft_p90_ms": (ttft, 0.90, "ms"),
        "ttft_p95_ms": (ttft, 0.95, "ms"),
        "stream_gap_p50_ms": (gaps, 0.50, "ms"),
        "stream_gap_p95_ms": (gaps, 0.95, "ms"),
    }
    out: Dict[str, Dict] = {}
    for name in want:
        if name in table:
            vals, q, unit = table[name]
            if vals:
                out[name] = {"value": percentile(vals, q), "unit": unit,
                             "n": len(vals), "q": q,
                             "supported": supported(len(vals), q)}
        elif name == "out_tok_s":
            out[name] = {"value": done_tok / seconds, "unit": "tokens/s",
                         "n": sum(1 for r in records
                                  if r.ok and t0 <= r.t_done < t1)}
    return out


def frames_check(measured: List[Record], chunk: int) -> Dict[str, float]:
    """Whether the stream's frames carried its text as it was made. The
    scheduler fans tokens out once a decode chunk, so a request of n tokens
    is due ceil(n / chunk) frames with text (and one more where its first
    token comes alone); a request with fewer had text held back, and then
    TTFT and the gaps time the holding back, not the server. ``short_share``
    is the share of finished requests with fewer."""
    ok = [r for r in measured if r.ok]
    due = [-(-r.eval_count // chunk) for r in ok]
    short = sum(1 for r, d in zip(ok, due) if len(r.frames) < d)
    return {"requests": len(ok), "chunk": chunk,
            "text_frames": sum(len(r.frames) for r in ok),
            "frames_due": sum(due), "short": short,
            "short_share": short / max(len(ok), 1)}


def overlap_tok_s(records: List[Record], t0: float, seconds: float) -> float:
    """For the earlier lines, not a metric: tokens a second with every
    finished request's ``eval_count`` shared evenly over the time from its
    first text frame to its end, and the window's share of that time taken.
    ``out_tok_s`` counts whole requests when they end, so which requests
    straddle the window's ends moves it by a few percent; this reading is here
    so that a later benchmark PR can see how much steadier it would be."""
    t1 = t0 + seconds
    tok = 0.0
    for r in records:
        if r.ok and r.frames:
            a, b = r.frames[0], max(r.t_done, r.frames[0] + 1e-9)
            tok += r.eval_count * max(0.0, min(b, t1) - max(a, t0)) / (b - a)
    return tok / seconds


def distributions(records: List[Record]) -> Dict[str, Dict[str, float]]:
    """For the earlier lines, not a metric: where the TTFTs and the gaps
    between text frames of the measured requests lie."""
    meas = [r for r in records if r.measured and r.ok]
    series = {
        "ttft_ms": [(r.frames[0] - r.t_due) * 1e3 for r in meas if r.frames],
        "gap_ms": [(b - a) * 1e3 for r in meas
                   for a, b in zip(r.frames, r.frames[1:])],
        "frames_per_request": [float(len(r.frames)) for r in meas],
    }
    return {k: {f"p{int(q * 100)}": round(percentile(v, q), 2)
                for q in (0.1, 0.5, 0.9, 0.95, 0.99, 1.0)}
            for k, v in series.items() if v}
