"""The one traffic generator: a mix file plus a seed gives the requests.

A mix is data (``traffic/<name>.json``); nothing here knows a mix by name.
Every seed gets the SAME multiset of prompt lengths, output lengths and
arrival gaps (the evenly spaced quantiles of the mix's distributions) in
another order, so two seeds offer the same work and differ only in how it
interleaves. Lengths are in tokens; ``prompt_text`` turns a length into text
that the byte-fallback tokenizer encodes one token a byte.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("closed", "open")       # "sessions" and "replay" have room here
LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)


@dataclass(frozen=True)
class Request:
    index: int
    prompt_tokens: int
    output_tokens: int
    due_s: Optional[float]       # open loop: seconds from the window's start


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic mix {name!r}: kind {mix.get('kind')!r} "
                         f"is not one of {KINDS}")
    return mix


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a length distribution, as whole
    tokens clipped to the spec's [lo, hi]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(spec["lo"]), float(spec["hi"])
    dist = spec["dist"]
    if dist == "uniform":
        x = lo + u * (hi - lo)
    elif dist == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(v)) for v in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def exponential_gaps(n: int, total_s: float) -> np.ndarray:
    """``n`` inter-arrival gaps with the exponential distribution's shape
    (its evenly spaced quantiles), scaled to sum to ``total_s``: a Poisson
    process whose count over the run is the same for every seed."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (total_s / g.sum())


def fit_context(prompt: np.ndarray, output: np.ndarray,
                max_seq_len: int) -> np.ndarray:
    """Prompts shortened so that prompt + output fits the served context
    (16 positions spare for BOS and the tokenizer's prefix)."""
    return np.minimum(prompt, max_seq_len - output - 16)


def make_requests(mix: dict, seed: int, *, max_seq_len: int,
                  seconds: float, rate_rps: Optional[float] = None
                  ) -> List[Request]:
    """The run's requests, in the order they are sent.

    Open loop: every request due from ``-ramp_seconds`` to ``seconds``
    (those due before 0 are the ramp: sent, not measured). Closed loop:
    ``pool`` requests in blocks of ``block`` that the clients draw from in order; ``due_s`` is
    None (a client sends its next when its last ends)."""
    rng = np.random.default_rng([int(seed), 0x7ff1c])

    def part(n: int, due: Optional[np.ndarray], first: int) -> List[Request]:
        prompt = quantiles(mix["prompt_tokens"], n)[rng.permutation(n)]
        output = quantiles(mix["output_tokens"], n)[rng.permutation(n)]
        prompt = fit_context(prompt, output, max_seq_len)
        if (prompt < 1).any():
            raise ValueError("the mix's outputs leave no room for a prompt "
                             f"in a context of {max_seq_len}")
        return [Request(first + i, int(prompt[i]), int(output[i]),
                        None if due is None else float(due[i]))
                for i in range(n)]

    if mix["kind"] == "closed":
        # block after block, each the same evenly spaced lengths in a seeded
        # order: whatever stretch of the pool a run gets through, it holds
        # the same mix of work under every seed
        block = int(mix["block"])
        out: List[Request] = []
        while len(out) < int(mix["pool"]):
            out.extend(part(block, None, len(out)))
        return out
    if not rate_rps or rate_rps <= 0:
        raise ValueError("an open-loop mix needs the cell's rate_rps")

    def arrivals(span: float, start: float) -> np.ndarray:
        n = max(1, int(round(rate_rps * span)))
        gaps = exponential_gaps(n, span)[rng.permutation(n)]
        return start + np.cumsum(gaps) - gaps

    # the ramp and the window are drawn apart, so that the window holds the
    # same count, lengths and gaps under every seed
    ramp_s = float(mix["ramp_seconds"])
    ramp = arrivals(ramp_s, -ramp_s)
    window = arrivals(float(seconds), 0.0)
    return (part(len(ramp), ramp, 0)
            + part(len(window), window, len(ramp)))


def ramp_output(req: Request, seed: int) -> int:
    """Closed loop only: a client's first request is cut to a seeded share
    of its output length, so the clients start out of step and the window
    opens on a mixed batch instead of one that ends all at once."""
    share = np.random.default_rng([int(seed), 0x4a3b, req.index]).uniform(
        0.1, 1.0)
    return max(8, int(req.output_tokens * share))


def prompt_text(n_tokens: int, overhead: int, seed: int, index: int) -> str:
    """Seeded lowercase text that encodes to ``n_tokens`` on the byte
    vocabulary (``overhead`` = the tokens the tokenizer adds by itself).
    Every request's text is its own: nothing is shared between prompts."""
    rng = np.random.default_rng([int(seed), 0x51ed, int(index)])
    return bytes(rng.choice(LETTERS, max(1, n_tokens - overhead))).decode()
