"""Reader of the server's ``/metrics`` text: samples by name and labels,
deltas between two scrapes, and a percentile of a histogram's delta."""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')

Key = Tuple[str, Tuple[Tuple[str, str], ...]]


def parse(text: str) -> Dict[Key, float]:
    out: Dict[Key, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if not m:
            continue
        try:
            val = float(m.group(3))
        except ValueError:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        out[(m.group(1), labels)] = val
    return out


def select(samples: Dict[Key, float], name: str, **labels: str
           ) -> List[Tuple[Dict[str, str], float]]:
    """Every sample of ``name`` whose labels include ``labels``."""
    got = []
    for (n, lab), v in samples.items():
        d = dict(lab)
        if n == name and all(d.get(k) == w for k, w in labels.items()):
            got.append((d, v))
    return got


def total(samples: Dict[Key, float], name: str, **labels: str
          ) -> Optional[float]:
    got = select(samples, name, **labels)
    return sum(v for _d, v in got) if got else None


def delta(before: Dict[Key, float], after: Dict[Key, float], name: str,
          **labels: str) -> Optional[float]:
    """after - before of the sum over matching samples; None where the
    later scrape has no such sample."""
    b = total(after, name, **labels)
    if b is None:
        return None
    return b - (total(before, name, **labels) or 0.0)


def hist_percentile(before: Dict[Key, float], after: Dict[Key, float],
                    name: str, q: float, **labels: str) -> Optional[float]:
    """The ``q`` quantile of the observations a histogram took between two
    scrapes, interpolated inside its bucket (upper bucket edges are the
    ``le`` labels). None with no observation in between."""
    edges: Dict[float, float] = {}
    for d, v in select(after, name + "_bucket", **labels):
        le = float("inf") if d["le"] == "+Inf" else float(d["le"])
        edges[le] = edges.get(le, 0.0) + v
    for d, v in select(before, name + "_bucket", **labels):
        le = float("inf") if d["le"] == "+Inf" else float(d["le"])
        edges[le] = edges.get(le, 0.0) - v
    if not edges:
        return None
    les = sorted(edges)
    n = edges[les[-1]]
    if n <= 0:
        return None
    rank = q * n
    lo_edge, lo_cum = 0.0, 0.0
    for le in les:
        cum = edges[le]
        if cum >= rank:
            if le == float("inf"):
                return lo_edge
            share = (rank - lo_cum) / max(cum - lo_cum, 1e-30)
            return lo_edge + (le - lo_edge) * share
        lo_edge, lo_cum = le, cum
    return lo_edge
