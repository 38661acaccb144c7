"""From a profiler trace (``.xplane.pb``) to numbers that need no names inside
the program: the device's busy and idle time, device time per XLA module
(program), the device operations that took most time under the names XLA
printed, and the longest idle gaps, each named by the modules on either side
of it (the program writes no host spans on the profiler's clock yet, so a gap
cannot be attributed to what the host was doing).

A device plane is one whose name starts with ``/device:TPU:``. Its line
"XLA Ops" holds one event per operation run, "XLA Modules" one per program
run. Busy time is the union of the operations' intervals; the window is from
the first device event's start to the last one's end, over all chips; chips
are averaged.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Interval = Tuple[int, int, str]     # start ns, end ns, name


def module_name(raw: str) -> str:
    """``jit__decode_n(1234567)`` -> ``jit__decode_n``: the number is the
    run's fingerprint, not part of the program's name."""
    return re.sub(r"\(\d+\)$", "", raw.strip())


def op_name(raw: str) -> str:
    """XLA prints an operation as its whole HLO line (``%fusion.3 = bf16[..]
    fusion(..), kind=..``); its name is what stands before the ``=``."""
    return raw.split(" = ", 1)[0].strip().lstrip("%")


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    busy, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy


def gaps_ns(intervals: List[Interval]) -> List[Tuple[int, str]]:
    """Idle stretches between consecutive busy stretches, each with the name
    of the interval that ended it and the one that follows."""
    out, cur_e, cur_name = [], None, ""
    for s, e, name in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((s - cur_e, f"{cur_name} -> {name}"))
        if cur_e is None or e > cur_e:
            cur_e, cur_name = e, name
    return out


def _lines(plane) -> Dict[str, List[Interval]]:
    out: Dict[str, List[Interval]] = {}
    for line in plane.lines:
        evs = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                str(ev.name)) for ev in line.events]
        out.setdefault(line.name, []).extend(evs)
    return out


def reduce_planes(planes, device_prefix: str = DEVICE_PREFIX) -> dict:
    """The reduction proper, over objects with ``.name`` and ``.lines``."""
    devices = [p for p in planes if p.name.startswith(device_prefix)]
    if not devices:
        return {"error": "no device plane in the trace",
                "planes": [p.name for p in planes]}
    per_dev = [_lines(p) for p in devices]
    starts = [s for d in per_dev for evs in d.values() for s, _e, _n in evs]
    ends = [e for d in per_dev for evs in d.values() for _s, e, _n in evs]
    if not starts:
        return {"error": "the device planes hold no event"}
    window = max(ends) - min(starts)
    busy, op_time, mod_time, mod_durs, gap_time = [], {}, {}, {}, {}
    for d in per_dev:
        ops = d.get(OPS_LINE, [])
        mods = [(s, e, module_name(n)) for s, e, n in d.get(MODULES_LINE, [])]
        busy.append(union_ns((s, e) for s, e, _n in ops))
        for s, e, n in ops:
            n = op_name(n)
            op_time[n] = op_time.get(n, 0) + (e - s)
        for s, e, n in mods:
            mod_time[n] = mod_time.get(n, 0) + (e - s)
            mod_durs.setdefault(n, []).append(e - s)
        # a gap is named by the programs around it; where the trace has no
        # module line, by the operations
        for g, name in gaps_ns(mods or ops):
            gap_time.setdefault(name, []).append(g)
    n = len(devices)

    def top(d: Dict[str, float]) -> List[List]:
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    longest = sorted(((max(v), k) for k, v in gap_time.items()),
                     reverse=True)[:TOP]
    return {
        "devices": n,
        "busy_s": sum(busy) / n / 1e9,
        "window_s": window / 1e9,
        "modules": {k: {"seconds": v / n / 1e9,
                        "runs": len(mod_durs[k]) / n,
                        "median_run_s": statistics.median(mod_durs[k]) / 1e9}
                    for k, v in sorted(mod_time.items(),
                                       key=lambda kv: -kv[1])},
        "device_ops": top(op_time),
        "idle_gaps": [[name, g / 1e9] for g, name in longest],
        "idle_gap_totals": top({k: float(sum(v))
                                for k, v in gap_time.items()}),
        "lines": sorted({ln for d in per_dev for ln in d}),
        "planes": [p.name for p in planes],
    }


def reduce_file(path: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(list(ProfileData.from_file(path).planes),
                         device_prefix)


def reduce_dir(trace_dir: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    """The newest ``.xplane.pb`` under a profiler output directory. (The
    rehearsal on the CPU passes the host's plane as the device, to run the
    same code; nothing it reads there is reported.)"""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        return {"error": f"no .xplane.pb under {trace_dir}"}
    out = reduce_file(found[-1], device_prefix)
    out["trace_bytes"] = os.path.getsize(found[-1])
    return out


if __name__ == "__main__":
    import json
    import sys
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
