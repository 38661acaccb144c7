"""From a profiler trace (``.xplane.pb``) to numbers that need the names the
program writes: device time by named scope, and device idle time by the host
span that was open meanwhile.

``reduce_trace.py`` reads what needs no names (busy, idle, time per program).
This module reads the two vocabularies of ``ollama_operator_tpu/runtime/
trace.py``:

- **Device scopes** (``jax.named_scope`` around the fixed parts of a model
  step). The profiler keeps a device operation's ``op_name`` in the ``tf_op``
  stat of its *event metadata* (``jit(_decode_n)/while/body/.../attn.core/
  dot_general``), which ``jax.profiler.ProfileData`` does not show, so the
  file is read here from its wire format (the XSpace schema of
  ``tsl/profiler/protobuf/xplane.proto``; no dependency). An operation's
  scope is the innermost known scope in that path. An operation that
  contains others on its line (``while``, ``call``, ``conditional``) counts
  only its **self time**: what its children do not cover.
- **Host spans** (``jax.profiler.TraceAnnotation`` events in the
  ``/host:CPU`` plane, on the device planes' clock). Each device idle
  interval is shared out over the innermost spans open on the scheduler's
  thread (the host line with most ``sched.*`` time) while it lasted.

Where a trace holds no such name (an older program, a CPU rehearsal) the
functions return ``None`` or empty tables and never raise.

    python3 -m benchmark.trace_spans <trace_dir or .xplane.pb>
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys
import tempfile
from typing import Dict, Iterator, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

# the program's device scopes (runtime/trace.py DEVICE_SCOPES; tests/ holds
# the two lists equal) and the prefixes of its host spans
SCOPES = ("embed", "attn.qkv", "attn.kv_write", "attn.core", "attn.out",
          "mlp", "moe.route", "moe.experts", "lm_head", "sample")
SPAN_PREFIXES = ("http.", "sched.", "engine.")
REST = "(no scope)"
NO_SPAN = "(no span)"

Interval = Tuple[int, int, str]     # start ps, end ps, name


# -- the wire format ---------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for varint
    and fixed fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        no, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield no, wt, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf) -> Tuple[int, object]:
    key, val = 0, b""
    for no, _wt, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            val = v
    return key, val


def _event(buf) -> Tuple[int, int, int]:
    """XEvent: (metadata id, offset ps, duration ps); its stats are skipped."""
    mid = off = dur = 0
    for no, _wt, v in _fields(buf):
        if no == 1:
            mid = v
        elif no == 2:
            off = v
        elif no == 3:
            dur = v
    return mid, off, dur


def _line(buf) -> dict:
    """XLine: name, timestamp ns and the raw events."""
    out = {"name": "", "display": "", "t_ns": 0, "events": []}
    for no, _wt, v in _fields(buf):
        if no == 2:
            out["name"] = _text(v)
        elif no == 11:
            out["display"] = _text(v)
        elif no == 3:
            out["t_ns"] = v
        elif no == 4:
            out["events"].append(v)
    return out


def _event_metadata(buf, stat_names: Dict[int, str]) -> Tuple[str, str]:
    """XEventMetadata: (name, its ``tf_op`` stat or "")."""
    name, tf_op = "", ""
    for no, _wt, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 5:
            sid, sval = 0, None
            for sno, _swt, sv in _fields(v):
                if sno == 1:
                    sid = sv
                elif sno == 5:
                    sval = sv
            if sval is not None and stat_names.get(sid) == "tf_op":
                tf_op = _text(sval)
    return name, tf_op


def read_planes(path: str) -> List[dict]:
    """The planes of an ``.xplane.pb``: name, lines (name, events as (start
    ps, end ps, metadata id)) and metadata (id -> (name, tf_op))."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for no, _wt, pbuf in _fields(space):
        if no != 1:
            continue
        name, lines, emeta, smeta = "", [], [], {}
        for pno, _pwt, v in _fields(pbuf):
            if pno == 2:
                name = _text(v)
            elif pno == 3:
                lines.append(v)
            elif pno == 4:
                emeta.append(v)
            elif pno == 5:
                sid, sbuf = _map_entry(v)
                for sno, _swt, sv in _fields(sbuf):
                    if sno == 2:
                        smeta[sid] = _text(sv)
        if not (name.startswith(DEVICE_PREFIX) or name == HOST_PLANE):
            continue
        meta = {}
        for v in emeta:
            mid, mbuf = _map_entry(v)
            meta[mid] = _event_metadata(mbuf, smeta)
        out_lines = []
        for lbuf in lines:
            ln = _line(lbuf)
            base = ln["t_ns"] * 1000
            evs = []
            for ebuf in ln["events"]:
                mid, off, dur = _event(ebuf)
                evs.append((base + off, base + off + dur, mid))
            out_lines.append({"name": ln["name"] or ln["display"],
                              "events": evs})
        planes.append({"name": name, "lines": out_lines, "meta": meta})
    return planes


# -- device time by scope ----------------------------------------------------

def scope_of(tf_op: str) -> str:
    """The innermost known scope in an operation's ``op_name`` path (XLA
    joins the paths of merged operations with ``;``: the first stands)."""
    for part in reversed(tf_op.split(";", 1)[0].split("/")):
        if part in SCOPES:
            return part
    return REST


def op_name(raw: str) -> str:
    return raw.split(" = ", 1)[0].strip().lstrip("%")


def module_name(raw: str) -> str:
    raw = raw.strip()
    return raw[:raw.rindex("(")] if raw.endswith(")") and "(" in raw else raw


def self_times(events: List[Tuple[int, int, object]]
               ) -> List[Tuple[int, int, object, int]]:
    """(start, end, key, self time) of each event of ONE line: an event that
    contains later ones counts only what they do not cover."""
    out: List[List] = []
    stack: List[List] = []
    for s, e, key in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        row = [s, e, key, e - s]
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append(row)
        out.append(row)
    return [(s, e, k, max(t, 0)) for s, e, k, t in out]


def device_scopes(plane: dict) -> Optional[dict]:
    """One device plane: per module, self time by scope and by operation,
    each module run's totals, and the busy intervals."""
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    ops = lines.get(OPS_LINE)
    if not ops:
        return None
    meta = plane["meta"]
    mods = sorted((s, e, module_name(meta.get(mid, ("?", ""))[0]))
                  for s, e, mid in lines.get(MODULES_LINE, []))
    rows = self_times(ops)
    by_mod: Dict[str, dict] = {}
    runs: Dict[str, List[dict]] = {}
    mi = 0
    for s, e, mid, self_ps in rows:
        while mi < len(mods) and mods[mi][1] <= s:
            mi += 1
        inside = mi < len(mods) and mods[mi][0] <= s
        mod = mods[mi][2] if inside else "(no module)"
        raw, tf_op = meta.get(mid, ("?", ""))
        sc = scope_of(tf_op)
        m = by_mod.setdefault(mod, {"scopes": {}, "ops": {}})
        m["scopes"][sc] = m["scopes"].get(sc, 0) + self_ps
        o = m["ops"].setdefault(op_name(raw), [0, sc])
        o[0] += self_ps
        if inside:
            rr = runs.setdefault(mod, [])
            if not rr or rr[-1]["start"] != mods[mi][0]:
                rr.append({"start": mods[mi][0],
                           "dur": mods[mi][1] - mods[mi][0], "scopes": {}})
            sd = rr[-1]["scopes"]
            sd[sc] = sd.get(sc, 0) + self_ps
    busy, cur_s, cur_e = [], None, None
    for s, e, _k in sorted(ops):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy.append((cur_s, cur_e))
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    busy.append((cur_s, cur_e))
    return {"modules": by_mod, "runs": runs, "busy": busy,
            "named": any(tf for _n, tf in meta.values())}


# -- device idle by host span ------------------------------------------------

def host_spans(planes: List[dict]) -> Dict[str, List[Interval]]:
    """The program's spans in the host plane, by thread line."""
    out: Dict[str, List[Interval]] = {}
    for p in planes:
        if p["name"] != HOST_PLANE:
            continue
        for i, ln in enumerate(p["lines"]):
            evs = []
            for s, e, mid in ln["events"]:
                name = p["meta"].get(mid, ("", ""))[0]
                if name.startswith(SPAN_PREFIXES):
                    evs.append((s, e, name))
            if evs:
                out[f"{ln['name']}#{i}"] = evs
    return out


def innermost(spans: List[Interval]) -> List[Interval]:
    """Flatten one thread's nested spans into consecutive stretches, each
    named by the innermost span open there."""
    out: List[Interval] = []
    stack: List[Interval] = []

    def emit(a: int, b: int) -> None:
        if stack and b > a:
            out.append((a, b, stack[-1][2]))

    cur = None
    for s, e, name in sorted(spans, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            emit(cur, stack[-1][1])
            cur = stack.pop()[1]
        if stack:
            emit(cur, s)
        cur = s
        stack.append((s, e, name))
    while stack:
        emit(cur, stack[-1][1])
        cur = stack.pop()[1]
    return out


def idle_by_span(busy: List[Tuple[int, int]], flat: List[Interval]) -> dict:
    """Share each idle interval between busy stretches out over the flat
    span timeline; what no span covers goes to NO_SPAN."""
    totals: Dict[str, int] = {}
    gaps = []
    fi = 0
    for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
        if s1 <= e0:
            continue
        while fi < len(flat) and flat[fi][1] <= e0:
            fi += 1
        shares: Dict[str, int] = {}
        j, covered = fi, 0
        while j < len(flat) and flat[j][0] < s1:
            ov = min(flat[j][1], s1) - max(flat[j][0], e0)
            if ov > 0:
                shares[flat[j][2]] = shares.get(flat[j][2], 0) + ov
                covered += ov
            j += 1
        if s1 - e0 > covered:
            shares[NO_SPAN] = s1 - e0 - covered
        for k, v in shares.items():
            totals[k] = totals.get(k, 0) + v
        gaps.append((s1 - e0, max(shares, key=shares.get), shares))
    idle = sum(totals.values())
    return {"idle_ps": idle,
            "attributed_ps": idle - totals.get(NO_SPAN, 0),
            "by_span": totals,
            "longest": sorted(gaps, key=lambda g: -g[0])[:TOP]}


# -- one reduction a trace ---------------------------------------------------

def find_trace(where: Optional[str] = None) -> Optional[str]:
    """The newest ``.xplane.pb``: under ``where`` (a profiler output
    directory or the file itself), else under the newest ``bench-trace-*``
    of the temporary directory, where ``run.py`` keeps its trace until the
    readers have run."""
    if where and os.path.isfile(where):
        return where
    roots = ([where] if where else
             glob.glob(os.path.join(tempfile.gettempdir(), "bench-trace-*")))
    found = [f for r in roots for f in glob.glob(
        os.path.join(r, "plugins", "profile", "*", "*.xplane.pb"))]
    return max(found, key=os.path.getmtime) if found else None


_CACHE: Dict[Tuple[str, float], Optional[dict]] = {}


def reduce(where: Optional[str] = None) -> Optional[dict]:
    """Device time by scope (first device plane that has operations) and
    idle by span, or None where there is no trace or no device plane."""
    path = find_trace(where)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        try:
            _CACHE[key] = reduce_planes(read_planes(path))
        except Exception as e:  # noqa: BLE001 — a reader returns None, never raises
            sys.stderr.write(f"trace_spans: {path}: {e!r}\n")
            _CACHE[key] = None
    return _CACHE[key]


def reduce_planes(planes: List[dict]) -> Optional[dict]:
    dev = None
    for p in planes:
        if p["name"].startswith(DEVICE_PREFIX):
            dev = device_scopes(p)
            if dev is not None:
                break
    if dev is None:
        return None
    threads = host_spans(planes)
    sched = max(threads, default=None, key=lambda t: sum(
        e - s for s, e, n in threads[t] if n.startswith("sched.")))
    idle = (idle_by_span(dev["busy"], innermost(threads[sched]))
            if sched is not None else None)
    return {"device": dev, "idle": idle, "scheduler_line": sched,
            "span_threads": len(threads)}


def decode_module(red: dict) -> Optional[str]:
    """The decode program: the module with "decode" in its name that took
    most device time (``decode_step_roofline`` picks it the same way)."""
    mods = red["device"]["modules"]
    named = [m for m in mods if "decode" in m]
    return max(named, key=lambda m: sum(mods[m]["scopes"].values()),
               default=None)


def decode_step_parts(red: Optional[dict], chunk: Optional[int]
                      ) -> Optional[dict]:
    """Seconds of one decode step by scope, from the module's complete runs
    (a run the trace's edge cut is shorter than the median run): the mean of
    their self time by scope over the steps of a run. None where the trace
    has no decode module or its operations carry no scope."""
    if not red or not chunk or not red["device"]["named"]:
        return None
    mod = decode_module(red)
    runs = red["device"]["runs"].get(mod) if mod else None
    if not runs:
        return None
    med = statistics.median(r["dur"] for r in runs)
    whole = [r for r in runs if r["dur"] >= 0.9 * med]
    parts: Dict[str, float] = {}
    for r in whole:
        for sc, ps in r["scopes"].items():
            parts[sc] = parts.get(sc, 0.0) + ps
    if not any(sc != REST for sc in parts):
        return None
    per = 1e-12 / (len(whole) * chunk)
    return {"module": mod, "runs": len(whole),
            "device_step_s": med * 1e-12 / chunk,
            "by_scope_s": {sc: ps * per for sc, ps in parts.items()}}


# the scopes each per-step metric sums (dequantisation runs under the
# matmuls' scopes)
GROUPS = {"attn": ("attn.core",),
          "matmul": ("attn.qkv", "attn.out", "mlp", "moe.route",
                     "moe.experts", "lm_head"),
          "sample": ("sample",)}


def step_ms(ctx, group: str) -> Optional[float]:
    """Milliseconds of one decode step under a group of GROUPS, for the
    per-layer readers; the whole split goes to ``ctx.notes``."""
    red = reduce()
    parts = decode_step_parts(red, ctx.resolved.get("decode_chunk"))
    if parts is None:
        return None
    by = parts["by_scope_s"]
    split = {g: 1e3 * sum(by.get(s, 0.0) for s in ss)
             for g, ss in GROUPS.items()}
    ctx.notes.setdefault("decode_step_parts", dict(
        module=parts["module"], runs=parts["runs"],
        device_step_ms=1e3 * parts["device_step_s"],
        **{g + "_ms": v for g, v in split.items()},
        rest_ms=1e3 * sum(by.values()) - sum(split.values()),
        by_scope_ms={k: 1e3 * v for k, v in sorted(by.items())},
        top_ops=top_ops(red, parts["module"])))
    return split[group]


def top_ops(red: dict, module: str, n: int = 24) -> List[List]:
    """[operation, self seconds in the trace, scope] of a module's costliest
    operations: what ``fusion.386`` is, by scope."""
    ops = red["device"]["modules"][module]["ops"]
    return [[k, v[0] * 1e-12, v[1]] for k, v in
            sorted(ops.items(), key=lambda kv: -kv[1][0])[:n]]


def main(argv: List[str]) -> int:
    red = reduce(argv[1] if len(argv) > 1 else None)
    if red is None:
        print(json.dumps({"error": "no trace with a device plane found"}))
        return 1
    dev, idle = red["device"], red["idle"]
    out = {"modules": {
        m: {"by_scope_s": {k: v * 1e-12 for k, v in sorted(
                d["scopes"].items(), key=lambda kv: -kv[1])},
            "top_ops": top_ops(red, m, TOP)}
        for m, d in sorted(dev["modules"].items(), key=lambda kv: -sum(
            kv[1]["scopes"].values()))[:TOP]},
        "scheduler_line": red["scheduler_line"]}
    if idle is not None:
        out["idle_s"] = idle["idle_ps"] * 1e-12
        out["idle_by_span_s"] = {k: v * 1e-12 for k, v in sorted(
            idle["by_span"].items(), key=lambda kv: -kv[1])}
        out["longest_gaps"] = [[g * 1e-12, name] for g, name, _ in
                               idle["longest"]]
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
