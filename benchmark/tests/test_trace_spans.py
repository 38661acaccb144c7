"""Checks of ``benchmark/trace_spans.py`` on traces worked by hand. Run with
the yardstick's other checks: ``python -m pytest benchmark/tests -q``."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark import trace_spans as ts  # noqa: E402

NS = 1000        # the module counts picoseconds; the cases are in ns


def plane(name, lines, meta=None):
    """A plane as ``read_planes`` returns it. ``lines``: {line: [(start ns,
    length ns, metadata id)]}; ``meta``: {id: (event name, tf_op)}."""
    return {"name": name, "meta": meta or {},
            "lines": [{"name": ln, "events": [
                (s * NS, (s + d) * NS, mid) for s, d, mid in evs]}
                for ln, evs in lines.items()]}


PATH = "jit(_decode_n)/while/body/closed_call/while/body/closed_call/"
META = {
    1: ("jit__decode_n(77)", ""),
    2: ("jit__admit(5)", ""),
    10: ("%while.47 = (s32[]) while(%t), body=%b", ""),
    11: ("%while.46 = (s32[]) while(%t), body=%b", PATH[:30]),
    12: ("%paged_v3.8 = bf16[64] custom-call(%a)",
         PATH + "attn.core/pallas_call"),
    13: ("%fusion.386 = bf16[64] fusion(%a)",
         PATH + "mlp/dot_general;" + PATH + "attn.out/add"),
    14: ("%sort.33 = f32[64] sort(%a)",
         "jit(_decode_n)/while/body/closed_call/sample/sort"),
    15: ("%copy.2 = f32[8] copy(%a)", "jit(_admit)/jit(main)/copy"),
    16: ("%fusion.9 = f32[8] fusion(%a)",
         "jit(_admit)/attn.qkv/dot_general"),
}


def decode_run(t0):
    """One run of the decode module, 1000 ns: the outer scan holds the
    layers' scan (600 ns: kernel 200, fusion 300, 100 of its own) and the
    sort (150); 250 ns of the outer loop are its own."""
    return [(t0, 1000, 10), (t0 + 50, 600, 11), (t0 + 100, 200, 12),
            (t0 + 300, 300, 13), (t0 + 700, 150, 14)]


def device_plane():
    ops = decode_run(0) + decode_run(2000) + [(1200, 100, 15),
                                              (1300, 200, 16)]
    # a third run the trace's end cut short: left out of the per-step means
    ops += [(4000, 300, 10), (4050, 200, 11), (4100, 100, 12)]
    mods = [(0, 1000, 1), (2000, 1000, 1), (4000, 300, 1), (1200, 300, 2)]
    return plane("/device:TPU:0", {"XLA Ops": ops, "XLA Modules": mods},
                 META)


def test_scope_of_takes_the_innermost_known_scope_of_the_first_path():
    assert ts.scope_of(PATH + "attn.core/pallas_call") == "attn.core"
    assert ts.scope_of(PATH + "mlp/moe.experts/dot_general") == "moe.experts"
    assert ts.scope_of(PATH + "mlp/dot;" + PATH + "sample/add") == "mlp"
    assert ts.scope_of("jit(f)/jit(main)/dot_general") == ts.REST
    assert ts.scope_of("") == ts.REST


def test_self_time_under_a_nested_while():
    rows = {k: t for _s, _e, k, t in ts.self_times(
        [(s * NS, (s + d) * NS, k) for s, d, k in decode_run(0)])}
    assert rows == {10: 250 * NS, 11: 100 * NS, 12: 200 * NS,
                    13: 300 * NS, 14: 150 * NS}
    assert sum(rows.values()) == 1000 * NS        # the run, counted once


def test_device_time_by_scope_and_the_decode_step_split():
    red = ts.reduce_planes([device_plane()])
    dev = red["device"]
    dec = dev["modules"]["jit__decode_n"]
    # two whole runs and the cut one: kernel 200+200+100, loops' own time
    assert dec["scopes"]["attn.core"] == 500 * NS
    assert dec["scopes"]["mlp"] == 600 * NS
    assert dec["scopes"]["sample"] == 300 * NS
    assert dec["ops"]["paged_v3.8"] == [500 * NS, "attn.core"]
    assert dec["ops"]["fusion.386"] == [600 * NS, "mlp"]
    assert dev["modules"]["jit__admit"]["scopes"] == {
        ts.REST: 100 * NS, "attn.qkv": 200 * NS}
    assert ts.decode_module(red) == "jit__decode_n"
    parts = ts.decode_step_parts(red, chunk=4)
    assert parts["runs"] == 2                     # the cut run is left out
    assert parts["device_step_s"] == pytest.approx(250e-9)
    by = parts["by_scope_s"]
    assert by["attn.core"] == pytest.approx(50e-9)
    assert by["mlp"] == pytest.approx(75e-9)
    assert by["sample"] == pytest.approx(37.5e-9)
    assert by[ts.REST] == pytest.approx(87.5e-9)
    # the parts and the rest are the step
    assert sum(by.values()) == pytest.approx(parts["device_step_s"])
    # the outer loop's own time: 250 + 250 and 100 of the cut run
    assert {k: (t, sc) for k, t, sc in ts.top_ops(red, "jit__decode_n", 3)
            } == {"while.47": (pytest.approx(600e-9), ts.REST),
                  "fusion.386": (pytest.approx(600e-9), "mlp"),
                  "paged_v3.8": (pytest.approx(500e-9), "attn.core")}


def host_plane():
    """The scheduler's thread and a handler's: sched.admit [1000, 1400)
    holds engine.admit [1150, 1350) which holds engine.upload [1300, 1340);
    sched.launch [1900, 2010); the handler flushes over the same time."""
    meta = {1: ("sched.admit", ""), 2: ("engine.admit", ""),
            3: ("engine.upload", ""), 4: ("sched.launch", ""),
            5: ("http.flush", ""), 6: ("ThreadpoolListener::Region", "")}
    return plane("/host:CPU", {
        "python3": [(1000, 400, 1), (1150, 200, 2), (1300, 40, 3),
                    (1900, 110, 4), (0, 5000, 6)],
        "http-worker": [(900, 1200, 5)]}, meta)


def test_idle_is_attributed_to_the_innermost_span_of_the_scheduler():
    red = ts.reduce_planes([host_plane(), device_plane()])
    assert red["scheduler_line"].startswith("python3")
    idle = red["idle"]
    # busy: [0,1000) [1200,1500) [2000,3000) [4000,4300); idle 200+500+1000
    assert idle["idle_ps"] == 1700 * NS
    by = idle["by_span"]
    # gap [1000,1200): sched.admit's own 150, then engine.admit 50
    # gap [1500,2000): nothing until sched.launch opens at 1900
    # gap [3000,4000): no span at all
    assert by == {"sched.admit": 150 * NS, "engine.admit": 50 * NS,
                  "sched.launch": 100 * NS, ts.NO_SPAN: 1400 * NS}
    assert idle["attributed_ps"] == 300 * NS
    assert [(g, name) for g, name, _ in idle["longest"]] == [
        (1000 * NS, ts.NO_SPAN), (500 * NS, ts.NO_SPAN),
        (200 * NS, "sched.admit")]
    flat = ts.innermost(ts.host_spans([host_plane()])["python3#0"])
    assert [(s // NS, e // NS, n) for s, e, n in flat] == [
        (1000, 1150, "sched.admit"), (1150, 1300, "engine.admit"),
        (1300, 1340, "engine.upload"), (1340, 1350, "engine.admit"),
        (1350, 1400, "sched.admit"), (1900, 2010, "sched.launch")]


def ctx(**resolved):
    return types.SimpleNamespace(resolved=resolved, notes={},
                                 live_tokens=None, peaks=None, conf={})


def test_readers_return_none_on_an_old_trace(tmp_path, monkeypatch):
    """A program without scopes or spans (the recorded PR 23 fixture), and no
    trace at all: every reader that needs names returns None."""
    from benchmark import run
    fixture = os.path.join(HERE, "small.xplane.pb")
    old = ts.reduce(fixture)
    assert old["idle"] is None and old["span_threads"] == 0
    assert ts.decode_step_parts(old, 32) is None       # no decode module
    unnamed = plane("/device:TPU:0", {
        "XLA Ops": decode_run(0), "XLA Modules": [(0, 1000, 1)]},
        {k: (n, "") for k, (n, _tf) in META.items()})
    assert ts.decode_step_parts(ts.reduce_planes([unnamed]), 4) is None
    monkeypatch.setattr(ts.tempfile, "gettempdir", lambda: str(tmp_path))
    assert ts.find_trace() is None and ts.reduce() is None
    for name in ("decode_attn_ms_per_step", "decode_matmul_ms_per_step",
                 "decode_sample_ms_per_step", "paged_attn_roofline",
                 "idle_attributed_share"):
        assert run.layer_reader(name).read(ctx(decode_chunk=32)) is None
    # and the counter readers on scrapes of a program without the series
    c = types.SimpleNamespace(before={}, after={}, notes={})
    for name in ("http_ingress_p95_ms", "http_flush_lag_p95_ms",
                 "ttft_queue_p90_ms", "ttft_prefill_p90_ms",
                 "slot_vacant_waiting_share", "sched_fanout_ms_per_chunk",
                 "decode_launch_ms"):
        assert run.layer_reader(name).read(c) is None


# -- the wire format ----------------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(no, value):
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(no << 3 | 2) + varint(len(value)) + value


def test_read_planes_from_bytes_encoded_by_hand(tmp_path):
    stat = field(1, 7) + field(5, "jit(f)/attn.core/dot_general")
    emeta = field(1, 3) + field(2, "%fusion.1 = f32[] fusion()") + \
        field(5, stat)
    event = field(1, 3) + field(2, 2500) + field(3, 1000) + \
        field(4, field(1, 9) + field(3, 12))          # a stat: skipped
    line = field(2, "XLA Ops") + field(3, 5) + field(4, event)
    plane_ = (field(2, "/device:TPU:0") + field(3, line)
              + field(4, field(1, 3) + field(2, emeta))
              + field(5, field(1, 7) + field(2, field(1, 7)
                                             + field(2, "tf_op"))))
    other = field(2, "/host:metadata")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, plane_) + field(1, other))
    (p,) = ts.read_planes(str(path))
    assert p["name"] == "/device:TPU:0"
    assert p["meta"] == {3: ("%fusion.1 = f32[] fusion()",
                             "jit(f)/attn.core/dot_general")}
    # line timestamp 5 ns, offset 2500 ps, duration 1000 ps
    assert p["lines"] == [{"name": "XLA Ops",
                           "events": [(7500, 8500, 3)]}]


FIXTURE = os.path.join(HERE, "small.xplane.pb")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="the recorded trace is not in this checkout")
def test_the_wire_reader_agrees_with_reduce_trace_on_the_recorded_trace():
    """Busy time and per-module seconds of the recorded v5e trace, read by
    this module's own parser, are what reduce_trace read through ProfileData
    (which hands out whole nanoseconds: the operations here last 14-640)."""
    import json
    with open(os.path.join(HERE, "small.expected.json")) as f:
        want = json.load(f)
    red = ts.reduce(FIXTURE)
    busy = sum(e - s for s, e in red["device"]["busy"])
    assert busy * 1e-12 == pytest.approx(want["busy_s"], rel=2e-3)
    for name, m in want["modules"].items():
        runs = red["device"]["runs"][name]
        assert len(runs) == m["runs"]
        assert sum(r["dur"] for r in runs) * 1e-12 == \
            pytest.approx(m["seconds"], rel=2e-3)
    # the recorded program had no scope: its tf_op is read, and has none
    assert red["device"]["named"]
    assert set(red["device"]["modules"]["jit_small_step"]["scopes"]) == {
        ts.REST}
