"""The span vocabulary of runtime/trace.py: host spans (one closed table,
each a histogram series and a TraceAnnotation), request stages folded from a
RequestTrace, the slot-vacancy counters, and the device scopes every path of
the model step carries into its lowered program."""

import re
import threading
import time

import jax
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.runtime import trace as trace_mod
from ollama_operator_tpu.runtime.engine import Engine, EngineConfig
from ollama_operator_tpu.runtime.trace import (DEVICE_SCOPES, SPAN_TABLE,
                                               SPANS, STAGES, TRACER,
                                               RequestTrace, device_scope,
                                               fold_stages, span)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS
from ollama_operator_tpu.server.metrics import STAGE_BUCKETS

from test_scheduler import GREEDY, make_stack

SPAN_NAMES = [row[0] for row in SPAN_TABLE]
# the scopes a dense (no MoE) model's step must carry, on every path
DENSE_SCOPES = [s for s in DEVICE_SCOPES
                if not s.startswith(("moe.", "ssm.", "conv.", "delta."))
                and s not in ("attn.window", "attn.index")]


def _series(name, labels):
    """(count, sum) of one histogram series of the global registry."""
    text = METRICS.render()
    out = []
    for suffix in ("_count", "_sum"):
        m = re.search(re.escape(name + suffix + labels) + r" (\S+)", text)
        assert m, f"{name}{suffix}{labels} is not on /metrics"
        out.append(float(m.group(1)))
    return out


# -- the closed tables -------------------------------------------------

def test_an_unknown_span_or_scope_is_an_error():
    with pytest.raises(KeyError):
        span("sched.made_up")
    with pytest.raises(KeyError):
        device_scope("attention")
    assert len(SPANS) == len(SPAN_TABLE)          # no name twice
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_every_span_is_described_and_preseeded(name):
    text = METRICS.render()
    assert "# HELP tpu_model_span_seconds " in text
    assert f'tpu_model_span_seconds_count{{span="{name}"}}' in text
    assert SPANS[name] in ("HTTP server", "scheduler", "admission",
                           "engine dispatch")
    assert name.startswith(("http.", "sched.", "engine."))


@pytest.mark.parametrize("stage", STAGES)
def test_every_stage_is_described_and_preseeded(stage):
    text = METRICS.render()
    assert "# HELP tpu_model_request_stage_seconds " in text
    assert (f'tpu_model_request_stage_seconds_count{{stage="{stage}"}}'
            in text)


def test_vacancy_counters_are_preseeded_and_the_gauge_is_gone():
    text = METRICS.render()
    for q in ("waiting", "empty"):
        assert (f'tpu_model_slot_vacant_seconds_total{{queue="{q}"}}'
                in text)
    assert "tpu_model_slot_seconds_total " in text
    assert "tpu_model_dispatch_ms" not in text


def test_metrics_discipline_passes_on_the_shipped_tree():
    from pathlib import Path

    from tools.invariant_lint.core import LintConfig, run_passes
    from tools.invariant_lint.passes.metrics_discipline import \
        MetricsDisciplinePass
    root = Path(__file__).resolve().parents[1]
    findings = [f for f in run_passes(LintConfig(root=root),
                                      [MetricsDisciplinePass()])
                if not f.suppressed]
    assert [str(f) for f in findings] == []


def test_stage_buckets_step_by_at_most_a_quarter_from_1ms_to_60s():
    assert STAGE_BUCKETS[0] == pytest.approx(1e-3)
    assert STAGE_BUCKETS[-1] == pytest.approx(60.0)
    steps = [b / a for a, b in zip(STAGE_BUCKETS, STAGE_BUCKETS[1:])]
    assert max(steps) <= 1.25
    bounds, counts = METRICS.hist_buckets("tpu_model_request_stage_seconds",
                                          '{stage="queue"}')
    assert bounds == STAGE_BUCKETS and len(counts) == len(bounds) + 1


def test_the_benchmark_reads_the_programs_vocabulary():
    from benchmark import (conv_spans, delta_spans, index_spans, ssm_spans,
                           trace_spans, window_spans)
    # the accepted reader knows the scopes the dense cells carry; each
    # hybrid stack's mixer, and latent attention's indexer, is read by the
    # reader that came with it
    lists = (trace_spans.SCOPES, ssm_spans.SCOPES, conv_spans.SCOPES,
             window_spans.SCOPES, delta_spans.SCOPES, index_spans.SCOPES)
    assert set().union(*lists) == set(DEVICE_SCOPES)
    assert sum(map(len, lists)) == len(DEVICE_SCOPES)     # no scope twice
    assert all(n.startswith(trace_spans.SPAN_PREFIXES) for n in SPANS)
    grouped = [s for ss in trace_spans.GROUPS.values() for s in ss]
    assert set(grouped) <= set(DEVICE_SCOPES)
    assert len(grouped) == len(set(grouped))


def test_the_caches_gauges_are_one_vocabulary():
    """``CACHE_GAUGES``: each gauge of the loaded model's cache is described
    for the scrape, registered and removed by ``LoadedModel`` from the
    engine's property of the same stem, and read by a per-layer metric of the
    benchmark under the label the vocabulary gives."""
    import glob
    import inspect
    import os

    from benchmark import trace_spans
    from ollama_operator_tpu.runtime import service
    from ollama_operator_tpu.runtime.engine import Engine
    from ollama_operator_tpu.runtime.trace import CACHE_GAUGES
    from ollama_operator_tpu.server import metrics
    served = inspect.getsource(service)
    described = inspect.getsource(metrics)
    readers = "".join(open(p).read() for p in glob.glob(os.path.join(
        os.path.dirname(trace_spans.__file__), "layer_metrics", "*.py")))
    assert set(CACHE_GAUGES) == set(re.findall(
        r'"(tpu_model_(?:cache_bytes|[a-z]+_positions))"', served))
    for name, (key, values) in CACHE_GAUGES.items():
        stem = name[len("tpu_model_"):]
        assert isinstance(getattr(Engine, stem, None), property) or (
            stem == "cache_bytes")
        assert served.count(f'"{name}"') == 2          # on and off
        assert f'GLOBAL.describe("{name}"' in described
        assert f'"{name}' in readers
        assert f'{{{{{key}="{{{key}}}"}}}}' in served
        assert len(set(values)) == len(values)


# -- the span primitive ------------------------------------------------

def test_a_span_observes_its_duration_once():
    n0, s0 = _series("tpu_model_span_seconds", '{span="sched.fanout"}')
    with span("sched.fanout") as sp:
        time.sleep(0.01)
    assert sp.dur >= 0.01 and sp.t0 > 0
    sp.end()                                      # idempotent
    n1, s1 = _series("tpu_model_span_seconds", '{span="sched.fanout"}')
    assert n1 == n0 + 1
    assert s1 - s0 == pytest.approx(sp.dur)


def test_nested_spans_give_the_parents_self_time():
    with span("sched.admit") as outer:
        time.sleep(0.005)
        with span("engine.admit") as inner:
            time.sleep(0.02)
            with span("engine.install_key") as leaf:
                time.sleep(0.005)
    assert inner.dur >= 0.025 and outer.dur >= inner.dur
    assert outer.self_s == pytest.approx(outer.dur - inner.dur)
    assert inner.self_s == pytest.approx(inner.dur - leaf.dur)
    assert leaf.self_s == pytest.approx(leaf.dur)
    # siblings both come off the parent
    with span("sched.housekeep") as p:
        with span("engine.release") as a:
            pass
        with span("engine.upload") as b:
            pass
    assert p.self_s == pytest.approx(p.dur - a.dur - b.dur)


def test_spans_nest_per_thread():
    seen = {}

    def other():
        with span("http.flush") as sp:
            time.sleep(0.01)
        seen["flush"] = sp

    with span("sched.wait") as outer:
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert outer.self_s == pytest.approx(outer.dur)   # not its child
    assert seen["flush"].self_s == pytest.approx(seen["flush"].dur)


def test_a_cancelled_span_records_nothing():
    n0, _ = _series("tpu_model_span_seconds", '{span="http.ingress"}')
    sp = span("http.ingress").begin()
    sp.cancel()
    sp.end()
    n1, _ = _series("tpu_model_span_seconds", '{span="http.ingress"}')
    assert n1 == n0
    with span("sched.idle") as after:            # the stack is clean again
        pass
    assert after.self_s == pytest.approx(after.dur)


def test_a_span_with_rid_stamps_the_requests_trace():
    tr = TRACER.begin("span-rid-1")
    with span("http.flush", tr, n_tokens=3, chars=7):
        pass
    with span("http.flush", "span-rid-1", n_tokens=1, chars=1):
        pass                                      # by id, through TRACER
    evs = [(n, f) for _t, n, f in tr.events]
    assert [n for n, _ in evs] == ["http_flush", "http_flush"]
    assert evs[0][1]["n_tokens"] == 3 and evs[0][1]["chars"] == 7
    assert "dur_ms" in evs[0][1]


def test_a_span_is_a_trace_annotation_while_a_session_runs(tmp_path):
    """The host plane of a profiler session holds the span, its nesting and
    its fields, at the host tracer level the benchmark uses."""
    from benchmark import trace_spans
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with span("sched.admit") as sp:
            sp.set(n=2)
            with span("engine.admit"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    planes = trace_spans.read_planes(trace_spans.find_trace(str(tmp_path)))
    lines = trace_spans.host_spans(planes)
    assert len(lines) == 1
    (evs,) = lines.values()
    assert sorted(n for _s, _e, n in evs) == ["engine.admit", "sched.admit"]
    flat = trace_spans.innermost(evs)
    assert [n for _s, _e, n in flat] == ["sched.admit", "engine.admit",
                                         "sched.admit"]


# -- request stages ----------------------------------------------------

def _stage_counts():
    return {st: _series("tpu_model_request_stage_seconds",
                        f'{{stage="{st}"}}')[0] for st in STAGES}


def test_fold_stages_worked_by_hand():
    tr = RequestTrace("fold-1")
    t0 = tr._t0
    tr.t_http = t0 - 0.004                        # handler began 4 ms early
    tr.event_at(t0 + 0.001, "queued")
    tr.event_at(t0 + 0.500, "prefill", kind="admit", dur_ms=200.0)
    tr.event_at(t0 + 0.501, "admitted")
    tr.event_at(t0 + 0.502, "first_token")
    tr.event_at(t0 + 0.503, "http_flush", dur_ms=2.0)
    tr.event_at(t0 + 1.700, "http_flush", dur_ms=1.0)
    tr.event_at(t0 + 2.502, "finish")
    got = fold_stages(tr)
    assert got == pytest.approx({"ingress": 0.005, "queue": 0.299,
                                 "prefill": 0.202, "first_flush": 0.003,
                                 "decode": 2.0})
    assert fold_stages(tr) == {}                  # once


def test_stage_durations_sum_to_ttft_and_finish_on_a_scheduler_run():
    cfg, params, eng, sched = make_stack(slots=1)
    try:
        before = _stage_counts()
        ingress = span("http.ingress").begin()    # as the handler does
        time.sleep(0.003)
        r = sched.submit(np.array([1, 2, 3, 4], np.int32), GREEDY,
                         max_tokens=6)
        ingress.end()
        assert len(list(r.tokens())) == 6
        tr = TRACER.get(r.id)
        assert tr.t_http == ingress.t0
        assert not tr.folded                      # the handler's to fold
        got = fold_stages(tr)
        at = {}
        for t, name, _f in tr.events:
            at.setdefault(name, t)
        http = tr.t_http - tr._t0
        assert set(got) == {"ingress", "queue", "prefill", "decode"}
        assert got["ingress"] >= 0.003
        assert got["ingress"] + got["queue"] + got["prefill"] == \
            pytest.approx(at["first_token"] - http)
        assert sum(got.values()) == pytest.approx(at["finish"] - http)
        after = _stage_counts()
        for st in got:
            assert after[st] == before[st] + 1
        assert after["first_flush"] == before["first_flush"]
    finally:
        sched.shutdown()


def test_a_request_no_handler_owns_folds_at_finish():
    cfg, params, eng, sched = make_stack(slots=1)
    try:
        before = _stage_counts()
        r = sched.submit(np.array([5, 6, 7], np.int32), GREEDY,
                         max_tokens=3)
        assert len(list(r.tokens())) == 3
        tr = TRACER.get(r.id)
        deadline = time.time() + 5
        while not tr.folded and time.time() < deadline:
            time.sleep(0.01)
        assert tr.t_http is None and tr.folded
        after = _stage_counts()
        assert after["queue"] == before["queue"] + 1
        assert after["decode"] == before["decode"] + 1
        assert after["ingress"] == before["ingress"]
    finally:
        sched.shutdown()


def test_trace_off_leaves_stage_histograms_untouched(monkeypatch):
    monkeypatch.setattr(trace_mod, "TRACE_ENABLED", False)
    cfg, params, eng, sched = make_stack(slots=1)
    try:
        before = _stage_counts()
        v0 = METRICS.get("tpu_model_slot_seconds_total")
        r = sched.submit(np.array([1, 2, 3], np.int32), GREEDY,
                         max_tokens=4)
        assert len(list(r.tokens())) == 4
        assert r.trace is trace_mod.NULL_TRACE
        assert fold_stages(r.trace) == {}
        time.sleep(0.12)
        assert _stage_counts() == before
        # the vacancy counters do not depend on the kill switch
        assert METRICS.get("tpu_model_slot_seconds_total") > v0
    finally:
        sched.shutdown()


# -- slot vacancy ------------------------------------------------------

def test_vacancy_counters_add_up_to_slots_times_wall():
    n_slots = 3
    cfg, params, eng, sched = make_stack(slots=n_slots)
    w, e = '{queue="waiting"}', '{queue="empty"}'
    try:
        time.sleep(0.15)                          # let the loop start
        t0 = time.perf_counter()
        s0 = METRICS.get("tpu_model_slot_seconds_total")
        v0 = (METRICS.get("tpu_model_slot_vacant_seconds_total", w)
              + METRICS.get("tpu_model_slot_vacant_seconds_total", e))
        e0 = METRICS.get("tpu_model_slot_vacant_seconds_total", e)
        r = sched.submit(np.array([1, 2, 3], np.int32), GREEDY,
                         max_tokens=8)
        assert len(list(r.tokens())) == 8
        time.sleep(0.6)                           # idle: every slot vacant
        wall = time.perf_counter() - t0
        s1 = METRICS.get("tpu_model_slot_seconds_total")
        v1 = (METRICS.get("tpu_model_slot_vacant_seconds_total", w)
              + METRICS.get("tpu_model_slot_vacant_seconds_total", e))
        e1 = METRICS.get("tpu_model_slot_vacant_seconds_total", e)
        # the loop's iterations tile the wall time (one is 50 ms at most)
        assert s1 - s0 == pytest.approx(n_slots * wall, abs=n_slots * 0.12)
        assert 0.0 < v1 - v0 <= s1 - s0 + 1e-9
        # nothing waited while the scheduler idled: that vacancy is "empty"
        assert e1 - e0 >= n_slots * 0.4
    finally:
        sched.shutdown()


# -- admissions by mode, and what a dispatch took -----------------------

def _admissions():
    return {m: METRICS.get("tpu_model_admissions_total", f'{{mode="{m}"}}')
            for m in ("launched", "awaited")}


@pytest.mark.parametrize("mode", ["launched", "awaited"])
def test_admissions_counter_is_described_and_preseeded(mode):
    text = METRICS.render()
    assert "# HELP tpu_model_admissions_total " in text
    assert re.search(
        rf'^tpu_model_admissions_total\{{mode="{mode}"\}} [0-9.]+$', text,
        re.M), f"mode={mode} absent from an idle scrape"


@pytest.mark.parametrize("async_dispatch, mode", [(True, "launched"),
                                                  (False, "awaited")])
def test_admission_modes_add_up_to_the_requests_admitted(async_dispatch,
                                                         mode):
    """Five requests over two slots: each is admitted once, and counted
    once, under the form its loop takes."""
    cfg, params, eng, sched = make_stack(slots=2,
                                         async_dispatch=async_dispatch)
    try:
        before = _admissions()
        reqs = [sched.submit(np.array([i + 1, i + 2, i + 3], np.int32),
                             GREEDY, max_tokens=4) for i in range(5)]
        assert all(len(list(r.tokens())) == 4 for r in reqs)
        moved = {m: v - before[m] for m, v in _admissions().items()}
        assert sum(moved.values()) == len(reqs)
        assert moved[mode] == len(reqs)
    finally:
        sched.shutdown()


def test_a_chunk_launched_behind_another_does_not_count_its_predecessor():
    """tpu_model_dispatch_seconds{kind="decode"} is what a dispatch took:
    from the later of its launch and its predecessor's tokens reaching the
    host. Two chunks launched back to back: the second's observation
    starts where the first landed, not at its own launch."""
    cfg, params, eng, sched = make_stack(slots=2)
    sched._stop.set()
    sched._wake.set()
    sched._thread.join(timeout=5)
    lab = '{kind="decode"}'
    try:
        eng.admit(0, np.array([1, 2, 3, 4], np.int32), GREEDY)
        eng.decode_n(4)                           # compiled before the pair
        n0, s0 = _series("tpu_model_dispatch_seconds", lab)
        h1 = eng.decode_n_launch(4)
        h2 = eng.decode_n_launch(4)
        time.sleep(0.05)                          # both run out meanwhile
        sched._wait_handle(h1)
        time.sleep(0.02)
        sched._wait_handle(h2)
        n1, s1 = _series("tpu_model_dispatch_seconds", lab)
        assert n1 == n0 + 2
        assert h1.t_begin == pytest.approx(h1.t_launch)   # nothing before it
        assert h2.t_launch < h1.t_done == h2.t_begin < h2.t_done
        assert s1 - s0 == pytest.approx(
            (h1.t_done - h1.t_launch) + (h2.t_done - h1.t_done), abs=1e-6)
        # launch to tokens would have counted the first chunk's 50 ms again
        assert h2.t_done - h2.t_begin < (h2.t_done - h2.t_launch) - 0.04
        assert eng.dispatch_ms["decode"] == pytest.approx(
            (h2.t_done - h2.t_begin) * 1e3)
    finally:
        sched.shutdown()
        eng.release(0)


@pytest.mark.parametrize("admitted,want", [
    (None, None),                    # the parent: no such counter
    ((0, 0), None),                  # nobody admitted in the window
    ((106, 0), 100.0), ((30, 10), 75.0), ((0, 8), 0.0)])
def test_admit_launched_share_reads_the_schedulers_own_count(admitted, want):
    """The benchmark's reader over two scrapes of the real registry's
    text: the window's launched admissions over all its admissions;
    nothing, and no raise, where the program has no such counter."""
    import types

    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    reg.inc("tpu_model_generated_tokens_total", 5.0)
    if admitted is not None:
        reg.inc("tpu_model_admissions_total", 7.0, '{mode="launched"}')
        reg.inc("tpu_model_admissions_total", 3.0, '{mode="awaited"}')
    before = prom.parse(reg.render())
    for mode, n in zip(("launched", "awaited"), admitted or ()):
        reg.inc("tpu_model_admissions_total", float(n),
                '{mode="%s"}' % mode)
    ctx = types.SimpleNamespace(before=before,
                                after=prom.parse(reg.render()))
    got = run.layer_reader("admit_launched_share").read(ctx)
    assert got == (None if want is None else pytest.approx(want))


# -- the stall for pages, the runtime's launch call, a dispatch by part --

NEW_COUNTERS = [("tpu_model_admission_passes_total", "stalled", v)
                for v in ("yes", "no")] + [
    ("tpu_model_page_stalls_total", "cause", v)
    for v in ("pool_dry_admit", "pool_dry_stitch", "pool_dry_decode")]
ADMIT_PARTS = ("launch", "behind", "run")
NEW_READERS = ("pass_stalled_share", "page_stall_ms_per_pass",
               "stall_idle_share", "sched_enqueue_share", "admit_launch_ms",
               "admit_behind_ms", "admit_run_ms")


def test_the_table_has_twenty_rows_and_the_two_new_ones_their_layers():
    assert len(SPAN_TABLE) == 20
    assert SPANS["sched.stall"] == "admission"
    assert SPANS["engine.enqueue"] == "engine dispatch"


@pytest.mark.parametrize("family,key,value", NEW_COUNTERS)
def test_stall_and_pass_counters_are_described_and_preseeded(family, key,
                                                             value):
    text = METRICS.render()
    assert f"# HELP {family} " in text
    assert re.search(rf'^{family}\{{{key}="{value}"\}} [0-9.]+$', text,
                     re.M), f"{key}={value} absent from an idle scrape"


@pytest.mark.parametrize("part", ADMIT_PARTS)
def test_admit_dispatch_parts_are_described_and_preseeded(part):
    text = METRICS.render()
    assert "# HELP tpu_model_admit_dispatch_seconds " in text
    assert (f'tpu_model_admit_dispatch_seconds_count{{part="{part}"}}'
            in text)
    bounds, _ = METRICS.hist_buckets("tpu_model_admit_dispatch_seconds",
                                     f'{{part="{part}"}}')
    assert bounds == STAGE_BUCKETS


def test_the_pass_holds_help_says_which_passes_it_leaves_out():
    text = METRICS.render()
    (help_,) = re.findall(r"^# HELP tpu_model_pass_holds_total (.*)$", text,
                          re.M)
    assert "no chunk in flight" in help_
    assert "tpu_model_admission_passes_total" in help_


def _stall_counts():
    get = METRICS.get
    return dict(
        stall=_series("tpu_model_span_seconds", '{span="sched.stall"}')[0],
        **{f"{key}={v}": get(family, f'{{{key}="{v}"}}')
           for family, key, v in NEW_COUNTERS})


def _moved(before):
    return {k: v - before[k] for k, v in _stall_counts().items() if
            v != before[k]}


def test_a_pass_that_stalls_for_pages_says_so_once():
    """The pool holds one request at a time and the second arrives while
    the first's last chunk is in flight (test_admit_launch's scene): its
    pass finds the pool dry, and that is one sched.stall span, one stall
    counted under its cause, one pass counted as stalled, the flight
    recorder's event as before, and the tokens a pool with room gives."""
    import dataclasses

    import test_admit_launch as tal
    from ollama_operator_tpu.models import decoder
    from ollama_operator_tpu.runtime.scheduler import Scheduler
    from ollama_operator_tpu.runtime.trace import FLIGHT
    cfg, ecfg = tal.KINDS["paged_int8"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jax.numpy.float32)
    eng = Engine(cfg, params, ecfg=dataclasses.replace(
        ecfg, max_slots=2, n_pages=3))
    pa, pb = tal.prompt(20), tal.prompt(20, base=11)
    (ref,) = tal.serve(eng, [pb], tal.GREEDY, launched=True, max_tokens=9)
    sched = tal.manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    try:
        before = _stall_counts()
        ra = sched.submit(pa, tal.GREEDY, max_tokens=5)
        sched._step()                   # first token; 4 more in flight
        assert _moved(before) == {"stalled=no": 1}
        assert sched._pending is not None
        rb = sched.submit(pb, tal.GREEDY, max_tokens=9)
        seq = FLIGHT.seq
        sched._step()
        assert _moved(before) == {"stalled=no": 1, "stalled=yes": 1,
                                  "cause=pool_dry_admit": 1, "stall": 1}
        events = [e for e in FLIGHT.snapshot() if e["seq"] > seq
                  and e["kind"] == "fence_quiesce"]
        assert [e["cause"] for e in events] == ["pool_dry_admit"]
        assert tal.frames(ra)[-1] == ("done", "length")
        tal.run_steps(sched)            # steps that admit nobody: no pass
        assert _moved(before) == {"stalled=no": 1, "stalled=yes": 1,
                                  "cause=pool_dry_admit": 1, "stall": 1}
        assert tal.frames(rb) == ref
    finally:
        sched.shutdown()
        tal.clean(eng)


def _record_spans(monkeypatch, module):
    """Every span ``module`` opens from now on, in order of opening."""
    made = []

    class Rec(span):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)
    monkeypatch.setattr(module, "span", Rec)
    return made


def test_the_stall_span_holds_the_drain_and_the_fanout(monkeypatch):
    """sched.stall is the parent of what the stall runs: the wait for the
    chunk in flight and its fan-out nest inside it, so its self time is
    what is left (the quiesce)."""
    from ollama_operator_tpu.runtime import scheduler as sched_mod
    cfg, params, eng, sched = make_stack(slots=2)
    sched._stop.set()
    sched._wake.set()
    sched._thread.join(timeout=5)
    made = _record_spans(monkeypatch, sched_mod)
    try:
        sched.submit(np.array([1, 2, 3], np.int32), GREEDY, max_tokens=9)
        sched._step()
        assert sched._pending is not None
        made.clear()
        sched._stall_for_pages("pool_dry_decode")
        assert sched._pending is None
        names = [s.name for s in made]
        assert names[0] == "sched.stall"
        assert {"sched.wait", "sched.fanout"} <= set(names[1:])
        stall = made[0]
        assert stall._fields == {"cause": "pool_dry_decode"}
        assert stall.self_s == pytest.approx(
            stall.dur - sum(s.dur for s in made[1:]
                            if s._parent is stall))
    finally:
        sched.shutdown()


def test_a_contiguous_cache_counts_its_passes_and_never_a_stall():
    cfg, params, eng, sched = make_stack(slots=2)
    try:
        before = _stall_counts()
        reqs = [sched.submit(np.array([i + 1, i + 2, i + 3], np.int32),
                             GREEDY, max_tokens=4) for i in range(5)]
        assert all(len(list(r.tokens())) == 4 for r in reqs)
        moved = _moved(before)
        assert set(moved) == {"stalled=no"}
        assert 1 <= moved["stalled=no"] <= len(reqs)
    finally:
        sched.shutdown()


def test_enqueue_is_observed_once_an_executable_call(monkeypatch):
    """One engine.enqueue a compiled program handed to the runtime (an
    admission: the key install, the prefill and their scalars; a chunk; a
    release), each named, and the launch's own span does not count it as
    its self time: a prefill call that stands 30 ms stands in
    engine.enqueue."""
    from ollama_operator_tpu.runtime import engine as engine_mod
    cfg, params, eng, sched = make_stack(slots=2)
    sched.shutdown()
    p = np.array([1, 2, 3, 4], np.int32)
    eng.admit(0, p, GREEDY)                       # compiled before the spy
    eng.decode_n(4)
    eng.release(0)
    made = _record_spans(monkeypatch, engine_mod)
    real = eng._admit_exec

    def slow(bucket):
        exe = real(bucket)
        return lambda *a: (time.sleep(0.03), exe(*a))[1]
    monkeypatch.setattr(eng, "_admit_exec", slow)
    n0, _ = _series("tpu_model_span_seconds", '{span="engine.enqueue"}')
    eng.admit_launch(0, p, GREEDY).wait()
    eng.decode_n_launch(4).wait()
    eng.release(0)
    n1, _ = _series("tpu_model_span_seconds", '{span="engine.enqueue"}')
    every = [s for s in made if s.name == "engine.enqueue"]
    assert n1 - n0 == len(every)
    # on one device a scalar's upload is a program too, and is enqueued
    # like one: the slot and the seed before the key install, the
    # constraint's flag, the slot, the length and the window before the
    # prefill, the slot before the release
    assert [s._fields["program"] for s in every] == (
        ["scalar_upload"] * 2 + ["install_key"] + ["scalar_upload"] * 4
        + ["admit", "decode", "scalar_upload", "release"])
    enq = [s for s in every if s._fields["program"] != "scalar_upload"]
    by = {s.name: s for s in made}
    admit, prefill = by["engine.admit"], enq[1]
    assert prefill._parent is admit and prefill.dur >= 0.03
    assert enq[0]._parent is by["engine.install_key"]
    assert enq[2]._parent is by["engine.decode_n"]
    assert enq[3]._parent is by["engine.release"]
    assert admit.self_s == pytest.approx(
        admit.dur - sum(s.dur for s in made if s._parent is admit))
    assert admit.self_s < admit.dur - 0.03


@pytest.mark.parametrize("stamps,want", [
    # launched behind a chunk in flight: staged 0.2, queued 0.3, ran 0.1
    ((10.0, 10.2, 10.5, 10.6), (0.2, 0.3, 0.1)),
    # an empty device (t_begin is the launch): nothing stood before it
    ((10.0, 10.2, 10.0, 10.6), (0.2, 0.0, 0.4)),
    # its predecessor landed while the launch still stood in the runtime
    ((10.0, 10.6, 10.5, 10.7), (0.6, 0.0, 0.1)),
])
def test_an_admission_dispatch_by_part_worked_by_hand(stamps, want):
    from ollama_operator_tpu.runtime.engine import admit_parts
    got = admit_parts(*stamps)
    assert got == pytest.approx(want)
    assert sum(got) == pytest.approx(stamps[3] - stamps[0])   # done - launch


def _part_series():
    return {p: _series("tpu_model_admit_dispatch_seconds",
                       f'{{part="{p}"}}') for p in ADMIT_PARTS}


@pytest.mark.parametrize("form", ["awaited", "launched", "batched"])
def test_a_dispatch_observes_its_parts_once_and_they_add_up(form,
                                                            monkeypatch):
    """An awaited admission, a launched one and an admit_many of two are
    one observation of each part, whose sum is t_done - t_launch; with
    TPU_TRACE off too; on an empty device nothing stood before it."""
    monkeypatch.setattr(trace_mod, "TRACE_ENABLED", False)
    cfg, params, eng, sched = make_stack(slots=2)
    sched.shutdown()
    p = np.array([1, 2, 3, 4], np.int32)
    before = _part_series()
    if form == "batched":
        h = eng.admit_many_launch([0, 1], [p, p + 1], [GREEDY, GREEDY])
        assert len(h.wait()) == 2
    elif form == "launched":
        h = eng.admit_launch(0, p, GREEDY)
        h.wait()
    else:
        eng.admit(0, p, GREEDY)
        h = None
    h is None or h.wait()                         # a second wait: no more
    after = _part_series()
    assert all(after[q][0] == before[q][0] + 1 for q in ADMIT_PARTS)
    moved = {q: after[q][1] - before[q][1] for q in ADMIT_PARTS}
    assert moved["behind"] == 0.0                 # the device stood empty
    if h is not None:
        assert sum(moved.values()) == pytest.approx(h.t_done - h.t_launch,
                                                    abs=1e-9)
        assert moved["launch"] == pytest.approx(h.t_queued - h.t_launch,
                                                abs=1e-9)


def _scrapes(fill):
    """(before, after): two scrapes of a registry of its own around
    ``fill(reg)``, each family of ``fill`` present (at 1) in the first."""
    from benchmark import prom
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    reg.inc("tpu_model_generated_tokens_total", 5.0)
    fill(reg, 1.0)
    before = prom.parse(reg.render())
    fill(reg, None)
    return before, prom.parse(reg.render())


def _ctx(fill, **kw):
    import types
    before, after = _scrapes(fill)
    return types.SimpleNamespace(before=before, after=after, notes={},
                                 trace=None, **kw)


def _fill_passes(yes, no, stall_s=0.0, causes=()):
    def fill(reg, first):
        for lab, n in (("yes", yes), ("no", no)):
            reg.inc("tpu_model_admission_passes_total",
                    first or float(n), f'{{stalled="{lab}"}}')
        reg.observe("tpu_model_span_seconds", first or stall_s,
                    '{span="sched.stall"}')
        for cause in causes:
            reg.inc("tpu_model_page_stalls_total", 1.0,
                    f'{{cause="{cause}"}}')
    return fill


def _fill_parts(n, launch, behind, run, passes=0):
    def fill(reg, first):
        for part, s in (("launch", launch), ("behind", behind),
                        ("run", run)):
            for _ in range(1 if first else n):
                reg.observe("tpu_model_admit_dispatch_seconds",
                            first or s / n, f'{{part="{part}"}}')
        if passes:
            reg.inc("tpu_model_admission_passes_total",
                    first or float(passes), '{stalled="no"}')
            reg.inc("tpu_model_admission_passes_total", first or 0.0,
                    '{stalled="yes"}')
    return fill


def _fill_enqueue(enqueue_s, phases):
    def fill(reg, first):
        reg.observe("tpu_model_span_seconds", first or enqueue_s,
                    '{span="engine.enqueue"}')
        for phase, s in phases.items():
            reg.inc("tpu_model_breakdown_seconds_total", first or s,
                    f'{{phase="{phase}"}}')
    return fill


@pytest.mark.parametrize("name,fill,want", [
    ("pass_stalled_share", _fill_passes(9, 1), 90.0),
    ("pass_stalled_share", _fill_passes(0, 12), 0.0),
    ("pass_stalled_share", _fill_passes(0, 0), None),     # nobody admitted
    ("page_stall_ms_per_pass",
     _fill_passes(9, 1, 3.0, ["pool_dry_admit"]), 300.0),
    ("page_stall_ms_per_pass", _fill_passes(0, 0, 0.5), None),
    ("sched_enqueue_share", _fill_enqueue(
        2.0, dict(host=3.0, dispatch_wait=6.0, idle=1.0)), 20.0),
    ("sched_enqueue_share", _fill_enqueue(0.0, {}), None),
    ("admit_launch_ms", _fill_parts(5, 0.5, 2.0, 0.1), 100.0),
    ("admit_behind_ms", _fill_parts(5, 0.5, 2.0, 0.1), 400.0),
    ("admit_run_ms", _fill_parts(5, 0.5, 2.0, 0.1, passes=2), 20.0),
    ("admit_run_ms", _fill_parts(0, 0.0, 0.0, 0.0), None),  # none landed
])
def test_the_new_readers_read_the_programs_own_counts(name, fill, want):
    """Each reader over two scrapes of a real registry's text: what the
    window added, over what the window added."""
    from benchmark import run
    ctx = _ctx(fill)
    got = run.layer_reader(name).read(ctx)
    assert got == (None if want is None else pytest.approx(want))
    if name == "page_stall_ms_per_pass" and want:
        assert ctx.notes["page_stalls"]["by_cause"] == {
            "pool_dry_admit": 1, "pool_dry_stitch": None,
            "pool_dry_decode": None}
    if name == "admit_run_ms" and want:
        assert ctx.notes["admit_dispatch"]["dispatches_per_pass"] == 2.5


def _hand_made_planes(stall):
    """A device that ran 0-100 and 200-300 (ps), and a scheduler thread
    with a pass open all along and ``stall`` (start, end) inside it."""
    names = {1: ("fusion.1", ""), 2: ("sched.admit", ""),
             3: ("sched.stall", ""), 4: ("sched.wait", "")}
    host = [(0, 400, 2)]
    if stall:
        host += [(stall[0], stall[1], 3), (stall[0], stall[1] - 10, 4)]
    return [{"name": "/device:TPU:0", "meta": names, "lines": [
                {"name": "XLA Ops", "events": [(0, 100, 1), (200, 300, 1)]},
                {"name": "XLA Modules", "events": []}]},
            {"name": "/host:CPU", "meta": names, "lines": [
                {"name": "scheduler", "events": host}]}]


@pytest.mark.parametrize("stall,want_ps", [
    ((150, 400), 50),         # the idle 100-200 half inside the stall
    ((90, 210), 100),         # all of it, and busy time is not idle
    ((210, 290), 0),          # a stall while the device ran
    (None, 0),                # the span in the vocabulary, none in the trace
])
def test_stall_idle_share_on_hand_made_planes(monkeypatch, stall, want_ps):
    from benchmark import run, trace_spans
    planes = _hand_made_planes(stall)
    monkeypatch.setattr(trace_spans, "find_trace", lambda where=None: "x")
    monkeypatch.setattr(trace_spans, "read_planes", lambda path: planes)
    monkeypatch.setattr(trace_spans, "reduce",
                        lambda where=None: trace_spans.reduce_planes(planes))
    ctx = _ctx(_fill_passes(1, 0, 0.1))
    ctx.trace = {"window_s": 300e-12, "busy_s": 200e-12}
    got = run.layer_reader("stall_idle_share").read(ctx)
    assert got == pytest.approx(100.0 * want_ps / 300)
    assert got <= 100.0 * (1 - 200 / 300) + 1e-9          # the idle share
    note = ctx.notes["stall_idle"]
    assert note["idle_in_stall_s"] == pytest.approx(want_ps * 1e-12)
    assert note["stalls"] == (1 if stall else 0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_program_without_the_families_reads_nothing(name, monkeypatch):
    """The parent under this PR's benchmark files: every accepted family
    is there, the new ones are not; traced or not, the reader returns
    None and does not raise."""
    from benchmark import run, trace_spans
    planes = _hand_made_planes(None)
    monkeypatch.setattr(trace_spans, "find_trace", lambda where=None: "x")
    monkeypatch.setattr(trace_spans, "read_planes", lambda path: planes)
    monkeypatch.setattr(trace_spans, "reduce",
                        lambda where=None: trace_spans.reduce_planes(planes))

    def parent(reg, first):
        reg.observe("tpu_model_span_seconds", 0.25,
                    '{span="engine.admit"}')
        reg.inc("tpu_model_breakdown_seconds_total", 4.0, '{phase="host"}')
        reg.inc("tpu_model_pass_holds_total", 3.0, '{end="filled"}')
    for trace in (None, {"window_s": 300e-12, "busy_s": 200e-12}):
        ctx = _ctx(parent)
        ctx.trace = trace
        assert run.layer_reader(name).read(ctx) is None
        assert ctx.notes == {}


def test_the_benchmark_lists_the_new_readers_where_they_read():
    import json
    from pathlib import Path
    bench = json.loads((Path(__file__).resolve().parents[1]
                        / "BENCHMARK.json").read_text())
    by = {m["name"]: m for m in bench["per_layer"]}
    paged = ["starcoder2-3b.decode-saturated", "phi-2.decode-saturated"]
    for name in NEW_READERS:
        dense_only = name in NEW_READERS[:3]
        assert by[name].get("workloads") == (paged if dense_only else None)
    names = [m["name"] for m in bench["per_layer"]]
    # the fence's reader came after them, for the paged cells alone (later
    # PRs' metrics follow it: entries are only ever appended)
    at = names.index("evict_free_share")
    assert names[at - 7:at] == list(NEW_READERS)
    assert by["evict_free_share"] == dict(
        name="evict_free_share", unit="%", better="higher",
        source="program_counter", layer="admission", moves="out_tok_s",
        workloads=paged)


# -- an evicted page by whether the fence let it go at once ------------

@pytest.mark.parametrize("fence", ["free", "fenced"])
def test_the_evicted_pages_counter_is_described_and_preseeded(fence):
    family = "tpu_model_radix_evicted_pages_total"
    text = METRICS.render()
    assert f"# HELP {family} " in text
    assert re.search(rf'^{family}\{{fence="{fence}"\}} [0-9.]+$', text,
                     re.M), f"fence={fence} absent from an idle scrape"


def _fill_evicted(free, fenced):
    def fill(reg, first):
        for lab, n in (("free", free), ("fenced", fenced)):
            reg.inc("tpu_model_radix_evicted_pages_total",
                    first or float(n), f'{{fence="{lab}"}}')
    return fill


@pytest.mark.parametrize("fill,want", [
    (_fill_evicted(30, 10), 75.0),
    (_fill_evicted(12, 0), 100.0),
    (_fill_evicted(0, 7), 0.0),
    (_fill_evicted(0, 0), None),          # a contiguous cache: seeded, still
    (lambda reg, first: None, None),      # the parent: no such family
])
def test_evict_free_share_reads_the_windows_evictions(fill, want):
    from benchmark import run
    ctx = _ctx(fill)
    got = run.layer_reader("evict_free_share").read(ctx)
    assert got == (None if want is None else pytest.approx(want))
    if want is None:
        assert ctx.notes == {}
    else:
        assert sum(ctx.notes["evicted_pages"].values()) > 0


# -- device scopes in every path's lowered program ---------------------

def _lowered_texts(monkeypatch, **ecfg_kw):
    """Lowered text (with locations) of the admit, decode and extend
    programs a tiny engine compiles when it serves one request."""
    texts = {}
    orig = Engine._compile

    def spy(self, kind, key, jit_fn, *args):
        texts.setdefault(kind, jit_fn.lower(*args).as_text(debug_info=True))
        return orig(self, kind, key, jit_fn, *args)

    monkeypatch.setattr(Engine, "_compile", spy)
    from ollama_operator_tpu.models import decoder
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jax.numpy.float32)
    kw = dict(max_slots=2, max_seq_len=64, cache_dtype=jax.numpy.float32,
              min_prefill_bucket=16)
    kw.update(ecfg_kw)
    eng = Engine(cfg, params, ecfg=EngineConfig(**kw))
    ids = np.arange(1, 21, dtype=np.int32)
    eng.admit(0, ids[:10])
    eng.decode_n(2)
    eng.release(0, park=True)
    eng.extend(0, ids, 10)
    eng.release(0)
    return texts


PATHS = {"dense": {}, "paged": dict(paged=True, page_size=16, n_pages=8),
         "paged_int8": dict(paged=True, page_size=16, n_pages=8,
                            cache_dtype=jax.numpy.int8)}


@pytest.fixture(scope="module")
def lowered():
    mp = pytest.MonkeyPatch()
    try:
        return {path: _lowered_texts(mp, **kw) for path, kw in PATHS.items()}
    finally:
        mp.undo()


@pytest.mark.parametrize("program", ["admit", "decode", "extend"])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_lowered_program_carries_every_scope(lowered, path, program):
    text = lowered[path][program]
    found = {s for s in DEVICE_SCOPES
             if re.search(r'[/"]' + re.escape(s) + r'[/"]', text)}
    assert found >= set(DENSE_SCOPES), (
        f"{path}/{program} lacks {sorted(set(DENSE_SCOPES) - found)}")
    # and no dotted scope that the table does not hold
    dotted = set(re.findall(r'[/"]((?:attn|moe)\.[a-z_]+)[/"]', text))
    assert dotted <= set(DEVICE_SCOPES)


def test_every_kernel_site_is_named_from_the_vocabulary():
    """Each ``pl.pallas_call`` under ops/pallas/ passes ``name=``, so a trace
    shows the kernel under a name that outlives a refactor, and the names
    are ``KERNEL_NAMES``: a literal at the site, or (the fused matmul's one
    site) a parameter whose callers pass literals of the list."""
    import ast
    import glob
    import os

    from ollama_operator_tpu.ops import pallas
    from ollama_operator_tpu.runtime.trace import KERNEL_NAMES
    sites, named = 0, set()
    for path in sorted(glob.glob(os.path.join(
            os.path.dirname(pallas.__file__), "*.py"))):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and ast.unparse(node.func) == "pl.pallas_call"):
                continue
            sites += 1
            name = next(kw.value for kw in node.keywords if kw.arg == "name")
            if isinstance(name, ast.Constant):
                named.add(name.value)
                continue
            # a parameter of the enclosing function: its callers' literals
            assert isinstance(name, ast.Name), path
            named |= {c.args[0].value for c in ast.walk(tree)
                      if isinstance(c, ast.Call) and c.args
                      and isinstance(c.args[0], ast.Constant)
                      and ast.unparse(c.func) == "_fused"}
    assert sites == 8
    assert named == set(KERNEL_NAMES)
    assert len(set(KERNEL_NAMES)) == len(KERNEL_NAMES)


def test_the_delta_kernel_runs_under_the_scope_its_metric_reads():
    """``benchmark/delta_spans.py`` reads ``delta.update``: the decode
    program's kernel call lies under that scope, by its name."""
    import dataclasses

    from ollama_operator_tpu.models import decoder
    cfg = dataclasses.replace(cfglib.PRESETS["tiny-olmo-hybrid"],
                              kernels="interpret")
    ssm, conv, _ = decoder.empty_state(cfg, 2)
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jax.numpy.float32)
    dp = {k: v[0] for k, v in params["layers"].items()
          if k.startswith("delta_")}
    u = jax.numpy.ones((2, 1, cfg.dim))
    text = jax.jit(lambda *a: decoder._delta_mixer(cfg, *a)).lower(
        dp, u, ssm, conv, jax.numpy.int32(1),
        jax.numpy.ones((2,), jax.numpy.int32)).as_text(debug_info=True)
    assert re.search(r'delta\.update/[^"]*delta_update', text)


def test_the_latent_kernel_runs_under_the_scope_its_metrics_read():
    """``decode_attn_ms_per_step`` and ``paged_attn_roofline`` read
    ``attn.core``: the decode step's kernel call lies under that scope, by
    its name, with the two einsums around it."""
    import dataclasses

    from ollama_operator_tpu.models import decoder
    cfg = dataclasses.replace(cfglib.PRESETS["tiny-glm5"],
                              kernels="interpret")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jax.numpy.float32)
    _, kd, vd = cfg.cache_row_dims
    zeros = jax.numpy.zeros
    text = jax.jit(lambda *a: decoder.forward_with_cache(
        params, cfg, *a, attn_len=32)).lower(
        zeros((2, 1), jax.numpy.int32), zeros((4, 2, 1, 64, kd)),
        zeros((4, 2, 1, 64, vd)), jax.numpy.array([3, 20], jax.numpy.int32)
    ).as_text(debug_info=True)
    assert re.search(r'attn\.core/[^"]*latent_decode', text)


def test_the_ring_kernel_runs_under_the_scope_its_metrics_read(monkeypatch):
    """``decode_window_attn_ms_per_step`` and ``ring_attn_roofline`` read
    ``attn.window``: a long ring's decode step lies under that scope whole,
    the row write and the kernel's call, by its name."""
    import dataclasses

    from ollama_operator_tpu.models import decoder
    monkeypatch.setattr(decoder, "_RING_SELECT_MAX", 0)
    cfg = dataclasses.replace(cfglib.PRESETS["tiny-smallthinker"],
                              kernels="interpret")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jax.numpy.float32)
    zeros = jax.numpy.zeros
    kc = zeros((cfg.n_full_layers, 2, cfg.n_kv_heads, 64, cfg.head_dim))
    K, V = decoder.join_state(kc, kc, decoder.empty_state(cfg, 2))
    text = jax.jit(lambda *a: decoder.forward_with_cache(
        params, cfg, *a, attn_len=32)).lower(
        zeros((2, 1), jax.numpy.int32), K, V,
        jax.numpy.array([3, 20], jax.numpy.int32)).as_text(debug_info=True)
    assert re.search(r'attn\.window/[^"]*ring_decode', text)
    assert re.search(r'attn\.window/[^"]*scatter', text)


def test_moe_scopes_nest_under_mlp():
    import dataclasses

    from ollama_operator_tpu.models import decoder
    cfg = dataclasses.replace(cfglib.PRESETS["tiny"], n_experts=4,
                              n_experts_used=2)
    params = decoder.init_params(cfg, jax.random.PRNGKey(1),
                                 dtype=jax.numpy.float32)
    toks = jax.numpy.zeros((1, 8), jax.numpy.int32)
    text = jax.jit(lambda p, t: decoder.prefill_chunk(p, cfg, t)[0]).lower(
        params, toks).as_text(debug_info=True)
    assert "mlp/moe.route/" in text and "mlp/moe.experts/" in text


# -- the candidate sort sits in a branch of the sampler's conditional ------

CELL_CONFIGS = ("starcoder2-3b", "phi-2", "granite-4.0-h-small")
SORTS = ("chlo.top_k", "stablehlo.sort")
BRANCHING = ("stablehlo.case", "stablehlo.if")


def _cell_decode_module(name):
    """The 4-step decode program of a benchmark cell's configuration at
    its rehearsal size, on the kind of engine the cell resolves to (an
    int8 paged pool; the contiguous int8 cache for the recurrent stack),
    lowered with locations and never run."""
    import os

    from benchmark import server_child as sc
    from ollama_operator_tpu.models import decoder
    conf = sc.load_conf(os.path.join(os.path.dirname(sc.__file__), "configs",
                                     name + ".json"), True)
    cfg = sc.model_config(conf, True)
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jax.numpy.float32)
    paged = (dict(paged=True, page_size=16, n_pages=None)
             if not cfg.layer_kinds else {})
    ecfg = EngineConfig(max_slots=4, max_seq_len=cfg.max_seq_len,
                        decode_chunk=4, cache_dtype=jax.numpy.int8,
                        min_prefill_bucket=64, **paged)
    got = []
    mp = pytest.MonkeyPatch()
    mp.setattr(Engine, "_compile",
               lambda self, kind, key, jit_fn, *args: got.append(
                   jit_fn.lower(*args).compiler_ir(dialect="stablehlo")))
    try:
        eng = Engine(cfg, params, ecfg=ecfg)
        assert eng.paged == (not eng.recurrent)
        eng._decode_n_exec(4, eng.max_seq)
    finally:
        mp.undo()
    return got[0]


def _reached_outside_branches(module, names):
    """(every operation of ``names``, those a run reaches without entering
    a branch of a conditional): an operation counts as inside where an
    ancestor is a stablehlo.case / if, or where its function is called
    from inside one only."""
    from jax._src.lib.mlir import ir
    found, calls = [], []

    def place(op):
        """(inside a conditional's branch?, the enclosing function)."""
        inside, op = False, op.parent
        while op.name != "func.func":
            inside = inside or op.name in BRANCHING
            op = op.parent
        return inside, ir.StringAttr(op.attributes["sym_name"]).value

    def visit(op):
        op = op.operation
        if op.name in names:
            found.append((op, *place(op)))
        elif op.name == "func.call":
            calls.append((ir.FlatSymbolRefAttr(
                op.attributes["callee"]).value, *place(op)))
        return ir.WalkResult.ADVANCE

    module.operation.walk(visit)
    open_funcs, grew = {"main"}, True
    while grew:
        grew = False
        for callee, inside, caller in calls:
            if (not inside and caller in open_funcs
                    and callee not in open_funcs):
                open_funcs.add(callee)
                grew = True
    return ([op for op, _i, _f in found],
            [op for op, inside, fn in found
             if not inside and fn in open_funcs])


@pytest.mark.parametrize("name", CELL_CONFIGS)
def test_the_candidate_sort_runs_only_inside_the_samplers_branch(name):
    """Every decode step of a greedy batch used to sort the vocabulary and
    throw the result away: in each cell's decode program the top-k and the
    typical-p argsort are reached through a conditional's branch and not
    from the scan body, and they keep the ``sample`` scope the benchmark's
    readers bill them to."""
    sorts, outside = (
        [op for op in ops if "/ops/sampling.py" in str(op.location)]
        for ops in _reached_outside_branches(_cell_decode_module(name),
                                             SORTS))      # not the router's
    assert {op.name for op in sorts} == set(SORTS)
    assert not outside, [str(op.location) for op in outside]
    # (the argsort's own function names its operations from its own root)
    for op in sorts:
        assert op.name != "chlo.top_k" or re.match(
            r'loc\("(.*/)?sample/cond/branch_1_fun/top_k"',
            str(op.location)), str(op.location)


def test_a_sort_outside_the_branch_is_found():
    """The walker above, on a program that sorts in the scan body as the
    sampler used to."""
    def step(carry, x):
        kept = jax.lax.cond(x.sum() > 0, lambda: jax.lax.top_k(x, 2)[0],
                            lambda: x[:2])
        return carry + jax.numpy.sort(x)[0], kept

    module = jax.jit(lambda xs: jax.lax.scan(step, 0.0, xs)).lower(
        jax.numpy.ones((3, 8))).compiler_ir(dialect="stablehlo")
    sorts, outside = _reached_outside_branches(module, SORTS)
    assert sorted(op.name for op in sorts) == sorted(SORTS)
    assert [op.name for op in outside] == ["stablehlo.sort"]


@pytest.mark.parametrize("steps,want", [
    (None, None),                    # the parent: no such counter
    ((0, 0), None),                  # no decode step in the window
    ((960, 0), 100.0), ((96, 32), 75.0), ((0, 64), 0.0)])
def test_sample_argmax_share_reads_the_engines_own_count(steps, want):
    """The benchmark's reader over two scrapes of the real registry's
    text: the window's argmax steps over all its decode steps; nothing,
    and no raise, where the program has no such counter."""
    import types

    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    reg.inc("tpu_model_generated_tokens_total", 5.0)
    if steps is not None:
        reg.inc("tpu_model_decode_steps_total", 7.0, '{sampler="argmax"}')
        reg.inc("tpu_model_decode_steps_total", 3.0,
                '{sampler="candidates"}')
    before = prom.parse(reg.render())
    for sampler, n in zip(("argmax", "candidates"), steps or ()):
        reg.inc("tpu_model_decode_steps_total", float(n),
                '{sampler="%s"}' % sampler)
    ctx = types.SimpleNamespace(before=before,
                                after=prom.parse(reg.render()))
    got = run.layer_reader("sample_argmax_share").read(ctx)
    assert got == (None if want is None else pytest.approx(want))
