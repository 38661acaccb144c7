"""Request-lifecycle tracing, the flight recorder, and metrics hygiene.

Covers runtime/trace.py (span timelines, the crash flight recorder),
the scheduler's event threading, the latency histograms, and the
strict Prometheus text-format contract /metrics must satisfy (the same
validator the CI metrics-lint step runs over a live scrape)."""

import io
import json
import re
import time

import numpy as np
import pytest

from ollama_operator_tpu.runtime import trace as trace_mod
from ollama_operator_tpu.runtime.faults import FAULTS, InjectedFault
from ollama_operator_tpu.runtime.trace import (FLIGHT, NULL_TRACE, TRACER,
                                               FlightRecorder, RequestTrace,
                                               Tracer)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS
from ollama_operator_tpu.server.metrics import Metrics

from test_scheduler import GREEDY, make_stack


# -- RequestTrace ------------------------------------------------------

def test_request_trace_events_and_event_at():
    tr = RequestTrace("42")
    tr.event("queued", n_prompt=3)
    t_launch = time.perf_counter()
    tr.event("admitted", slot=0)
    tr.event_at(t_launch, "dispatch", kind="decode")
    d = tr.to_dict()
    assert d["id"] == "42"
    names = [e["ev"] for e in d["events"]]
    assert names == ["queued", "admitted", "dispatch"]
    assert d["events"][0]["n_prompt"] == 3
    # event_at back-dates: the dispatch launch precedes the admitted stamp
    assert d["events"][2]["t_ms"] <= d["events"][1]["t_ms"]
    assert all(e["t_ms"] >= 0 for e in d["events"])


def test_request_trace_timings_summary():
    tr = RequestTrace("7")
    tr.event("queued")
    tr.event("admitted")
    tr.event("dispatch")
    tr.event("dispatch")
    tm = tr.timings()
    spans = {s["ev"]: s for s in tm["spans"]}
    assert spans["dispatch"]["n"] == 2
    assert spans["dispatch"]["first_ms"] <= spans["dispatch"]["last_ms"]
    assert tm["queue_wait_ms"] >= 0


def test_null_trace_is_inert():
    NULL_TRACE.event("x", a=1)
    NULL_TRACE.event_at(0.0, "y")
    NULL_TRACE.set_identity("high", "acme")
    assert NULL_TRACE.to_dict()["events"] == []
    assert NULL_TRACE.timings() == {"spans": []}
    assert NULL_TRACE.cls is None and NULL_TRACE.tenant is None


def test_request_trace_identity_labels():
    tr = RequestTrace("9")
    d = tr.to_dict()
    assert "class" not in d and "tenant" not in d   # unset → omitted
    tr.set_identity("high", "acme")
    d = tr.to_dict()
    assert d["class"] == "high" and d["tenant"] == "acme"
    # falsy args never clobber an identity already set
    tr.set_identity(None, None)
    assert tr.cls == "high" and tr.tenant == "acme"


# -- Tracer registry ---------------------------------------------------

def test_tracer_bounded_registry_evicts_oldest():
    t = Tracer(keep=3)
    for i in range(5):
        t.begin(i)
    assert t.ids() == ["2", "3", "4"]
    assert t.get(1) is None
    assert t.get("4").rid == "4"


def test_tracer_disabled_returns_null(monkeypatch):
    monkeypatch.setattr(trace_mod, "TRACE_ENABLED", False)
    t = Tracer(keep=3)
    tr = t.begin(99)
    assert tr is NULL_TRACE
    assert t.ids() == []        # nothing registered when disabled


# -- FlightRecorder ----------------------------------------------------

def test_flight_recorder_ring_bounds_and_seq():
    fr = FlightRecorder(maxlen=16)
    for i in range(40):
        fr.record("tick", i=i)
    evs = fr.snapshot()
    assert len(evs) == 16                    # ring keeps only the tail
    assert fr.seq == 40                      # ...but the seq keeps counting
    assert [e["i"] for e in evs] == list(range(24, 40))
    assert [e["seq"] for e in evs] == list(range(25, 41))


def test_flight_recorder_dump_format():
    fr = FlightRecorder(maxlen=16)
    fr.record("admit", rid=1, slot=0)
    fr.record("restart", n=1)
    out = io.StringIO()
    n = fr.dump("unit test", stream=out)
    assert n == 2 and fr.dumps == 1
    lines = out.getvalue().splitlines()
    assert lines[0] == "--- flight recorder dump: unit test (2 events) ---"
    assert lines[-1] == "--- end flight recorder dump: unit test ---"
    evs = [json.loads(ln) for ln in lines[1:-1]]
    assert [e["kind"] for e in evs] == ["admit", "restart"]
    assert all("t_unix" in e and "seq" in e for e in evs)
    # last= trims to the newest events
    out2 = io.StringIO()
    assert fr.dump("tail", stream=out2, last=1) == 1
    assert json.loads(out2.getvalue().splitlines()[1])["kind"] == "restart"


def test_fault_injection_lands_in_flight_recorder():
    seq0 = FLIGHT.seq
    FAULTS.arm("unit.point", "fail:once")
    with pytest.raises(InjectedFault):
        FAULTS.check("unit.point")
    evs = [e for e in FLIGHT.snapshot() if e["seq"] > seq0]
    faults = [e for e in evs if e["kind"] == "fault_injected"]
    assert faults and faults[0]["point"] == "unit.point"
    assert faults[0]["spec"] == "fail:once"


# -- scheduler threading -----------------------------------------------

def test_scheduler_traces_request_lifecycle():
    cfg, params, eng, sched = make_stack(slots=2)
    try:
        r = sched.submit(np.array([1, 2, 3], np.int32), GREEDY,
                         max_tokens=5)
        assert len(list(r.tokens())) == 5
        tr = TRACER.get(r.id)
        assert tr is not None
        names = [n for _, n, _ in tr.events]
        for must in ("queued", "admitted", "first_token", "finish"):
            assert must in names, f"missing {must!r} in {names}"
        assert any(n.startswith("prefill") for n in names)
        assert any(n == "dispatch" for n in names)
        # timeline is summarisable for the opt-in timings block
        tm = tr.timings()
        assert tm["queue_wait_ms"] >= 0
        assert {s["ev"] for s in tm["spans"]} >= {"queued", "finish"}
    finally:
        sched.shutdown()


def test_scheduler_threads_identity_into_trace():
    cfg, params, eng, sched = make_stack(slots=2)
    try:
        r = sched.submit(np.array([1, 2], np.int32), GREEDY, max_tokens=3,
                         priority="high", tenant="acme")
        list(r.tokens())
        d = TRACER.get(r.id).to_dict()
        assert d["class"] == "high" and d["tenant"] == "acme"
    finally:
        sched.shutdown()


def test_displacement_records_flight_event():
    """Satellite 2: queue-full displacement leaves a dedicated
    'displaced' event carrying both sides' class/tenant, distinct from
    the victim's own shed."""
    from test_stall_free import manual
    seq0 = FLIGHT.seq
    sched = manual(make_stack(slots=1)[3])
    sched._admission.max_queue = 2
    try:
        sched.submit(np.array([1], np.int32), GREEDY, max_tokens=8,
                     priority="normal")
        victim = sched.submit(np.array([2], np.int32), GREEDY,
                              max_tokens=8, priority="best_effort",
                              tenant="acme")
        high = sched.submit(np.array([3], np.int32), GREEDY, max_tokens=8,
                            priority="high")
        evs = [e for e in FLIGHT.snapshot()
               if e["seq"] > seq0 and e["kind"] == "displaced"]
        assert evs, "no displaced event recorded"
        assert evs[0]["rid"] == victim.id
        assert evs[0]["cls"] == "best_effort"
        assert evs[0]["tenant"] == "acme"
        assert evs[0]["by"] == high.id
        assert evs[0]["by_cls"] == "high"
    finally:
        sched.shutdown()


def test_scheduler_records_admit_flight_events():
    seq0 = FLIGHT.seq
    cfg, params, eng, sched = make_stack(slots=2)
    try:
        r = sched.submit(np.array([1, 2], np.int32), GREEDY, max_tokens=3)
        list(r.tokens())
        admits = [e for e in FLIGHT.snapshot()
                  if e["seq"] > seq0 and e["kind"] == "admit"]
        assert any(e["rid"] == r.id for e in admits)
    finally:
        sched.shutdown()


def test_scheduler_observes_latency_histograms():
    q0 = _hist_count("tpu_model_queue_wait_seconds")
    cfg, params, eng, sched = make_stack(slots=2)
    try:
        r = sched.submit(np.array([1, 2], np.int32), GREEDY, max_tokens=4)
        list(r.tokens())
    finally:
        sched.shutdown()
    assert _hist_count("tpu_model_queue_wait_seconds") > q0
    text = METRICS.render()
    assert 'tpu_model_dispatch_seconds_bucket{kind="decode"' in text
    assert re.search(r'tpu_model_dispatch_seconds_bucket\{kind="(admit|'
                     r'extend)"', text)


def _hist_count(name, labels=""):
    h = METRICS._hists.get((name, labels))
    return h.n if h is not None else 0


@pytest.mark.chaos
def test_supervised_restart_dumps_flight_recorder(capsys, monkeypatch):
    """ISSUE 7 acceptance: the chaos drill's supervised restart dumps a
    flight-recorder post-mortem — >= 10 structured events including the
    injected fault and the restart itself."""
    monkeypatch.setenv("TPU_RESTART_REPLAY_MAX", "0")
    dumps0 = FLIGHT.dumps
    cfg, params, eng, sched = make_stack(slots=2, restart_backoff=0.001)
    try:
        # a little pre-fault traffic so the ring has history to dump
        for i in range(3):
            r = sched.submit(np.array([i + 1, i + 2], np.int32), GREEDY,
                             max_tokens=3)
            list(r.tokens())
        seq_fault = FLIGHT.seq
        FAULTS.arm("engine.step", "fail:once")
        r1 = sched.submit(np.array([9, 9], np.int32), GREEDY, max_tokens=4)
        with pytest.raises(RuntimeError, match="injected fault"):
            list(r1.tokens())
        deadline = time.monotonic() + 5
        while FLIGHT.dumps == dumps0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert FLIGHT.dumps == dumps0 + 1
        kinds = [e["kind"] for e in FLIGHT.snapshot()
                 if e["seq"] > seq_fault]
        assert "fault_injected" in kinds
        assert "engine_failure" in kinds
        assert "restart" in kinds
        assert len(FLIGHT.snapshot()) >= 10
        err = capsys.readouterr().err
        assert "flight recorder dump: supervised restart #" in err
    finally:
        sched.shutdown()


# -- metrics hygiene ---------------------------------------------------

def test_gauge_errors_counted_not_swallowed():
    m = Metrics()

    def boom():
        raise RuntimeError("dead weakref")

    m.gauge_fn("good_gauge", lambda: 7.0)
    m.gauge_fn("bad_gauge", boom)
    text = m.render()
    assert "good_gauge 7.0" in text
    assert "bad_gauge" not in text
    # the failure is counted, and visible in the SAME scrape
    assert "tpu_model_metrics_gauge_errors_total 1.0" in text
    assert "tpu_model_metrics_gauge_errors_total 2.0" in m.render()


def test_preseeded_counters_present_when_idle():
    text = METRICS.render()
    for name in ("tpu_model_preemptions_total",
                 "tpu_model_requests_total",
                 "tpu_model_generated_tokens_total",
                 "tpu_model_prompt_tokens_total",
                 "tpu_model_stream_frames_total",
                 "tpu_model_metrics_gauge_errors_total"):
        assert re.search(rf"^{name} [0-9.]+$", text, re.M), \
            f"{name} absent from an idle scrape"


def test_shed_counter_preseeds_full_label_matrix():
    """ISSUE 8: every {class,cause} combination of tpu_model_shed_total
    must exist at 0 before the first shed — a PromQL rate() over a
    series that appears mid-incident reads as a counter reset. Same for
    the per-tenant throttle/token series (default bucket)."""
    from ollama_operator_tpu.runtime.admission import (PRIORITIES,
                                                       SHED_CAUSES,
                                                       shed_labels)
    text = METRICS.render()
    for p in PRIORITIES:
        for c in SHED_CAUSES:
            series = f"tpu_model_shed_total{shed_labels(p, c)}"
            assert re.search(rf"^{re.escape(series)} [0-9.]+$", text,
                             re.M), f"{series} not pre-seeded"
    for series in (
            'tpu_model_tenant_throttles_total'
            '{class="best_effort",tenant="default"}',
            'tpu_model_tenant_decode_tokens_total{tenant="default"}'):
        assert re.search(rf"^{re.escape(series)} [0-9.]+$", text, re.M), \
            f"{series} not pre-seeded"


def test_utilization_metric_families_preseeded():
    """PR 10: every utilization/goodput series must exist at 0 on an
    idle scrape — rate() over a series that first appears mid-serving
    reads as a counter reset (same discipline as the shed matrix)."""
    text = METRICS.render()
    series = ([f'tpu_model_recompiles_total{{kind="{k}"}}'
               for k in ("decode", "admit", "admit_many", "extend")]
              + [f'tpu_model_useful_tokens_total{{kind="{k}"}}'
                 for k in ("decode", "prefill")]
              + [f'tpu_model_padded_tokens_total{{kind="{k}"}}'
                 for k in ("decode", "prefill")]
              + [f'tpu_model_breakdown_seconds_total{{phase="{p}"}}'
                 for p in ("dispatch_wait", "host", "idle")]
              + [f'tpu_model_decode_steps_total{{sampler="{s}"}}'
                 for s in ("argmax", "candidates")])
    for s in series:
        assert re.search(rf"^{re.escape(s)} [0-9.]+$", text, re.M), \
            f"{s} not pre-seeded"
    assert re.search(r"^tpu_model_model_flops_total [0-9.eE+]+$", text,
                     re.M), "tpu_model_model_flops_total not pre-seeded"


def test_utilization_series_pass_strict_validator():
    from ollama_operator_tpu.models.config import PRESETS
    from ollama_operator_tpu.runtime.accounting import UtilizationAccounting
    acct = UtilizationAccounting(PRESETS["tiny"], peak_flops=1e12,
                                 device_kind="unit")
    acct.on_decode(0.01, ctxs=[4, 6], n_steps=2, capacity=4)
    acct.on_prefill(0.01, 0, 5, 16)
    acct.on_wait(0.005)
    acct.on_idle(0.005)
    validate_prometheus_text(METRICS.render())


def test_admission_label_sets_pass_strict_validator():
    """Sheds, per-class queue-wait observations, and per-tenant series
    must render as parseable, HELP/TYPE-covered samples — label sets
    with {class,tenant,cause} go through the same strict contract as
    everything else."""
    from ollama_operator_tpu.runtime.admission import shed_labels
    METRICS.inc("tpu_model_shed_total",
                labels=shed_labels("best_effort", "queue_full"))
    METRICS.inc("tpu_model_tenant_throttles_total",
                labels='{class="best_effort",tenant="unit-t"}')
    METRICS.inc("tpu_model_tenant_decode_tokens_total", 5.0,
                '{tenant="unit-t"}')
    METRICS.observe("tpu_model_class_queue_wait_seconds", 0.002,
                    '{class="high"}')
    text = METRICS.render()
    validate_prometheus_text(text)
    assert 'tpu_model_class_queue_wait_seconds_bucket{class="high"' in text


# -- strict Prometheus text-format validator ---------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? (NaN|[+-]?Inf|[-+0-9.eE]+)$")


def _strip_le(labels):
    """Histogram group key: the label set minus the per-bucket le."""
    if not labels:
        return ""
    parts = [p for p in labels[1:-1].split(",")
             if p and not p.startswith("le=")]
    return "{" + ",".join(parts) + "}" if parts else ""


def validate_prometheus_text(text):
    """Strict structural check of a text-format exposition: HELP and TYPE
    on every series, no duplicate headers, parseable samples, cumulative
    monotone histogram buckets with consistent _count/_sum. Shared with
    test_server (live /metrics scrape) and the CI metrics-lint step."""
    types, helps, samples = {}, {}, []
    assert text.endswith("\n"), "exposition must end with a newline"
    for ln in text.rstrip("\n").splitlines():
        if ln.startswith("# HELP "):
            name = ln.split()[2]
            assert name not in helps, f"duplicate HELP for {name}"
            helps[name] = ln
        elif ln.startswith("# TYPE "):
            parts = ln.split()
            assert len(parts) == 4, f"malformed TYPE line: {ln!r}"
            name, typ = parts[2], parts[3]
            assert name not in types, f"duplicate TYPE for {name}"
            assert typ in ("counter", "gauge", "histogram"), ln
            types[name] = typ
        else:
            m = _SAMPLE_RE.match(ln)
            assert m, f"unparseable sample line: {ln!r}"
            samples.append((m.group(1), m.group(2) or "",
                            float(m.group(3))))

    def base_of(name):
        for suf in ("_bucket", "_sum", "_count"):
            root = name[:-len(suf)] if name.endswith(suf) else None
            if root and types.get(root) == "histogram":
                return root
        return name

    hist_groups = {}
    for name, labels, val in samples:
        base = base_of(name)
        assert base in types, f"sample {name} has no TYPE header"
        assert base in helps, \
            f"series {base} lacks HELP (add a describe() call)"
        if types[base] == "histogram":
            key = (base, _strip_le(labels))
            g = hist_groups.setdefault(key,
                                       {"buckets": [], "sum": None,
                                        "count": None})
            if name.endswith("_bucket"):
                le = re.search(r'le="([^"]*)"', labels).group(1)
                g["buckets"].append((float("inf") if le == "+Inf"
                                     else float(le), val))
            elif name.endswith("_sum"):
                g["sum"] = val
            elif name.endswith("_count"):
                g["count"] = val
        elif types[base] == "counter":
            assert val >= 0, f"counter {name} is negative: {val}"
    for (base, _), g in hist_groups.items():
        assert g["sum"] is not None and g["count"] is not None, \
            f"histogram {base} missing _sum/_count"
        les = [le for le, _ in g["buckets"]]
        counts = [c for _, c in g["buckets"]]
        assert les == sorted(les), f"{base} buckets out of order"
        assert les and les[-1] == float("inf"), f"{base} lacks +Inf bucket"
        assert counts == sorted(counts), \
            f"{base} cumulative counts not monotone: {counts}"
        assert counts[-1] == g["count"], \
            f"{base} +Inf bucket {counts[-1]} != _count {g['count']}"
    assert samples, "empty exposition"
    return len(samples)


def test_global_metrics_pass_strict_validator():
    # exercise at least one histogram + counter first so the validator
    # sees every shape
    METRICS.observe("tpu_model_queue_wait_seconds", 0.001)
    assert validate_prometheus_text(METRICS.render()) > 10


def test_validator_rejects_bad_expositions():
    good = ("# HELP x_total ok\n# TYPE x_total counter\nx_total 1.0\n")
    validate_prometheus_text(good)
    with pytest.raises(AssertionError, match="lacks HELP"):
        validate_prometheus_text("# TYPE y counter\ny 1.0\n")
    with pytest.raises(AssertionError, match="no TYPE"):
        validate_prometheus_text("# HELP y ok\ny 1.0\n")
    with pytest.raises(AssertionError, match="duplicate TYPE"):
        validate_prometheus_text("# HELP y ok\n# TYPE y counter\n"
                                 "# TYPE y counter\ny 1.0\n")
    bad_hist = ("# HELP h ok\n# TYPE h histogram\n"
                'h_bucket{le="0.1"} 5\nh_bucket{le="1.0"} 3\n'
                'h_bucket{le="+Inf"} 3\nh_sum 1.0\nh_count 3\n')
    with pytest.raises(AssertionError, match="not monotone"):
        validate_prometheus_text(bad_hist)
