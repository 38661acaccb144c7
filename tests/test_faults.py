"""Fault injection (runtime/faults.py) and the recovery paths it drives.

The `chaos`-marked tests are the CI chaos-smoke set: each injects a real
fault at a named point and asserts the corresponding recovery path —
supervised engine restart, per-request admission error, kube client
retry — recovers within ONE restart/retry. They are also tier-1 (not
slow): every recovery path runs on every push.
"""

import time

import numpy as np
import pytest

from ollama_operator_tpu.runtime.faults import (FAULTS, FaultInjector,
                                                InjectedFault, _parse_spec)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

from test_scheduler import GREEDY, make_stack


# -- spec grammar ------------------------------------------------------

def test_spec_parsing():
    assert _parse_spec("fail") == ("fail", "always", 0.0, 0.0)
    assert _parse_spec("fail:once") == ("fail", "n", 1.0, 0.0)
    assert _parse_spec("fail:n=2") == ("fail", "n", 2.0, 0.0)
    assert _parse_spec("fail:every=3") == ("fail", "every", 3.0, 0.0)
    assert _parse_spec("fail:after=4") == ("fail", "after", 4.0, 0.0)
    assert _parse_spec("delay:50ms") == ("delay", "always", 0.0, 0.05)
    assert _parse_spec("delay:0.2s") == ("delay", "always", 0.0, 0.2)
    # delays take the same trigger modes as fail (a drill can wedge
    # exactly one dispatch)
    assert _parse_spec("delay:50ms:once") == ("delay", "n", 1.0, 0.05)
    assert _parse_spec("delay:1s:n=2") == ("delay", "n", 2.0, 1.0)
    assert _parse_spec("delay:5ms:every=3") == ("delay", "every", 3.0,
                                                0.005)
    assert _parse_spec("delay:5ms:after=4") == ("delay", "after", 4.0,
                                                0.005)
    for bad in ("fail:sometimes", "delay:50", "jitter:1ms", "fail:n=0",
                "delay:1ms:sometimes", "delay:1ms:n=0"):
        with pytest.raises(ValueError):
            _parse_spec(bad)


def test_injector_modes():
    f = FaultInjector()
    f.arm("p", "fail:once")
    with pytest.raises(InjectedFault):
        f.check("p")
    f.check("p")                     # disarmed after the first hit
    assert f.hits("p") == 1          # disarmed checks don't count

    f.arm("q", "fail:every=2")
    f.check("q")
    with pytest.raises(InjectedFault):
        f.check("q")
    f.check("q")
    with pytest.raises(InjectedFault):
        f.check("q")

    f.arm("r", "fail:after=1")
    f.check("r")
    with pytest.raises(InjectedFault):
        f.check("r")
    with pytest.raises(InjectedFault):
        f.check("r")

    # delay modes share the trigger grammar: :once sleeps on the first
    # hit only (the sleep itself is what fires — assert via wall clock)
    f.arm("d", "delay:30ms:once")
    t0 = time.monotonic()
    f.check("d")
    assert time.monotonic() - t0 >= 0.025
    t0 = time.monotonic()
    f.check("d")                     # disarmed: no sleep
    assert time.monotonic() - t0 < 0.025

    f.reset()
    f.check("q")                     # everything disarmed


def test_env_arming(monkeypatch):
    f = FaultInjector()
    monkeypatch.setenv("TPU_FAULTS", "a=fail:once, b=delay:1ms")
    f.arm_from_env()
    with pytest.raises(InjectedFault):
        f.check("a")
    f.check("b")                     # delays, doesn't raise
    assert f.hits("b") == 1


def test_unarmed_check_is_noop():
    f = FaultInjector()
    f.check("anything")
    assert f.hits("anything") == 0


# -- chaos: supervised engine restart ----------------------------------

@pytest.mark.chaos
def test_engine_step_fault_supervised_restart(monkeypatch):
    """ISSUE 2 acceptance: engine.step fail:once errors only the
    in-flight request, the supervisor rebuilds in-process, a subsequent
    request completes on the SAME scheduler object, and
    tpu_model_engine_restarts_total increments."""
    # replay off: this drill pins the pre-replay error path (the
    # replay-on drill lives in test_lifecycle.py)
    monkeypatch.setenv("TPU_RESTART_REPLAY_MAX", "0")
    cfg, params, eng, sched = make_stack(slots=2, restart_backoff=0.001)
    restarts_before = METRICS.get("tpu_model_engine_restarts_total")
    try:
        FAULTS.arm("engine.step", "fail:once")
        r1 = sched.submit(np.array([1, 2], np.int32), GREEDY, max_tokens=4)
        with pytest.raises(RuntimeError, match="injected fault"):
            list(r1.tokens())
        # supervisor rebuilt the engine state in-process: same scheduler
        # object, loop thread alive, not broken, restart counted
        deadline = time.monotonic() + 5
        while sched.n_restarts == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sched.n_restarts == 1
        assert sched._thread.is_alive()
        assert not sched.broken
        assert METRICS.get("tpu_model_engine_restarts_total") \
            == restarts_before + 1
        r2 = sched.submit(np.array([3, 4], np.int32), GREEDY, max_tokens=3)
        assert len(list(r2.tokens())) == 3
        assert sched.n_restarts == 1     # recovery took exactly one restart
    finally:
        sched.shutdown()


@pytest.mark.chaos
def test_engine_step_fault_spares_waiting_requests(monkeypatch):
    """Queued requests survive the restart: only the in-flight request
    errors; the waiting one is admitted after the rebuild and completes."""
    monkeypatch.setenv("TPU_RESTART_REPLAY_MAX", "0")
    cfg, params, eng, sched = make_stack(slots=1, restart_backoff=0.001)
    try:
        r1 = sched.submit(np.array([1, 2], np.int32), GREEDY,
                          max_tokens=64)
        it = r1.tokens()
        next(it)                      # r1 occupies the only slot
        r2 = sched.submit(np.array([3, 4], np.int32), GREEDY, max_tokens=3)
        FAULTS.arm("engine.step", "fail:once")
        with pytest.raises(RuntimeError, match="injected fault"):
            list(it)
        assert len(list(r2.tokens())) == 3   # never errored, just delayed
        assert not sched.broken
    finally:
        sched.shutdown()


@pytest.mark.chaos
def test_engine_admit_fault_errors_only_that_request():
    """An admission fault is a per-request error (the caller sees it),
    NOT a loop failure: no restart, and the next request admits fine."""
    cfg, params, eng, sched = make_stack(slots=2)
    try:
        FAULTS.arm("engine.admit", "fail:once")
        r1 = sched.submit(np.array([1, 2], np.int32), GREEDY, max_tokens=3)
        with pytest.raises(RuntimeError, match="injected fault"):
            list(r1.tokens())
        assert sched.n_restarts == 0
        assert not sched.broken
        r2 = sched.submit(np.array([3, 4], np.int32), GREEDY, max_tokens=3)
        assert len(list(r2.tokens())) == 3
    finally:
        sched.shutdown()


# -- chaos: kube client retries ----------------------------------------

@pytest.mark.chaos
def test_kube_request_fault_retried_on_get():
    """kube.request fail:once: the read-only GET retries transparently
    and the operator never sees the blip."""
    from ollama_operator_tpu.operator.client import KubeClient
    from fake_kube import FakeKube, serve_http
    fake = FakeKube()
    fake.create({"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": "chaos", "namespace": "default"}})
    srv = serve_http(fake)
    try:
        host, port = srv.server_address
        c = KubeClient(f"http://{host}:{port}", timeout=5)
        FAULTS.arm("kube.request", "fail:once")
        obj = c.get("v1", "Pod", "default", "chaos")
        assert obj is not None and obj["metadata"]["name"] == "chaos"
        assert FAULTS.hits("kube.request") == 1     # fired once, then retried
    finally:
        srv.shutdown()


def test_fault_catalog_points_are_complete_and_documented():
    """Satellite 2: FAULTS.points() is the chaos campaign's draw set —
    sorted, stable, and every point carries its check site and a doc
    string (the invariant-lint fault-catalog pass enforces the same
    contract statically)."""
    from ollama_operator_tpu.runtime.faults import CATALOG, FAULTS
    pts = FAULTS.points()
    assert [p.name for p in pts] == sorted(CATALOG)
    assert len(pts) >= 12
    for p in pts:
        assert p.site, p.name
        assert p.doc, p.name


def test_chaos_metric_preseeds_mirror_fault_catalog():
    """metrics.py pre-seeds tpu_model_chaos_events_total for every
    catalogued point (rate() alerts must read 0, not absent, before the
    first campaign); the literal list there must track the CATALOG."""
    from ollama_operator_tpu.runtime.faults import FAULTS
    rendered = METRICS.render()
    for p in FAULTS.points():
        series = f'tpu_model_chaos_events_total{{point="{p.name}"}}'
        assert series in rendered, \
            f"{series} not pre-seeded in server/metrics.py"


def test_tier_metric_preseeds_cover_the_matrix():
    """metrics.py pre-seeds the tiered-KV hit/miss matrix (tier 0/1/2),
    the spill counter, and the restitch histogram so dashboards read 0,
    not absent, on engines that never spill."""
    # a second, private load of the module: a registry that nothing but
    # the seeding code has touched, so the 0 below is the pre-seed's
    # whatever restitching test this worker ran before
    import importlib.util
    from ollama_operator_tpu.server import metrics as served
    spec = importlib.util.spec_from_file_location("_fresh_metrics",
                                                  served.__file__)
    fresh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fresh)
    rendered = fresh.GLOBAL.render()
    for fam in ("tpu_model_tier_hit_tokens_total",
                "tpu_model_tier_miss_tokens_total"):
        for tier in ("0", "1", "2"):
            series = f'{fam}{{tier="{tier}"}}'
            assert series in rendered, f"{series} not pre-seeded"
    assert "\ntpu_model_spilled_pages_total " in "\n" + rendered
    assert "tpu_model_restitch_seconds_bucket" in rendered
    assert "tpu_model_restitch_seconds_count 0" in rendered


def test_retry_transient_backoff_and_classification():
    from ollama_operator_tpu.operator.client import (ApiError, Conflict,
                                                     NotFound,
                                                     retry_transient)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ApiError(503, "apiserver hiccup")
        return "ok"

    assert retry_transient(flaky, backoff=0.001) == "ok"
    assert calls["n"] == 3

    # 4xx are real answers — never retried
    for exc in (NotFound(404, "gone"), Conflict(409, "rv"),
                ApiError(400, "bad")):
        calls["n"] = 0

        def fail_4xx(exc=exc):
            calls["n"] += 1
            raise exc

        with pytest.raises(ApiError):
            retry_transient(fail_4xx, backoff=0.001)
        assert calls["n"] == 1

    # exhausted attempts re-raise the transient error
    def always_503():
        calls["n"] += 1
        raise ApiError(500, "down")

    calls["n"] = 0
    with pytest.raises(ApiError):
        retry_transient(always_503, attempts=3, backoff=0.001)
    assert calls["n"] == 3
