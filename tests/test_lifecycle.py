"""Stream-preserving restart recovery, graceful drain, and the
hung-dispatch watchdog (the PR 9 lifecycle layer in runtime/scheduler.py).

The replay chaos drills here are the zero-error counterparts of the
exactly-once error drills in test_faults/test_paged_async/test_stall_free
(which pin the fallback path with TPU_RESTART_REPLAY_MAX=0): with replay
ON, an engine failure mid-stream must be INVISIBLE to a deterministic
client — same tokens, same queue, no error frame — because the rebuilt
engine re-prefills prompt+generated through the preempt/resume machinery
and greedy/seeded sampling is bit-identical by construction (engine.py
seeds are slot-independent for opts.seed >= 0 and per-step keys fold in
the absolute position).
"""

import queue as queue_mod
import time

import numpy as np
import pytest

from ollama_operator_tpu.runtime.engine import SlotOptions
from ollama_operator_tpu.runtime.errors import DeadlineExceeded
from ollama_operator_tpu.runtime.faults import FAULTS
from ollama_operator_tpu.runtime.scheduler import SchedulerBusy
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

from test_scheduler import GREEDY, make_stack

SEEDED = SlotOptions(temperature=0.9, seed=1234, repeat_penalty=1.0)
UNSEEDED = SlotOptions(temperature=0.9, repeat_penalty=1.0)   # seed=-1

PROMPT = np.array([5, 6], np.int32)


def _fail_decode_once(eng, fail_on=2):
    """Make the Nth decode entry raise (counting both the sync path and
    the async launch), then serve normally — one deterministic mid-stream
    engine failure, unlike an armed fail:after rule which fires forever."""
    calls = {"n": 0}
    real_decode_n = eng.decode_n
    real_launch = eng.decode_n_launch

    def flaky(n=None):
        calls["n"] += 1
        if calls["n"] == fail_on:
            raise RuntimeError("injected mid-stream failure")
        return real_decode_n(n)

    def flaky_launch(n=None):
        calls["n"] += 1
        if calls["n"] == fail_on:
            raise RuntimeError("injected mid-stream failure")
        return real_launch(n)

    eng.decode_n = flaky
    eng.decode_n_launch = flaky_launch
    return calls


def _reference(opts, max_tokens=24):
    """Uninterrupted run of PROMPT on a fresh stack."""
    cfg, params, eng, sched = make_stack(slots=2)
    try:
        return list(sched.submit(PROMPT, opts, max_tokens=max_tokens)
                    .tokens())
    finally:
        sched.shutdown()


# -- replay: zero-error, bit-identical continuation --------------------

@pytest.mark.chaos
def test_replay_greedy_zero_errors_bit_identical():
    """Tentpole acceptance: a mid-stream engine failure with replay on
    is client-invisible for a greedy stream — the SAME output queue
    carries the SAME tokens, no error frame, and the replay counters
    account for the re-prefilled work."""
    ref = _reference(GREEDY)
    assert len(ref) >= 8                       # failure lands mid-stream
    cfg, params, eng, sched = make_stack(slots=2, restart_backoff=0.001)
    rr0 = METRICS.get("tpu_model_replayed_requests_total")
    rt0 = METRICS.get("tpu_model_replayed_tokens_total")
    try:
        _fail_decode_once(eng, fail_on=2)
        r = sched.submit(PROMPT, GREEDY, max_tokens=24)
        out = list(r.tokens())                 # must NOT raise
        assert out == ref
        assert r.error is None
        assert r.done_reason in ("stop", "length")
        with pytest.raises(queue_mod.Empty):   # stream is terminal
            r.out.get_nowait()
        assert sched.n_replays == 1
        assert sched.n_replay_fallbacks == 0
        assert sched.n_restarts == 1
        assert not sched.broken
        assert METRICS.get("tpu_model_replayed_requests_total") == rr0 + 1
        # token cost = prompt + generated-so-far at failure time
        assert METRICS.get("tpu_model_replayed_tokens_total") > rt0
    finally:
        sched.shutdown()


@pytest.mark.chaos
def test_replay_seeded_zero_errors_bit_identical():
    """Seeded sampling (opts.seed >= 0) is in the determinism contract:
    the base key is slot-independent and per-step keys fold in the
    absolute position, so replay continues byte-identical."""
    ref = _reference(SEEDED)
    assert len(ref) >= 8
    cfg, params, eng, sched = make_stack(slots=2, restart_backoff=0.001)
    try:
        _fail_decode_once(eng, fail_on=2)
        r = sched.submit(PROMPT, SEEDED, max_tokens=24)
        out = list(r.tokens())
        assert out == ref
        assert r.error is None
        assert sched.n_replays == 1
    finally:
        sched.shutdown()


@pytest.mark.chaos
def test_replay_both_streams_recover_and_new_work_serves():
    """Two concurrent greedy streams both replay after one failure, and
    the scheduler keeps serving fresh work afterwards."""
    cfg, params, eng, sched = make_stack(slots=2, restart_backoff=0.001)
    try:
        _fail_decode_once(eng, fail_on=2)
        reqs = [sched.submit(np.array([i + 1, i + 2], np.int32), GREEDY,
                             max_tokens=16) for i in range(2)]
        outs = [list(r.tokens()) for r in reqs]
        assert all(len(o) == 16 for o in outs)
        assert all(r.error is None for r in reqs)
        assert sched.n_replays == 2
        r2 = sched.submit(np.array([9], np.int32), GREEDY, max_tokens=3)
        assert len(list(r2.tokens())) == 3
    finally:
        sched.shutdown()


def test_replay_unseeded_sampling_errors_exactly_once():
    """Unseeded temperature sampling derives its RNG from (slot,
    seq_len) — not replayable. Fail-safe: today's exactly-one error
    frame, counted under cause="nondeterministic"."""
    cfg, params, eng, sched = make_stack(slots=2, restart_backoff=0.001)
    fb0 = METRICS.get("tpu_model_replay_fallback_total",
                      '{cause="nondeterministic"}')
    try:
        _fail_decode_once(eng, fail_on=2)
        r = sched.submit(PROMPT, UNSEEDED, max_tokens=24)
        with pytest.raises(RuntimeError, match="injected"):
            list(r.tokens())
        with pytest.raises(queue_mod.Empty):   # exactly once
            r.out.get_nowait()
        assert sched.n_replays == 0
        assert sched.n_replay_fallbacks == 1
        assert METRICS.get("tpu_model_replay_fallback_total",
                           '{cause="nondeterministic"}') == fb0 + 1
    finally:
        sched.shutdown()


def test_replay_over_budget_errors_exactly_once(monkeypatch):
    """ISSUE acceptance: a replay-ineligible failure (over the token
    budget) produces exactly ONE error, never a duplicate or a hang."""
    monkeypatch.setenv("TPU_RESTART_REPLAY_TOKENS", "1")
    cfg, params, eng, sched = make_stack(slots=2, restart_backoff=0.001)
    fb0 = METRICS.get("tpu_model_replay_fallback_total",
                      '{cause="over_budget"}')
    try:
        _fail_decode_once(eng, fail_on=2)
        r = sched.submit(PROMPT, GREEDY, max_tokens=24)
        with pytest.raises(RuntimeError, match="injected"):
            list(r.tokens())
        with pytest.raises(queue_mod.Empty):
            r.out.get_nowait()
        assert sched.n_replays == 0
        assert METRICS.get("tpu_model_replay_fallback_total",
                           '{cause="over_budget"}') == fb0 + 1
        # the loop recovered regardless: fresh work serves
        r2 = sched.submit(np.array([9], np.int32), GREEDY, max_tokens=3)
        assert len(list(r2.tokens())) == 3
    finally:
        sched.shutdown()


@pytest.mark.chaos
def test_replay_fault_point_forces_fallback():
    """scheduler.replay fail: the injected fault must push the stream
    down the fail-safe exactly-once error path (cause="faulted"), not
    crash the classification loop."""
    cfg, params, eng, sched = make_stack(slots=2, restart_backoff=0.001)
    fb0 = METRICS.get("tpu_model_replay_fallback_total",
                      '{cause="faulted"}')
    try:
        FAULTS.arm("scheduler.replay", "fail")
        _fail_decode_once(eng, fail_on=2)
        r = sched.submit(PROMPT, GREEDY, max_tokens=24)
        with pytest.raises(RuntimeError, match="injected mid-stream"):
            list(r.tokens())
        with pytest.raises(queue_mod.Empty):
            r.out.get_nowait()
        assert METRICS.get("tpu_model_replay_fallback_total",
                           '{cause="faulted"}') == fb0 + 1
    finally:
        FAULTS.disarm("scheduler.replay")
        sched.shutdown()


def test_replay_eligibility_classification():
    """The determinism contract, as a table."""
    from ollama_operator_tpu.runtime.scheduler import Scheduler

    class R:
        embeds = None
        opts = GREEDY

    r = R()
    assert Scheduler._replay_ineligible(r) is None          # greedy
    r.opts = SEEDED
    assert Scheduler._replay_ineligible(r) is None          # seeded
    r.opts = UNSEEDED
    assert Scheduler._replay_ineligible(r) == "nondeterministic"
    r.opts = SlotOptions(temperature=0.0, mirostat=2)
    assert Scheduler._replay_ineligible(r) == "nondeterministic"
    r.opts = GREEDY
    r.embeds = object()
    assert Scheduler._replay_ineligible(r) == "multimodal"


# -- graceful drain ----------------------------------------------------

def test_drain_sheds_new_submits_and_running_completes():
    """begin_drain: new submits shed 503 + Retry-After immediately;
    streams already running keep generating to completion."""
    cfg, params, eng, sched = make_stack(slots=1)
    ds0 = METRICS.get("tpu_model_drain_started_total")
    try:
        r = sched.submit(PROMPT, GREEDY, max_tokens=12)
        it = r.tokens()
        next(it)                                # running for sure
        sched.begin_drain()
        assert METRICS.get("tpu_model_drain_started_total") == ds0 + 1
        sched.begin_drain()                     # idempotent
        assert METRICS.get("tpu_model_drain_started_total") == ds0 + 1
        with pytest.raises(SchedulerBusy) as ei:
            sched.submit(np.array([9], np.int32), GREEDY, max_tokens=1)
        assert ei.value.retry_after_s >= 1
        rest = list(it)                         # finishes, not shed
        assert len(rest) >= 1
        assert r.done_reason in ("stop", "length")
        assert sched.lifecycle_stats()["state"] == "draining"
        # nothing left: drain returns without shedding anyone
        assert sched.drain(timeout_s=5) == 0
    finally:
        sched.shutdown()


def test_drain_timeout_sheds_stragglers():
    """drain(timeout) with an unbounded stream still running: the
    straggler gets a terminal ("done", "drain") frame (partial output
    stands) and waiting requests shed 503 with Retry-After."""
    cfg, params, eng, sched = make_stack(slots=1)
    sh0 = METRICS.get("tpu_model_drain_shed_total")
    try:
        r_run = sched.submit(PROMPT, GREEDY, max_tokens=10_000)
        it = r_run.tokens()
        next(it)                                # occupies the only slot
        # slow every decode step so the stream can't finish (or the
        # queued request get admitted) inside the drain window
        FAULTS.arm("engine.step", "delay:150ms")
        r_q = sched.submit(np.array([9], np.int32), GREEDY, max_tokens=4)
        shed = sched.drain(timeout_s=0.4)
        assert shed == 2
        assert METRICS.get("tpu_model_drain_shed_total") >= sh0 + 2
        list(it)                                # drains to the done frame
        assert r_run.done_reason == "drain"
        with pytest.raises(DeadlineExceeded) as ei:
            list(r_q.tokens())
        assert ei.value.while_queued
        assert ei.value.retry_after_s >= 1
        assert sched.n_active == 0
    finally:
        FAULTS.disarm("engine.step")
        sched.shutdown()


def test_drain_timeout_env_default(monkeypatch):
    from ollama_operator_tpu.runtime.scheduler import drain_timeout_s
    monkeypatch.delenv("TPU_DRAIN_TIMEOUT_S", raising=False)
    assert drain_timeout_s() == 30.0
    monkeypatch.setenv("TPU_DRAIN_TIMEOUT_S", "7.5")
    assert drain_timeout_s() == 7.5


# -- hung-dispatch watchdog --------------------------------------------

@pytest.mark.chaos
def test_watchdog_fires_and_replay_recovers(monkeypatch):
    """engine.watchdog delay (a wedged dispatch): the watchdog fires at
    its budget, the wait is abandoned, the supervisor restarts, and the
    stream REPLAYS to the same tokens an unwedged run produces."""
    ref = _reference(GREEDY, max_tokens=10)
    monkeypatch.setenv("TPU_DISPATCH_WATCHDOG_MS", "300")
    cfg, params, eng, sched = make_stack(slots=2, restart_backoff=0.001)
    wf0 = METRICS.get("tpu_model_watchdog_fires_total")
    try:
        # the wedge outlives the whole test: only the abandon path can
        # unblock the stream (the :once mode disarms it for the retry)
        FAULTS.arm("engine.watchdog", "delay:30s:once")
        t0 = time.monotonic()
        r = sched.submit(PROMPT, GREEDY, max_tokens=10)
        out = list(r.tokens())
        assert time.monotonic() - t0 < 20      # abandoned, not waited out
        assert out == ref
        assert r.error is None
        assert sched.n_watchdog_fires == 1
        assert sched.n_replays >= 1
        assert sched.n_restarts >= 1
        assert not sched.broken
        assert METRICS.get("tpu_model_watchdog_fires_total") == wf0 + 1
    finally:
        FAULTS.disarm("engine.watchdog")
        sched.shutdown()


def test_watchdog_timeout_knob(monkeypatch):
    cfg, params, eng, sched = make_stack(slots=1)
    try:
        monkeypatch.setenv("TPU_DISPATCH_WATCHDOG_MS", "0")
        assert sched._watchdog_timeout_s() == 0.0      # disabled
        monkeypatch.setenv("TPU_DISPATCH_WATCHDOG_MS", "2500")
        assert sched._watchdog_timeout_s() == 2.5
        monkeypatch.delenv("TPU_DISPATCH_WATCHDOG_MS")
        # auto mode: histogram-derived, clamped to [15s, 120s] — never
        # tighter than the 15s floor whatever this session observed
        assert 15.0 <= sched._watchdog_timeout_s() <= 120.0
    finally:
        sched.shutdown()


def test_watched_ferries_results_and_exceptions(monkeypatch):
    """_watched is transparent when nothing wedges: values return,
    exceptions re-raise on the scheduler thread."""
    monkeypatch.setenv("TPU_DISPATCH_WATCHDOG_MS", "5000")
    cfg, params, eng, sched = make_stack(slots=1)
    try:
        assert sched._watched(lambda: 42) == 42
        with pytest.raises(ValueError, match="boom"):
            sched._watched(lambda: (_ for _ in ()).throw(ValueError("boom")))
        # the persistent worker survives a ferried exception
        assert sched._watched(lambda: "ok") == "ok"
    finally:
        sched.shutdown()


# -- /api/ps lifecycle block ------------------------------------------

def test_lifecycle_stats_shape():
    cfg, params, eng, sched = make_stack(slots=1)
    try:
        st = sched.lifecycle_stats()
        assert st["state"] == "serving"
        assert st["replay"]["enabled"] is True
        assert st["replay"]["max_streams"] == 64
        assert st["replay"]["token_budget"] == 65536
        assert st["replay"]["replayed_streams"] == 0
        assert st["watchdog"]["timeout_s"] > 0
        sched.begin_drain()
        assert sched.lifecycle_stats()["state"] == "draining"
    finally:
        sched.shutdown()
