"""Stall-free continuous batching (chunked prefill + double-buffered
async dispatch + batched admission).

The invariants under test:
- chunked admission is BIT-IDENTICAL to one-shot admission (every prompt
  length, with and without prefix-cache reuse, and across a
  preempt-and-readmit mid-prefill) — the final piece's PRNG seed derives
  from (slot, full prompt length), same as a one-shot admit;
- async double-buffered dispatch delivers the same streams in the same
  order as synchronous dispatch;
- batched same-bucket admission (admit_many) matches per-slot admits;
- a supervisor restart mid-pipeline (async decode in flight, or a
  chunked prefill mid-piece) errors each in-flight request exactly once
  and the next request serves normally;
- an exhausted max_tokens budget finishes with done_reason "length"
  (Ollama semantics: truncation, not a natural stop).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)
from ollama_operator_tpu.runtime.faults import FAULTS
from ollama_operator_tpu.runtime.paged import PagesExhausted
from ollama_operator_tpu.runtime.scheduler import Scheduler
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)


@pytest.fixture(scope="module")
def eng():
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    return Engine(cfg, params,
                  ecfg=EngineConfig(max_slots=4, max_seq_len=64,
                                    cache_dtype=jnp.float32,
                                    min_prefill_bucket=16))


@pytest.fixture(autouse=True)
def _clean_slots(eng):
    yield
    for s in range(eng.n_slots):
        eng.release(s)


def prompt(n, base=1):
    return ((np.arange(n) + base) % 50 + 1).astype(np.int32)


def run_one(eng, ids, *, prefill_chunk, async_dispatch, max_tokens=6):
    """One request through a fresh scheduler; returns (tokens, reason)."""
    sched = Scheduler(eng, prefill_chunk=prefill_chunk,
                      async_dispatch=async_dispatch)
    try:
        r = sched.submit(np.asarray(ids, np.int32), GREEDY,
                         max_tokens=max_tokens)
        toks = list(r.tokens())
        return toks, r.done_reason
    finally:
        sched.shutdown()
        for s in range(eng.n_slots):
            eng.release(s)


def manual(sched):
    """Stop the loop thread so tests can drive _step() deterministically."""
    sched._stop.set()
    sched._wake.set()
    sched._thread.join(timeout=5)
    return sched


# ---------------------------------------------------------------- parity

@pytest.mark.parametrize("plen", [17, 24, 33, 40, 48])
def test_chunked_admission_parity(eng, plen):
    """Chunked admission (16-token pieces) streams the exact one-shot
    tokens for every prompt length spanning the bucket ladder."""
    ids = prompt(plen)
    base, base_reason = run_one(eng, ids, prefill_chunk=0,
                                async_dispatch=False)
    c0 = METRICS.get("tpu_model_prefill_chunks_total")
    chunked, reason = run_one(eng, ids, prefill_chunk=16,
                              async_dispatch=False)
    assert chunked == base
    assert reason == base_reason
    # first piece + at least one interleaved piece actually ran
    assert METRICS.get("tpu_model_prefill_chunks_total") - c0 >= 2


def test_chunked_prefix_reuse_parity(eng):
    """A chunked admission whose first piece reuses a parked prefix
    (engine.extend from the parked length) still matches one-shot."""
    p1 = prompt(20)
    sched = Scheduler(eng, prefill_chunk=16, async_dispatch=False)
    try:
        r1 = sched.submit(p1, GREEDY, max_tokens=4)
        out1 = list(r1.tokens())
        # continuation prompt: the parked tokens plus a >1-piece tail
        p2 = np.concatenate([p1, np.asarray(out1, np.int32),
                             prompt(20, base=30)])
        r2 = sched.submit(p2, GREEDY, max_tokens=4)
        out2 = list(r2.tokens())
        assert r2.stats.n_reused >= Scheduler.MIN_PREFIX_REUSE
    finally:
        sched.shutdown()
        for s in range(eng.n_slots):
            eng.release(s)
    base, _ = run_one(eng, p2, prefill_chunk=0, async_dispatch=False,
                      max_tokens=4)
    assert out2 == base


def test_preempt_mid_chunked_prefill_readmits(eng, monkeypatch):
    """PagesExhausted on an interleaved piece requeues the request; the
    re-admission restarts the prompt and the stream is still exactly the
    one-shot stream (no tokens were emitted before the preempt)."""
    ids = prompt(40)
    base, _ = run_one(eng, ids, prefill_chunk=0, async_dispatch=False)
    calls = {"n": 0}
    orig = eng.extend

    def flaky(slot, full_ids, start, *a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise PagesExhausted("injected mid-prefill pool pressure")
        return orig(slot, full_ids, start, *a, **kw)

    monkeypatch.setattr(eng, "extend", flaky)
    out, reason = run_one(eng, ids, prefill_chunk=16, async_dispatch=False)
    assert calls["n"] >= 2           # the preempted piece was retried
    assert out == base
    assert reason in ("stop", "length")


def test_async_dispatch_parity(eng):
    """Double-buffered dispatch: same streams, same order, as sync."""
    prompts = [prompt(6 + 3 * i, base=7 * i) for i in range(4)]
    outs = {}
    for async_d in (False, True):
        sched = Scheduler(eng, prefill_chunk=0, async_dispatch=async_d)
        try:
            reqs = [sched.submit(p, GREEDY, max_tokens=9) for p in prompts]
            outs[async_d] = [list(r.tokens()) for r in reqs]
        finally:
            sched.shutdown()
            for s in range(eng.n_slots):
                eng.release(s)
    assert outs[True] == outs[False]
    assert all(len(o) == 9 for o in outs[True])


def test_chunk_frames_arrive_in_order(eng):
    """Per-dispatch frames under async dispatch concatenate to the token
    stream (no reorder, no duplicate, no loss)."""
    sched = Scheduler(eng, prefill_chunk=0, async_dispatch=True)
    try:
        r = sched.submit(prompt(8), GREEDY, max_tokens=20)
        frames = list(r.chunks())
        flat = [t for f in frames for t in f]
        assert len(flat) == 20
        assert flat == r.all_tokens[:20]
        assert r.done_reason == "length"
    finally:
        sched.shutdown()


def test_interleaved_prefill_keeps_decoders_running(eng):
    """A long chunked admission interleaves with active decoders: every
    stream still matches its solo greedy run (per-slot rows are
    independent), and the decoders keep producing between pieces."""
    bg1, bg2, long_p = prompt(6), prompt(9, base=11), prompt(44, base=3)
    base_bg1, _ = run_one(eng, bg1, prefill_chunk=0, async_dispatch=False,
                          max_tokens=16)
    base_bg2, _ = run_one(eng, bg2, prefill_chunk=0, async_dispatch=False,
                          max_tokens=16)
    base_long, _ = run_one(eng, long_p, prefill_chunk=0,
                           async_dispatch=False, max_tokens=4)
    sched = Scheduler(eng, prefill_chunk=16, async_dispatch=True)
    try:
        r1 = sched.submit(bg1, GREEDY, max_tokens=16)
        r2 = sched.submit(bg2, GREEDY, max_tokens=16)
        time.sleep(0.05)           # let the decoders start
        rl = sched.submit(long_p, GREEDY, max_tokens=4)
        assert list(r1.tokens()) == base_bg1
        assert list(r2.tokens()) == base_bg2
        assert list(rl.tokens()) == base_long
    finally:
        sched.shutdown()
        for s in range(eng.n_slots):
            eng.release(s)


# ------------------------------------------------------ batched admission

def test_admit_many_matches_single_admits(eng):
    """One batched prefill dispatch == per-slot admits: same first
    tokens, same cache state (verified by decoding a chunk after)."""
    p1, p2 = prompt(14), prompt(11, base=23)
    t1 = eng.admit(0, p1, GREEDY)
    t2 = eng.admit(1, p2, GREEDY)
    rows_single = np.asarray(eng.decode_n(8))[:, :2].copy()
    for s in range(eng.n_slots):
        eng.release(s)
    toks = eng.admit_many([0, 1], [p1, p2], [GREEDY, GREEDY])
    assert toks == [t1, t2]
    rows_batched = np.asarray(eng.decode_n(8))[:, :2]
    np.testing.assert_array_equal(rows_batched, rows_single)


def test_scheduler_batches_same_bucket_admissions(eng, monkeypatch):
    """Several same-bucket waiters admit in ONE admit_many dispatch, and
    their streams match sequential one-shot runs."""
    prompts = [prompt(10, base=5 * i) for i in range(4)]
    bases = [run_one(eng, p, prefill_chunk=0, async_dispatch=False,
                     max_tokens=5)[0] for p in prompts]
    calls = []
    orig = eng.admit_many

    def spy(slots, ids_list, opts_list=None):
        calls.append(list(slots))
        return orig(slots, ids_list, opts_list)

    monkeypatch.setattr(eng, "admit_many", spy)
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=False))
    try:
        reqs = [sched.submit(p, GREEDY, max_tokens=5) for p in prompts]
        for _ in range(64):
            sched._step()
            if (all(sched._running[s] is None
                    for s in range(eng.n_slots))
                    and sched._admission.empty()
                    and not sched._prefilling):
                break
        outs = [list(r.tokens()) for r in reqs]
    finally:
        sched.shutdown()
        for s in range(eng.n_slots):
            eng.release(s)
    assert calls and len(calls[0]) == 4    # one batched dispatch of 4
    assert outs == bases


def test_admit_many_fault_falls_back_to_single(eng):
    """A failing batched dispatch retries each member on the single-admit
    path — no request is lost or double-admitted."""
    prompts = [prompt(10, base=5 * i) for i in range(2)]
    bases = [run_one(eng, p, prefill_chunk=0, async_dispatch=False,
                     max_tokens=5)[0] for p in prompts]
    FAULTS.arm("engine.admit", "fail:once")
    try:
        sched = manual(Scheduler(eng, prefill_chunk=0,
                                 async_dispatch=False))
        try:
            reqs = [sched.submit(p, GREEDY, max_tokens=5)
                    for p in prompts]
            for _ in range(64):
                sched._step()
                if all(sched._running[s] is None
                       for s in range(eng.n_slots)) \
                        and sched._admission.empty():
                    break
            outs = [list(r.tokens()) for r in reqs]
        finally:
            sched.shutdown()
            for s in range(eng.n_slots):
                eng.release(s)
    finally:
        FAULTS.disarm("engine.admit")
    assert outs == bases


# ------------------------------------------------------------ semantics

def test_max_tokens_finishes_with_length(eng):
    toks, reason = run_one(eng, prompt(5), prefill_chunk=0,
                           async_dispatch=True, max_tokens=3)
    assert len(toks) == 3
    assert reason == "length"


def test_max_tokens_one_finishes_with_length(eng):
    # budget exhausted by the prefill-sampled token itself
    toks, reason = run_one(eng, prompt(5), prefill_chunk=0,
                           async_dispatch=False, max_tokens=1)
    assert len(toks) == 1
    assert reason == "length"


def test_dispatch_latency_gauges_populate(eng):
    assert set(eng.dispatch_ms) == {"decode", "admit", "extend"}
    run_one(eng, prompt(20), prefill_chunk=16, async_dispatch=True,
            max_tokens=4)
    assert eng.dispatch_ms["decode"] > 0.0
    assert eng.dispatch_ms["extend"] > 0.0


# ----------------------------------------------------------------- chaos

@pytest.mark.chaos
def test_restart_mid_async_pipeline_errors_once(eng, monkeypatch):
    """engine.step dies with a dispatch in flight: the already-computed
    dispatch is delivered, the owner gets exactly ONE error frame, the
    supervisor restarts, and the next request serves."""
    monkeypatch.setenv("TPU_RESTART_REPLAY_MAX", "0")
    sched = Scheduler(eng, prefill_chunk=0, async_dispatch=True,
                      restart_backoff=0.001)
    try:
        FAULTS.arm("engine.step", "fail:after=1")
        r = sched.submit(prompt(6), GREEDY, max_tokens=40)
        got = []
        with pytest.raises(RuntimeError):
            for chunk in r.chunks():
                got.extend(chunk)
        FAULTS.disarm("engine.step")
        # exactly once: nothing further lands on this request's queue
        deadline = time.monotonic() + 1.0
        while sched.n_restarts < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r.out.empty()
        assert sched.n_restarts >= 1
        assert not sched.broken
        # the launch-before-materialise pipeline delivered dispatch N
        # before the failing launch of N+1 surfaced
        assert got == r.all_tokens[:len(got)]
        r2 = sched.submit(prompt(4), GREEDY, max_tokens=4)
        assert len(list(r2.tokens())) == 4
    finally:
        FAULTS.disarm("engine.step")
        sched.shutdown()
        for s in range(eng.n_slots):
            eng.release(s)


@pytest.mark.chaos
def test_restart_mid_chunked_prefill_errors_once(eng, monkeypatch):
    """engine.admit dies on an INTERLEAVED prefill piece (fail:after=1
    lets the first piece through): the supervisor restarts and the
    mid-prefill request errors exactly once."""
    monkeypatch.setenv("TPU_RESTART_REPLAY_MAX", "0")
    sched = Scheduler(eng, prefill_chunk=16, async_dispatch=False,
                      restart_backoff=0.001)
    try:
        c0 = METRICS.get("tpu_model_prefill_chunks_total")
        FAULTS.arm("engine.admit", "fail:after=1")
        r = sched.submit(prompt(40), GREEDY, max_tokens=4)
        with pytest.raises(RuntimeError):
            list(r.tokens())
        FAULTS.disarm("engine.admit")
        deadline = time.monotonic() + 1.0
        while sched.n_restarts < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r.out.empty()
        assert sched.n_restarts >= 1
        assert METRICS.get("tpu_model_prefill_chunks_total") - c0 >= 1
        r2 = sched.submit(prompt(4), GREEDY, max_tokens=3)
        assert len(list(r2.tokens())) == 3
    finally:
        FAULTS.disarm("engine.admit")
        sched.shutdown()
        for s in range(eng.n_slots):
            eng.release(s)


@pytest.mark.chaos
def test_cancel_mid_chunked_prefill(eng):
    """Cancelling a request between prefill pieces frees the slot before
    any token was produced."""
    sched = manual(Scheduler(eng, prefill_chunk=16, async_dispatch=False))
    try:
        r = sched.submit(prompt(40), GREEDY, max_tokens=4)
        sched._step()              # first piece admitted, job registered
        assert sched._prefilling
        r.cancel()
        sched._step()
        assert not sched._prefilling
        assert r.out.get(timeout=1) == ("done", "cancelled")
        assert sched._running[r.slot or 0] is None
    finally:
        sched.shutdown()
        for s in range(eng.n_slots):
            eng.release(s)
