"""An admission pass launches its prefills and the next decode chunk back to
back and collects the first tokens afterwards (Scheduler._launches,
Engine.admit_launch and its two siblings).

The invariants under test:
- a launched admission gives the stream an awaited one gives, bit for bit
  (greedy and seeded; alone and in batched groups of 2 and 4; paged int8,
  contiguous int8, and a hybrid stack with a recurrent state);
- between a pass's first admission launch and the decode launch the host
  fetches nothing from the device;
- a first token that ends its request finds its slot riding the chunk that
  was launched meanwhile: one `done`, the right count, a slot that serves
  the next request as a fresh one would;
- a request cancelled between its launch and its collect leaves one
  terminal frame and a clean slot;
- a fault at collect errors that admission's owner exactly once and nobody
  else; a wedged device at collect goes to the supervisor, which errors (or
  replays) every owner exactly once;
- constrained requests take the awaited form, and
  `tpu_model_admissions_total{mode}` says which form each request took;
- a prompt past one prefill piece launches its pieces too: the streams are
  the awaited pieces' and the one-shot admission's, every job gets pieces
  while the step's budget of prompt tokens lasts (the oldest first), and
  nothing is fetched between the pieces and the chunk launched behind them.
"""

import dataclasses
import queue as queue_mod
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.models.config import PRESETS
from ollama_operator_tpu.runtime.engine import (AdmitHandle, Engine,
                                                EngineConfig, SlotOptions)
from ollama_operator_tpu.runtime.scheduler import Scheduler, WatchdogTimeout
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

TINY = dataclasses.replace(PRESETS["tiny"], kernels="xla")
HYBRID = PRESETS["tiny-hybrid"]
RINGS = PRESETS["tiny-exaone"]
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
SEEDED = SlotOptions(temperature=0.9, top_k=40, seed=1234)
ECFG = EngineConfig(max_slots=4, max_seq_len=128, cache_dtype=jnp.int8,
                    decode_chunk=4, min_prefill_bucket=16)
KINDS = {
    "paged_int8": (TINY, dataclasses.replace(ECFG, paged=True, page_size=16)),
    "contiguous_int8": (TINY, ECFG),
    "hybrid_int8": (HYBRID, ECFG),
}
# the kinds a prompt past one piece is tried on: a window layer's ring is
# one more state that only advances (the piece is two windows long)
PIECE_KINDS = dict(KINDS, rings_int8=(RINGS, ECFG))


@pytest.fixture(scope="module")
def engines():
    """One engine a kind, built when first asked for."""
    built = {}

    def get(kind):
        if kind not in built:
            cfg, ecfg = PIECE_KINDS[kind]
            params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
            built[kind] = Engine(cfg, params, ecfg=ecfg)
        return built[kind]
    return get


@pytest.fixture
def eng(engines):
    return engines("contiguous_int8")


def prompt(n, base=1):
    return ((np.arange(n) + base) % 50 + 3).astype(np.int32)


def manual(sched):
    """Stop the loop thread so a test drives _step() itself."""
    sched._stop.set()
    sched._wake.set()
    sched._thread.join(timeout=5)
    assert not sched._thread.is_alive()
    return sched


def idle(sched):
    return (sched.n_active == 0 and sched.qsize == 0
            and sched._pending is None and not sched._launched
            and not sched.engine.quarantined_pages)


def run_steps(sched, limit=400):
    for _ in range(limit):
        sched._step()
        if idle(sched):
            return
    raise AssertionError("the scheduler did not come to rest")


def clean(eng):
    """Free every slot and forget every donated prefix, so the next
    scheduler's admissions are cold ones whatever ran before."""
    for s in range(eng.n_slots):
        eng.release(s)
    if eng.radix_enabled:
        eng.radix_reset()


def frames(req):
    """Everything on a request's queue, without blocking."""
    out = []
    while True:
        try:
            out.append(req.out.get_nowait())
        except queue_mod.Empty:
            return out


def tokens_of(fr):
    return [t for kind, payload in fr if kind == "tokens" for t in payload]


def modes():
    return {m: METRICS.get("tpu_model_admissions_total", f'{{mode="{m}"}}')
            for m in ("launched", "awaited")}


def moved(before):
    return {m: v - before[m] for m, v in modes().items()}


def spy_on(eng, sched, monkeypatch):
    """The engine's dispatches, its fetches and the fan-outs, in order."""
    log = []
    fetch = eng._fetch

    def spy(name):
        real = getattr(eng, name)

        def call(*a, **kw):
            log.append(name)
            return real(*a, **kw)
        monkeypatch.setattr(eng, name, call)

    for name in ("admit_launch", "admit_many_launch", "extend_launch",
                 "decode_n_launch", "admit", "admit_many", "extend"):
        spy(name)
    monkeypatch.setattr(
        eng, "_fetch", lambda x: (log.append("fetch"), fetch(x))[1])
    fanout = sched._fanout
    monkeypatch.setattr(sched, "_fanout", lambda *a, **kw: (
        log.append("fanout"), fanout(*a, **kw))[1])
    return log


def serve(eng, prompts, opts, *, launched, max_tokens=11, monkeypatch=None,
          **submit_kw):
    """The prompts through one scheduler stepped by hand, all waiting when
    the first pass runs; returns their frames. ``launched=False`` holds
    the loop to the awaited form with everything else as it is."""
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    if not launched:
        monkeypatch.setattr(sched, "_launches", lambda req=None: False)
    try:
        reqs = [sched.submit(p, opts, max_tokens=max_tokens, **submit_kw)
                for p in prompts]
        run_steps(sched)
        return [frames(r) for r in reqs]
    finally:
        sched.shutdown()
        clean(eng)


# ---------------------------------------------------------------- parity

@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("opts", [GREEDY, SEEDED], ids=["greedy", "seeded"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_launched_streams_equal_awaited(engines, monkeypatch, kind, opts,
                                        group):
    """Same-bucket prompts (so groups of 2 and 4 go through admit_many):
    every frame of every request, terminal one included, is the awaited
    form's; the counter names the form each arm took."""
    eng = engines(kind)
    prompts = [prompt(9 + i, base=7 * i) for i in range(group)]
    before = modes()
    want = serve(eng, prompts, opts, launched=False, monkeypatch=monkeypatch)
    assert moved(before) == {"launched": 0, "awaited": group}
    before = modes()
    got = serve(eng, prompts, opts, launched=True)
    assert moved(before) == {"launched": group, "awaited": 0}
    assert got == want
    for fr in got:
        assert len(tokens_of(fr)) == 11 and fr[-1] == ("done", "length")


def test_launched_extend_equals_awaited(eng, monkeypatch):
    """A prefix-reusing admission (Engine.extend_launch) too: the second
    request finds the first one's parked slot."""
    first, second = prompt(20), np.concatenate([prompt(20), prompt(9, 31)])

    def arm(launched):
        sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
        if not launched:
            monkeypatch.setattr(sched, "_launches", lambda req=None: False)
        try:
            out = []
            for p in (first, second):
                r = sched.submit(p, GREEDY, max_tokens=6)
                run_steps(sched)
                out.append((frames(r), r.stats.n_reused))
            return out
        finally:
            sched.shutdown()
            clean(eng)

    want, got = arm(False), arm(True)
    assert got == want
    assert got[1][1] >= 16          # the extend path was the one compared


# ------------------------------------------------------- the pass's order

def test_no_host_fetch_between_admission_launch_and_decode_launch(
        eng, monkeypatch):
    """An engine that records its calls: with a chunk in flight, a pass of
    three admissions (a batched pair and a single of another bucket) and
    the chunk launched behind it fetch nothing from the device; the first
    tokens are fetched after that launch, oldest first, and before the
    fan-out of the chunk that was in flight."""
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    log = spy_on(eng, sched, monkeypatch)
    try:
        r0 = sched.submit(prompt(9), GREEDY, max_tokens=40)
        sched._step()
        assert sched._pending is not None       # a chunk is in flight
        del log[:]
        rs = [sched.submit(p, GREEDY, max_tokens=40)
              for p in (prompt(10, 5), prompt(11, 9), prompt(30, 2))]
        sched._step()
        assert log == ["admit_many_launch", "admit_launch",
                       "decode_n_launch",
                       "fetch",                 # the chunk in flight
                       "fetch", "fetch",        # the pair's, the single's
                       "fanout"]
        assert not sched._launched
        assert all(len(tokens_of(frames(r))) == 1 for r in rs)
        assert sorted(sched._pending[1]) == [0, 1, 2, 3]   # all ride it
        for r in [r0] + rs:
            r.cancel()
        run_steps(sched)
    finally:
        sched.shutdown()
        clean(eng)


# ------------------------------------- a first token that ends its request

@pytest.mark.parametrize("how", ["budget", "eog"])
def test_first_token_that_ends_the_request(engines, how):
    """``num_predict`` 1, or an end-of-generation token first: the slot is
    riding the chunk launched behind the admission when the collect
    finishes it. One `done`, the right count, nothing after it; and the
    slot serves the next request as a slot never used would."""
    eng = engines("paged_int8")
    p, nxt = prompt(9), prompt(12, base=20)
    (ref,) = serve(eng, [nxt], GREEDY, launched=True, max_tokens=9)
    (probe,) = serve(eng, [p], GREEDY, launched=True, max_tokens=3)
    first = tokens_of(probe)[0]
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    try:
        before = modes()
        kw = (dict(max_tokens=1) if how == "budget" else
              dict(max_tokens=9, eog_ids=frozenset([first])))
        r = sched.submit(p, GREEDY, **kw)
        sched._step()
        # finished at the collect, while chunk N+1 carries its slot
        assert sched._pending is not None and 0 in sched._pending[1]
        assert sched._running[0] is None and not eng.active[0]
        run_steps(sched)
        if how == "budget":
            assert frames(r) == [("tokens", [first]), ("done", "length")]
            assert r.stats.n_generated == 1
        else:
            assert frames(r) == [("done", "stop")]
            assert r.stats.n_generated == 0
        assert moved(before) == {"launched": 1, "awaited": 0}
        r2 = sched.submit(nxt, GREEDY, max_tokens=9)
        run_steps(sched)
        assert frames(r2) == ref
    finally:
        sched.shutdown()
        clean(eng)


# ------------------------------------------------------------------ cancel

@pytest.mark.parametrize("when", ["before_the_chunk", "behind_the_chunk"])
def test_cancel_between_launch_and_collect(engines, monkeypatch, when):
    """Cancelled right after its launch, the request leaves its slot before
    the chunk is launched and its token is dropped at the collect;
    cancelled once the chunk carries its slot, it gets its first token and
    leaves on the next pass. Either way one terminal frame, a slot free
    and clean, and the next request's stream is a fresh slot's."""
    eng = engines("paged_int8")
    nxt = prompt(12, base=20)
    (ref,) = serve(eng, [nxt], GREEDY, launched=True, max_tokens=9)
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    hook = ("admit_launch" if when == "before_the_chunk"
            else "decode_n_launch")
    real = getattr(eng, hook)
    reqs = []

    def then_cancel(*a, **kw):
        out = real(*a, **kw)
        reqs[0].cancel()
        return out

    monkeypatch.setattr(eng, hook, then_cancel)
    try:
        reqs.append(sched.submit(prompt(9), GREEDY, max_tokens=9))
        sched._step()
        assert not sched._launched
        run_steps(sched)
        fr = frames(reqs[0])
        assert fr[-1] == ("done", "cancelled")
        assert len(tokens_of(fr)) == (0 if when == "before_the_chunk" else 1)
        assert [k for k, _ in fr].count("done") == 1
        assert sched._running[0] is None and not eng.active[0]
        monkeypatch.setattr(eng, hook, real)
        r2 = sched.submit(nxt, GREEDY, max_tokens=9)
        run_steps(sched)
        assert frames(r2) == ref
    finally:
        sched.shutdown()
        clean(eng)


# ------------------------------------------------------------------ faults

class _FailsAtWait:
    """An admission handle whose program failed on the device: the launch
    went through, the fetch raises."""
    kind = "admit"

    def __init__(self, exc, launched):
        self._exc = exc
        self.t_queued = launched.t_queued
        self.enqueue_s = launched.enqueue_s

    def ready(self):
        return False

    def wait(self):
        raise self._exc


def _fail_second_admission(eng, monkeypatch, exc):
    real = eng.admit_launch
    n = []

    def launch(*a, **kw):
        handle = real(*a, **kw)
        n.append(handle)
        return _FailsAtWait(exc, handle) if len(n) == 2 else handle
    monkeypatch.setattr(eng, "admit_launch", launch)


@pytest.mark.chaos
def test_fault_at_collect_errors_its_owner_once_and_nobody_else(
        engines, monkeypatch):
    """The request whose prefill failed gets one error frame; the one that
    was decoding beside it and the one admitted after it stream what they
    stream alone; no restart."""
    eng = engines("paged_int8")
    p1, p2, p3 = prompt(9), prompt(25, base=4), prompt(12, base=20)
    ref1, ref3 = (serve(eng, [p], GREEDY, launched=True, max_tokens=13)[0]
                  for p in (p1, p3))
    _fail_second_admission(eng, monkeypatch, RuntimeError("HBM parity"))
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    try:
        r1 = sched.submit(p1, GREEDY, max_tokens=13)
        sched._step()
        r2 = sched.submit(p2, GREEDY, max_tokens=13)
        sched._step()
        assert frames(r2) == [("error", "HBM parity")]
        assert r2.error == "HBM parity"
        assert sched._running[1] is None and not eng.active[1]
        r3 = sched.submit(p3, GREEDY, max_tokens=13)
        run_steps(sched)
        assert frames(r2) == []             # exactly once
        assert frames(r1) == ref1
        assert frames(r3) == ref3
        assert sched.n_restarts == 0 and not sched.broken
        if eng.paged:
            eng._pt.check()
    finally:
        sched.shutdown()
        clean(eng)


@pytest.mark.chaos
@pytest.mark.parametrize("replay", [False, True])
def test_wedge_at_collect_goes_to_the_supervisor_exactly_once(
        engines, monkeypatch, replay):
    """A collect that outlasts the watchdog is an engine failure: with
    replay off every owner (the one decoding, the one launched and not
    collected) gets exactly one error frame; with replay on both streams
    go on after the restart as if nothing had happened. The chunk that was
    in flight is delivered first; the next request serves."""
    eng = engines("paged_int8")
    monkeypatch.setenv("TPU_RESTART_REPLAY_MAX", "8" if replay else "0")
    p1, p2 = prompt(9), prompt(25, base=4)
    ref1, ref2 = (serve(eng, [p], GREEDY, launched=True, max_tokens=13)[0]
                  for p in (p1, p2))
    _fail_second_admission(eng, monkeypatch, WatchdogTimeout("wedged"))
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True,
                             restart_backoff=0.001))
    try:
        r1 = sched.submit(p1, GREEDY, max_tokens=13)
        sched._step()                   # r1 decodes, a chunk is in flight
        r2 = sched.submit(p2, GREEDY, max_tokens=13)
        # the loop thread takes over: its first pass launches r2
        sched._stop.clear()
        sched._thread = threading.Thread(target=sched._loop, daemon=True)
        sched._thread.start()
        t1 = time.monotonic() + 10
        while sched.n_restarts < 1 and time.monotonic() < t1:
            time.sleep(0.005)
        assert sched.n_restarts == 1 and not sched.broken
        if replay:
            assert list(r1.tokens()) == tokens_of(ref1)
            assert list(r2.tokens()) == tokens_of(ref2)
        else:
            for r, ref in ((r1, ref1), (r2, ref2)):
                got = []
                with pytest.raises(RuntimeError, match="wedged"):
                    for chunk in r.chunks():
                        got.extend(chunk)
                assert got == tokens_of(ref)[:len(got)]
            assert len(got) == 0            # r2's token never arrived
        time.sleep(0.05)
        assert r1.out.empty() and r2.out.empty()    # exactly once
        r3 = sched.submit(p1, GREEDY, max_tokens=13)
        assert list(r3.tokens()) == tokens_of(ref1)
        assert not sched._launched
    finally:
        sched.shutdown()
        clean(eng)


# --------------------------------------------- pages behind the fence

def test_a_pass_that_drains_for_fenced_pages_admits_where_it_stands():
    """The pool holds one request at a time; the second arrives while the
    first's last chunk is in flight. Its pass finds the pool dry, drains
    the pipeline (the first finishes in that fan-out and its pages leave
    the quarantine) and admits the second in the same pass: nobody is
    sent round to the next one beside pages that are free."""
    cfg, ecfg = KINDS["paged_int8"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    eng = Engine(cfg, params, ecfg=dataclasses.replace(
        ecfg, max_slots=2, n_pages=3))
    pa, pb = prompt(20), prompt(20, base=11)
    (ref,) = serve(eng, [pb], GREEDY, launched=True, max_tokens=9)
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    try:
        ra = sched.submit(pa, GREEDY, max_tokens=5)
        sched._step()                   # first token; 4 more in flight
        assert sched._pending is not None and eng.free_pages == 1
        rb = sched.submit(pb, GREEDY, max_tokens=9)
        sched._step()
        assert frames(ra)[-1] == ("done", "length")
        assert rb in sched._running and not sched._preempted
        assert sched.n_preemptions == 0
        run_steps(sched)
        assert frames(rb) == ref
        eng._pt.check()
    finally:
        sched.shutdown()
        clean(eng)


def test_a_pass_that_evicts_for_pages_admits_where_it_stands():
    """Nothing in flight and the pool full of a finished request's donated
    pages: the pass evicts them (free at once, no dispatch holds them) and
    admits the newcomer in the same pass."""
    cfg, ecfg = KINDS["paged_int8"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    eng = Engine(cfg, params, ecfg=dataclasses.replace(
        ecfg, max_slots=2, n_pages=4))
    pa, pb = prompt(40), prompt(20, base=11)
    (ref,) = serve(eng, [pb], GREEDY, launched=True, max_tokens=9)
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    try:
        ra = sched.submit(pa, GREEDY, max_tokens=9)
        run_steps(sched)
        assert frames(ra)[-1] == ("done", "length")
        assert eng.radix_pages == 3 and eng.free_pages == 1
        rb = sched.submit(pb, GREEDY, max_tokens=9)
        sched._step()
        assert rb in sched._running and not sched._preempted
        assert eng.radix_pages < 3
        run_steps(sched)
        assert frames(rb) == ref
        eng._pt.check()
    finally:
        sched.shutdown()
        clean(eng)


@pytest.mark.parametrize("reuse", [0, 16], ids=["cold", "with_a_prefix"])
def test_only_a_cold_request_is_tried_again_in_the_pass(engines, monkeypatch,
                                                        reuse):
    """A pool that stays dry whatever is reclaimed: a request that came
    cold is tried once more after each reclaiming and then requeued; one
    that came with a stitched prefix takes the cold fallback it always
    took and goes to the next pass, which stitches it again."""
    from ollama_operator_tpu.runtime.paged import PagesExhausted
    eng = engines("paged_int8")
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    calls = []

    def dry(name):
        def f(*a, **kw):
            calls.append(name)
            raise PagesExhausted("dry")
        return f
    monkeypatch.setattr(eng, "admit_launch", dry("admit"))
    monkeypatch.setattr(eng, "extend_launch", dry("extend"))
    monkeypatch.setattr(sched, "_evict_one_parked", lambda n=1: True)
    try:
        req = sched.submit(prompt(20), GREEDY, max_tokens=3)
        assert sched._admission.pop() is req
        assert sched._admit_one(0, req, reuse) is False
        assert sched._preempted == [req] and not sched._launched
        assert calls == (["extend", "admit"] if reuse else ["admit"] * 3)
    finally:
        sched._preempted.clear()
        sched.shutdown()
        clean(eng)


# -------------------------------------------------- what stays awaited

def test_a_synchronous_loop_awaits(eng):
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=False))
    try:
        before = modes()
        r = sched.submit(prompt(9), GREEDY, max_tokens=5)
        run_steps(sched)
        assert len(tokens_of(frames(r))) == 5
        assert moved(before) == {"launched": 0, "awaited": 1}
    finally:
        sched.shutdown()


@pytest.mark.parametrize("device_grammar", [False, True])
def test_a_constrained_request_is_awaited(monkeypatch, device_grammar):
    """Its first token advances the automaton and the mask of its first
    decode step follows from that, host-masked or through the device's
    table; the plain request of the same pass is launched."""
    from ollama_operator_tpu.ops.constrain import (
        INITIAL_STATE, JsonConstraint, advance_bytes)
    from test_constrain import EOS, PIECES, make_table
    params = decoder.init_params(TINY, jax.random.key(0), jnp.float32)
    eng = Engine(TINY, params, ecfg=dataclasses.replace(
        ECFG, cache_dtype=jnp.float32, paged=True, page_size=8))
    if not device_grammar:
        monkeypatch.setattr(eng, "_grammar_device", False)
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    try:
        before = modes()
        rc = sched.submit([5, 9, 2],
                          SlotOptions(temperature=0.9, seed=1,
                                      repeat_penalty=1.0),
                          max_tokens=12, eog_ids=frozenset([EOS]),
                          constraint=JsonConstraint(make_table()))
        rp = sched.submit(prompt(9), GREEDY, max_tokens=5)
        sched._step()
        assert moved(before) == {"launched": 1, "awaited": 1}
        run_steps(sched)
        toks = tokens_of(frames(rc))
        assert toks
        assert advance_bytes(INITIAL_STATE,
                             b"".join(PIECES[t] for t in toks)) is not None
        assert len(tokens_of(frames(rp))) == 5
    finally:
        sched.shutdown()


# ------------------------------------------------- a prompt past one piece

def serve_pieces(eng, prompts, opts, *, piece, launched, budget=None,
                 monkeypatch=None, max_tokens=7):
    """``serve`` with prompts admitted in pieces of ``piece`` tokens (0:
    whole), ``budget`` prompt tokens of pieces a step."""
    sched = manual(Scheduler(eng, prefill_chunk=piece, async_dispatch=True))
    if budget is not None:
        sched._piece_tokens = budget
    if not launched:
        monkeypatch.setattr(sched, "_launches", lambda req=None: False)
    try:
        reqs = [sched.submit(p, opts, max_tokens=max_tokens)
                for p in prompts]
        run_steps(sched)
        return [frames(r) for r in reqs]
    finally:
        sched.shutdown()
        clean(eng)


@pytest.mark.parametrize("budget", [16, 64])
@pytest.mark.parametrize("opts", [GREEDY, SEEDED], ids=["greedy", "seeded"])
@pytest.mark.parametrize("kind", list(PIECE_KINDS))
def test_launched_pieces_give_the_awaited_and_the_one_shot_stream(
        engines, monkeypatch, kind, opts, budget):
    """Three prompts of two and three pieces and one of one piece, all
    waiting at the first pass: each stream is what awaited pieces give, at
    a budget of one piece a step and at one that takes a whole prompt in a
    step; greedy, also what an admission in one piece gives (a piece
    attends over its predecessors' int8 keys where a whole prompt attends
    over its own unrounded ones: a sampler at temperature 0.9 can tell)."""
    eng = engines(kind)
    prompts = [prompt(40, 2), prompt(21, 9), prompt(12, 5), prompt(33, 17)]
    whole = serve_pieces(eng, prompts, opts, piece=0, launched=True)
    before = modes()
    want = serve_pieces(eng, prompts, opts, piece=16, launched=False,
                        monkeypatch=monkeypatch)
    assert moved(before) == {"launched": 0, "awaited": 4}
    before = modes()
    c0 = METRICS.get("tpu_model_prefill_chunks_total")
    got = serve_pieces(eng, prompts, opts, piece=16, launched=True,
                       budget=budget)
    assert moved(before) == {"launched": 4, "awaited": 0}
    assert METRICS.get("tpu_model_prefill_chunks_total") - c0 == 3 + 2 + 3
    assert got == want
    if opts is GREEDY:
        assert got == whole
    for fr in got:
        assert len(tokens_of(fr)) == 7 and fr[-1] == ("done", "length")


def test_no_job_waits_for_anothers_pieces(eng, monkeypatch):
    """With a chunk in flight, two prompts of two pieces are both in
    after ONE pass: four prefill launches and the chunk behind them with
    no fetch between, then the chunk in flight, the four collects and the
    fan-out; both first tokens are out and both slots ride the chunk."""
    sched = manual(Scheduler(eng, prefill_chunk=16, async_dispatch=True))
    sched._piece_tokens = 64
    log = spy_on(eng, sched, monkeypatch)
    try:
        r0 = sched.submit(prompt(9), GREEDY, max_tokens=40)
        sched._step()
        assert sched._pending is not None       # a chunk is in flight
        del log[:]
        rs = [sched.submit(p, GREEDY, max_tokens=40)
              for p in (prompt(20, 5), prompt(27, 9))]
        before = modes()
        sched._step()
        assert log == ["admit_launch", "extend_launch",
                       "admit_launch", "extend_launch",
                       "decode_n_launch",
                       "fetch",                 # the chunk in flight
                       "fetch", "fetch", "fetch", "fetch",
                       "fanout"]
        assert moved(before) == {"launched": 2, "awaited": 0}
        assert not sched._launched and not sched._prefilling
        assert all(len(tokens_of(frames(r))) == 1 for r in rs)
        assert sorted(sched._pending[1]) == [0, 1, 2]      # all ride it
        for r in [r0] + rs:
            r.cancel()
        run_steps(sched)
    finally:
        sched.shutdown()
        clean(eng)


def test_a_steps_pieces_stay_inside_its_budget_oldest_job_first(
        eng, monkeypatch):
    """Two prompts of three pieces (16, 16, 8) at a budget of 32 tokens a
    step: the pass that admits them gives the older one two pieces and the
    younger its first (a new job's first piece always goes); the next step
    ends the older one, then spends what is left on the younger."""
    sched = manual(Scheduler(eng, prefill_chunk=16, async_dispatch=True))
    assert sched._piece_tokens == 16    # 4 slots x 4 steps: one piece
    sched._piece_tokens = 32
    log = spy_on(eng, sched, monkeypatch)
    try:
        a, b = (sched.submit(p, GREEDY, max_tokens=5)
                for p in (prompt(40, 2), prompt(40, 11)))
        sched._step()
        assert [x for x in log if x.endswith("_launch")] == [
            "admit_launch", "extend_launch", "admit_launch"]
        assert {s: j.done for s, j in sched._prefilling.items()} == {
            a.slot: 32, b.slot: 16}
        assert not tokens_of(frames(a)) and not sched._decoding()
        del log[:]
        sched._step()
        assert [x for x in log if x.endswith("_launch")] == [
            "extend_launch", "extend_launch", "extend_launch",
            "decode_n_launch"]
        assert not sched._prefilling
        assert sorted(sched._pending[1]) == sorted([a.slot, b.slot])
        run_steps(sched)
    finally:
        sched.shutdown()
        clean(eng)


def test_a_piece_that_fails_at_collect_errors_its_owner_once(eng,
                                                             monkeypatch):
    """A launched piece whose fetch raises: the job's owner gets one error
    frame, its slot is free and no job is left; the other request, and
    the next one on that slot, are served."""
    sched = manual(Scheduler(eng, prefill_chunk=16, async_dispatch=True))
    launch = eng.admit_launch
    hit = {}

    def poisoned(slot, ids, *a, **kw):
        h = launch(slot, ids, *a, **kw)
        if len(ids) == 16 and not hit:
            hit["slot"] = slot

            def wait():
                raise RuntimeError("device said no")
            h = type("H", (), {"wait": staticmethod(wait), "kind": "admit",
                               "slots": (slot,), "t_queued": h.t_queued,
                               "ready": staticmethod(lambda: False)})()
        return h

    monkeypatch.setattr(eng, "admit_launch", poisoned)
    try:
        bad = sched.submit(prompt(40, 2), GREEDY, max_tokens=4)
        ok = sched.submit(prompt(9, 7), GREEDY, max_tokens=4)
        run_steps(sched)
        assert frames(bad) == [("error", "device said no")]
        assert len(tokens_of(frames(ok))) == 4
        assert not sched._prefilling
        assert sched._running[hit["slot"]] is None
        nxt = sched.submit(prompt(40, 2), GREEDY, max_tokens=4)
        run_steps(sched)
        assert len(tokens_of(frames(nxt))) == 4
    finally:
        sched.shutdown()
        clean(eng)


# ------------------------------------------------------------- the engine

def test_the_awaited_forms_are_launch_then_wait(eng):
    """admit / admit_many / extend keep their contract: the launched form's
    handle yields the tokens the awaited form returns, and the slot is
    live as soon as the launch returns."""
    p1, p2 = prompt(14), prompt(11, base=23)
    t1 = eng.admit(0, p1, GREEDY)
    eng.release(0, park=True)
    t1x = eng.extend(0, np.concatenate([p1, p2]), len(p1) - 1, GREEDY)
    clean(eng)
    many = eng.admit_many([0, 1], [p1, p2], [GREEDY, GREEDY])
    clean(eng)

    h = eng.admit_launch(0, p1, GREEDY)
    assert isinstance(h, AdmitHandle) and h.slots == (0,)
    assert eng.active[0] and eng._host_lengths[0] == len(p1)
    assert h.wait() == [t1] and h.wait() == [t1]
    assert h.t_launch <= h.t_begin <= h.t_done
    eng.release(0, park=True)
    hx = eng.extend_launch(0, np.concatenate([p1, p2]), len(p1) - 1, GREEDY)
    assert hx.kind == "extend" and hx.wait() == [t1x]
    clean(eng)
    hm = eng.admit_many_launch([0, 1], [p1, p2], [GREEDY, GREEDY])
    assert eng.active[0] and eng.active[1]
    assert hm.wait() == many == [t1, many[1]]
    clean(eng)


def test_a_launched_admission_raises_what_the_awaited_one_raises(engines):
    """Pool exhaustion and an armed engine.admit fault surface at the
    launch, before any dispatch: the slot stays free."""
    from ollama_operator_tpu.runtime.faults import FAULTS, InjectedFault
    from ollama_operator_tpu.runtime.paged import PagesExhausted
    cfg, ecfg = KINDS["paged_int8"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    small = Engine(cfg, params, ecfg=dataclasses.replace(ecfg, n_pages=3))
    with pytest.raises(PagesExhausted):
        small.admit_launch(0, prompt(60), GREEDY)
    assert not small.active[0]
    small._pt.check()
    FAULTS.arm("engine.admit", "fail:once")
    try:
        with pytest.raises(InjectedFault):
            small.admit_launch(0, prompt(9), GREEDY)
    finally:
        FAULTS.disarm("engine.admit")
    assert not small.active[0]
    assert small.admit_launch(0, prompt(9), GREEDY).wait()
    small.release(0)
