"""Paged async dispatch: epoch-fenced page reclamation (ISSUE 5).

Coverage: PageTable quarantine lifecycle (stamp/retire/drain, sync
pass-through, check() invariants), engine-level fencing of release /
donation / radix eviction while a dispatch is in flight, the scheduler
double-buffering in paged mode with bit-identical async-vs-sync streams
(greedy AND seeded, with and without a radix hit), quarantine
convergence under pool pressure (preemption in flight), the async
fallback observability counter, and the engine.step chaos drill
(fail:after=1 in paged+async: owners errored exactly once, restart
drains the quarantine, accounting stays clean).
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
from ollama_operator_tpu.runtime.engine import Engine, EngineConfig, SlotOptions
from ollama_operator_tpu.runtime.faults import FAULTS
from ollama_operator_tpu.runtime.paged import PageTable
from ollama_operator_tpu.runtime.scheduler import Scheduler
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

BASE = PRESETS["tiny"]
XLA = dataclasses.replace(BASE, kernels="xla")
GREEDY = SlotOptions(temperature=0.0)
SEEDED = SlotOptions(temperature=0.9, top_k=40)
DENSE = EngineConfig(max_slots=4, max_seq_len=64, cache_dtype=jnp.float32,
                     min_prefill_bucket=16)
PAGED = dataclasses.replace(DENSE, paged=True, page_size=8)

PREFIX = np.arange(1, 25, dtype=np.int32)          # 24 tokens = 3 pages
PROMPT = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(BASE, jax.random.key(0), jnp.float32)


def _drain(sched, deadline_s=5.0):
    t1 = time.monotonic() + deadline_s
    while ((sched.n_active or sched.engine.quarantined_pages)
           and time.monotonic() < t1):
        time.sleep(0.01)
    assert sched.n_active == 0
    assert sched.engine.quarantined_pages == 0


# ---------------------------------------------------------------------------
# quarantine lifecycle on the bare page table (no engine)
# ---------------------------------------------------------------------------

def test_sync_reclaim_is_passthrough():
    """With no dispatch in flight (epoch == retired) frees keep today's
    exact semantics: straight to the free list, quarantine untouched."""
    pt = PageTable(n_slots=2, n_pages=6, page_size=8, max_blocks=8)
    assert pt.grow(0, 16)
    pt.release(0)
    assert pt.quarantined == 0 and pt.n_free == 5
    # retiring up to date keeps the fence open
    e = pt.advance_epoch()
    pt.retire_epoch(e)
    assert pt.grow(0, 8)
    pt.release(0)
    assert pt.quarantined == 0 and pt.n_free == 5
    pt.check()


def test_quarantine_stamps_and_partial_retire():
    """Frees during epoch N are stamped N and become allocatable only
    when N retires — retiring e1 must not release e2's pages."""
    pt = PageTable(n_slots=2, n_pages=6, page_size=8, max_blocks=8)
    assert pt.grow(0, 8) and pt.grow(1, 8)
    e1 = pt.advance_epoch()
    pt.release(0)                              # stamped e1
    e2 = pt.advance_epoch()
    pt.release(1)                              # stamped e2
    assert pt.quarantined == 2 and pt.n_free == 3
    pt.check()
    pt.retire_epoch(e1)
    assert pt.quarantined == 1 and pt.n_free == 4
    pt.retire_epoch(e2)
    assert pt.quarantined == 0 and pt.n_free == 5
    # retire clamps to the launched epoch and is idempotent
    pt.retire_epoch(999)
    pt.check()


def test_drain_quarantine_reclaims_everything():
    pt = PageTable(n_slots=2, n_pages=6, page_size=8, max_blocks=8)
    assert pt.grow(0, 16)
    pt.advance_epoch()
    pt.release(0)
    assert pt.quarantined == 2
    assert pt.drain_quarantine() == 2
    assert pt.quarantined == 0 and pt.n_free == 5
    pt.check()


@pytest.mark.parametrize("unmapped", ["at_a_retired_epoch", "in_flight"])
def test_unpin_routes_through_the_fence(unmapped):
    """Radix eviction frees via unpin, and the fence holds a page by when
    its last SLOT mapping went, not by when the tree lets go. A tree-only
    page whose slot went before the dispatch in flight was launched is in
    no block table that dispatch captured: free at once. One whose slot
    went while the dispatch was in flight must quarantine until that
    dispatch retires."""
    pt = PageTable(n_slots=2, n_pages=6, page_size=8, max_blocks=8)
    assert pt.grow(0, 8)
    pg = pt.slot_pages(0)[0]
    pt.pin(pg)                                 # the tree adopts it
    if unmapped == "in_flight":
        e = pt.advance_epoch()                 # a dispatch is in flight
        pt.release(0)                          # ... and holds the row
    else:
        pt.release(0)
        e = pt.advance_epoch()                 # launched without the page
    assert pt.n_free == 4                      # pinned: stays resident
    assert pt.fenced(pg) is (unmapped == "in_flight")
    at_once = pt.unpin(pg)                     # LRU eviction
    if unmapped == "in_flight":
        assert not at_once
        assert pt.quarantined == 1 and pt.n_free == 4
        pt.check()
        pt.retire_epoch(e)
    else:
        assert at_once
    assert pt.quarantined == 0 and pt.n_free == 5
    pt.check()
    pt.drain_quarantine()
    pt.check()


def test_a_stitched_page_carries_its_last_unmap():
    """A cached page stitched into a second slot and released again while
    a dispatch is in flight was in that dispatch's block table: the later
    unmap is the stamp, whatever the first one was."""
    pt = PageTable(n_slots=2, n_pages=6, page_size=8, max_blocks=8)
    assert pt.grow(0, 8)
    pg = pt.slot_pages(0)[0]
    pt.pin(pg)
    pt.release(0)                              # unmapped at epoch 0
    assert not pt.fenced(pg)
    pt.map_shared(1, [pg])                     # a radix hit stitches it
    e1 = pt.advance_epoch()                    # launched with slot 1's row
    assert not pt.fenced(pg)                   # mapped: the stamp is old
    pt.release(1)                              # unmapped in flight
    assert pt.fenced(pg)
    e2 = pt.advance_epoch()
    assert not pt.unpin(pg)                    # evicted: must quarantine
    assert pt.quarantined == 1
    pt.check()
    pt.retire_epoch(e1)                        # the FIFO's stamp is e2's
    assert pt.quarantined == 1
    pt.retire_epoch(e2)
    assert pt.quarantined == 0 and pt.n_free == 5
    pt.check()


def test_a_page_the_tree_allocated_counts_as_mapped_then():
    """alloc_pinned: no slot ever maps the page, so it is held as if its
    last unmap were the allocation."""
    pt = PageTable(n_slots=1, n_pages=4, page_size=8, max_blocks=4)
    e = pt.advance_epoch()
    pg = pt.alloc_pinned()
    assert pt.fenced(pg)
    assert not pt.unpin(pg)
    assert pt.quarantined == 1
    pt.retire_epoch(e)
    assert pt.quarantined == 0 and pt.n_free == 3
    pg = pt.alloc_pinned()                     # nothing in flight now
    assert not pt.fenced(pg) and pt.unpin(pg)
    pt.check()


def test_check_refuses_a_free_page_unmapped_after_the_retired_epoch():
    pt = PageTable(n_slots=1, n_pages=4, page_size=8, max_blocks=4)
    assert pt.grow(0, 8)
    pg = pt.slot_pages(0)[0]
    e = pt.advance_epoch()
    pt.release(0)
    assert pt.quarantined == 1
    pt._quarantine.clear()                     # corrupt: skip the fence
    pt._free.append(pg)
    with pytest.raises(AssertionError, match="unmapped at epoch"):
        pt.check()
    pt.retire_epoch(e)                         # now the rule allows it
    pt.check()


def test_sharded_table_delegates_the_stamp():
    """Each dp shard's table stamps its own pages; epochs are global."""
    from ollama_operator_tpu.runtime.paged import ShardedPageTable
    spt = ShardedPageTable(n_slots=4, dp=2, pages_per_shard=3,
                           page_size=8, max_blocks=4)
    assert spt.grow(0, 8) and spt.grow(2, 8)   # one slot a shard
    spt.release(0)                             # nothing in flight: free
    assert spt.quarantined == 0 and spt.n_free == 5
    e = spt.advance_epoch()
    spt.release(2)                             # in flight: the other shard
    assert spt.quarantined == 1
    assert [pt.quarantined for pt in spt._pts] == [0, 1]
    spt.check()
    spt.retire_epoch(e)
    assert spt.quarantined == 0 and spt.n_free == 6
    spt.check()


def test_check_catches_free_and_quarantined():
    pt = PageTable(n_slots=1, n_pages=4, page_size=8, max_blocks=4)
    assert pt.grow(0, 8)
    pg = pt.slot_pages(0)[0]
    pt.advance_epoch()
    pt.release(0)
    assert pt.quarantined == 1
    pt._free.append(pg)                        # corrupt: free AND fenced
    with pytest.raises(AssertionError):
        pt.check()
    pt._free.pop()                             # restore sanity
    pt.drain_quarantine()
    pt.check()


def test_check_catches_referenced_while_quarantined():
    pt = PageTable(n_slots=1, n_pages=4, page_size=8, max_blocks=4)
    assert pt.grow(0, 8)
    pg = pt.slot_pages(0)[0]
    pt.advance_epoch()
    pt.release(0)
    pt._rc[pg] = 1                             # corrupt: live ref in fence
    with pytest.raises(AssertionError):
        pt.check()
    pt._rc[pg] = 0                             # restore sanity
    pt.drain_quarantine()
    pt.check()


# ---------------------------------------------------------------------------
# engine: frees while a dispatch is genuinely in flight
# ---------------------------------------------------------------------------

def test_release_in_flight_quarantines_then_retires(params):
    """A slot released while a launched chunk is still un-materialised
    must fence its pages; the next launch's retire= ack (the epoch the
    caller already waited on) unfences them."""
    eng = Engine(XLA, params, ecfg=PAGED)
    eng.admit(0, PROMPT, GREEDY)
    eng.admit(1, PROMPT + 1, GREEDY)
    h1 = eng.decode_n_launch()
    assert h1.epoch == 1
    eng.release(1)                             # in flight: must fence
    assert eng.quarantined_pages >= 1
    eng._pt.check()
    h1.wait()
    h2 = eng.decode_n_launch(retire=h1.epoch)  # ack unfences stamp<=1
    assert eng.quarantined_pages == 0
    h2.wait()
    assert eng.fence_quiesce() == 0            # nothing left to drain
    eng.release(0)                             # sync again: direct free
    assert eng.quarantined_pages == 0
    assert eng.free_pages == eng._pt.data_pages
    eng._pt.check()


def test_donate_and_evict_in_flight_route_through_fence(params):
    """Radix donation (duplicate/tail frees) and LRU eviction (unpins)
    while a chunk is in flight must quarantine; fence_quiesce reclaims
    the whole pool once the program materialises."""
    eng = Engine(XLA, params, ecfg=PAGED)
    assert eng.radix_enabled
    donor = np.arange(1, 29, dtype=np.int32)   # 28 tokens
    first = eng.admit(0, donor, GREEDY)
    rows = eng.decode_n(4)                     # sync: epoch==retired
    gen = [first] + [int(r[0]) for r in rows]
    handle = eng.decode_n_launch()             # NOW a program is in flight
    eng.donate_prefix(0, list(donor) + gen[:-1])   # 32 tokens = 4 pages
    assert eng.radix_nodes == 4
    assert eng.quarantined_pages >= 1          # the slot's tail pages
    eng._pt.check()
    n_evicted = eng.radix_evict(10)            # unpin all 4 tree pages
    assert n_evicted == 4
    assert eng.radix_nodes == 0
    assert eng.quarantined_pages >= 5
    eng._pt.check()
    handle.wait()
    assert eng.fence_quiesce() >= 5
    assert eng.quarantined_pages == 0
    assert eng.free_pages == eng._pt.data_pages
    eng._pt.check()


# ---------------------------------------------------------------------------
# scheduler: double-buffered paged decode, stream parity
# ---------------------------------------------------------------------------

def test_paged_scheduler_double_buffers_by_default(params):
    """The `and not engine.paged` gate is gone: a paged scheduler with
    TPU_ASYNC_DISPATCH unset/on runs double-buffered."""
    eng = Engine(XLA, params, ecfg=PAGED)
    sched = Scheduler(eng, async_dispatch=True)
    try:
        assert sched.async_dispatch
        out = list(sched.submit(PROMPT, max_tokens=6, opts=GREEDY).tokens())
        assert len(out) == 6
        _drain(sched)
    finally:
        sched.shutdown()


def _arm(params, async_on, warm):
    """One scheduler arm: optional warm donor (radix hit for the probes),
    then greedy + seeded probes sharing PREFIX. Returns all streams."""
    eng = Engine(XLA, params, ecfg=PAGED)
    sched = Scheduler(eng, async_dispatch=async_on)
    try:
        assert sched.async_dispatch is async_on
        outs = []
        if warm:
            donor = np.concatenate([PREFIX, np.array([60, 61], np.int32)])
            outs.append(list(sched.submit(donor, max_tokens=4,
                                          opts=GREEDY).tokens()))
        probes = [
            (np.concatenate([PREFIX, np.array([70], np.int32)]), GREEDY),
            (np.concatenate([PREFIX, np.array([70], np.int32)]), SEEDED),
            (PROMPT, GREEDY),
        ]
        reqs = [sched.submit(p, max_tokens=8, opts=o) for p, o in probes]
        outs += [list(r.tokens()) for r in reqs]
        for r in reqs:
            assert r.error is None
        if warm:
            assert any(r.stats.n_reused >= 16 for r in reqs)
        _drain(sched)
        return outs
    finally:
        sched.shutdown()


@pytest.mark.parametrize("warm", [False, True],
                         ids=["cold", "radix-hit"])
def test_paged_async_streams_match_sync(params, warm):
    """The acceptance bar: paged async streams are bit-identical to the
    sync path — greedy and seeded, cold and with a radix stitch."""
    assert _arm(params, True, warm) == _arm(params, False, warm)


def test_preempt_under_pressure_in_flight_converges(params):
    """Pool pressure with async double-buffering: preemption and
    eviction route through the fence (drain-then-unfence before any
    sacrifice), every stream still gets its full budget, and the
    quarantine is empty once the dust settles."""
    eng = Engine(XLA, params, ecfg=dataclasses.replace(
        PAGED, max_slots=3, n_pages=6))
    sched = Scheduler(eng, async_dispatch=True)
    try:
        assert sched.async_dispatch
        reqs = [sched.submit(PROMPT + i, max_tokens=12, opts=GREEDY)
                for i in range(3)]
        outs = [list(r.tokens()) for r in reqs]
        for r, out in zip(reqs, outs):
            assert r.error is None
            assert len(out) == 12, (len(out), r.error)
        _drain(sched)
        assert eng.free_pages == eng._pt.data_pages - eng.radix_pages
        eng._pt.check()
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# a pool the radix tree keeps full: every pass must evict
# ---------------------------------------------------------------------------

CHURN = dataclasses.replace(PAGED, decode_chunk=4)
N_CLIENTS, N_ROUNDS = 2, 7


def _churn_prompt(i):
    return ((np.arange(9 + 2 * (i % 5)) + 7 * i) % 50 + 3).astype(np.int32)


def _evict_counts():
    out = {f: METRICS.get("tpu_model_radix_evicted_pages_total",
                          f'{{fence="{f}"}}') for f in ("free", "fenced")}
    out["stalls"] = sum(
        METRICS.get("tpu_model_page_stalls_total", f'{{cause="{c}"}}')
        for c in ("pool_dry_admit", "pool_dry_stitch", "pool_dry_decode"))
    return out


def _churn(params, n_pages, async_on, calls=None):
    """Two clients, each sending its next request when its last one is
    done, through a scheduler stepped by hand: fourteen distinct greedy
    requests of 21-29 positions (each donates two or three pages when it
    ends). Returns (streams by request, what the counters moved by, the
    most radix pages seen, the free list at rest). ``calls`` wraps the
    engine in a mirror that records the call stream."""
    from test_admit_launch import frames, manual, tokens_of
    eng = Engine(XLA, params, ecfg=dataclasses.replace(CHURN,
                                                       n_pages=n_pages))
    served = eng
    if calls is not None:
        from ollama_operator_tpu.runtime.follower import MirroredEngine

        class Tape:
            dispatch_lock = threading.Lock()

            def broadcast(self, msg):
                calls.append(msg)
        served = MirroredEngine(eng, Tape())
    sched = manual(Scheduler(served, prefill_chunk=0,
                             async_dispatch=async_on))
    before = _evict_counts()
    streams, live, sent, most = {}, {}, 0, 0
    try:
        for _ in range(600):
            for c in range(N_CLIENTS):
                req = live.get(c)
                if req is not None and not req.stats.t_done:
                    continue
                if req is not None:
                    assert req.error is None
                    live[c] = None
                if sent < N_CLIENTS * N_ROUNDS:
                    live[c] = sched.submit(_churn_prompt(sent), GREEDY,
                                           max_tokens=12)
                    streams[sent] = live[c]
                    sent += 1
            if sent == N_CLIENTS * N_ROUNDS and not any(live.values()) \
                    and sched._pending is None:
                break
            sched._step()
            most = max(most, eng.radix_pages)
            eng._pt.check()
        else:
            raise AssertionError("the clients did not finish")
        out = {i: tokens_of(frames(r)) for i, r in streams.items()}
        assert all(len(t) == 12 for t in out.values())
        if eng.quarantined_pages:
            sched._step()                      # the idle step unfences
        now = _evict_counts()
        return (out, {k: now[k] - before[k] for k in now}, most,
                list(eng._pt._free))
    finally:
        sched.shutdown()


def test_a_full_pool_evicts_at_once_and_streams_stay(params):
    """(a) the streams of a pool that must evict in every pass are those
    of a pool that never evicts and of the synchronous loop; (b) with a
    chunk in flight the pass takes its pages from leaves whose slots went
    at a retired epoch: no stall for pages, every eviction free at once,
    none into the quarantine."""
    tight, moved, most, _ = _churn(params, 14, True)
    roomy, moved_roomy, most_roomy, _ = _churn(params, 200, True)
    sync, moved_sync, _, _ = _churn(params, 14, False)
    assert tight == roomy == sync
    assert moved_roomy == {"free": 0, "fenced": 0, "stalls": 0}
    assert most_roomy > 14                     # more than the tight pool is
    assert moved["stalls"] == 0 and moved["fenced"] == 0
    assert moved["free"] >= 10                 # a page or more a request
    assert moved_sync["stalls"] == 0 and moved_sync["fenced"] == 0
    assert moved_sync["free"] >= 10


def test_leaves_donated_in_flight_fall_back_to_the_stall(params):
    """(c) the tree's only leaves went with the chunk in flight still
    holding their rows: nothing is free at once, so the pass stalls as it
    always did (lands the chunk, unfences), evicts then, and admits in
    the same step. Nothing is evicted into the quarantine on the way."""
    from test_admit_launch import frames, manual, run_steps, tokens_of
    eng = Engine(XLA, params, ecfg=dataclasses.replace(
        CHURN, max_slots=2, n_pages=7))         # seven pages of eight
    sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
    try:
        ra = sched.submit(_churn_prompt(0), GREEDY, max_tokens=5)   # 9+5
        rb = sched.submit(_churn_prompt(3), GREEDY, max_tokens=9)   # 15+9
        sched._step()                          # both admitted, chunk 1
        sched._step()                          # chunk 2 launched, 1 landed:
        assert ra.stats.t_done                 # a ended, donated in flight
        assert sched._pending is not None and eng.radix_pages == 1
        assert all(eng._pt.fenced(pg) for pg in range(1, 8)
                   if eng._pt._pins[pg])
        # b maps three pages, the tree pins one, a's other two wait in the
        # quarantine: one page is free, and a prompt of four pages (five
        # with a chunk's headroom) finds the only leaf fenced
        before = _evict_counts()
        rc = sched.submit(np.arange(60, 90, dtype=np.int32), GREEDY,
                          max_tokens=4)
        assert eng.free_pages == 1 and eng.quarantined_pages == 2
        sched._step()
        moved = {k: v - before[k] for k, v in _evict_counts().items()}
        assert moved["stalls"] == 1 and moved["fenced"] == 0
        assert moved["free"] == 1              # after the unfence, at once
        assert rb.stats.t_done                 # the stall landed b's last
        assert rc.slot is not None and sched._running[rc.slot] is rc
        run_steps(sched)
        assert len(tokens_of(frames(rc))) == 4
        assert len(tokens_of(frames(rb))) == 9
        eng._pt.check()
    finally:
        sched.shutdown()


def test_fence_retire_is_mirrored_and_a_replay_keeps_the_free_list(params):
    """(d) the retire at the pass is a mirrored call; a follower that
    replays the leader's call stream (and never waits a handle) ends with
    the leader's free list, eviction for eviction."""
    from ollama_operator_tpu.runtime.follower import MirroredEngine
    assert "fence_retire" in MirroredEngine.MIRRORED
    calls = []
    _, moved, _, free = _churn(params, 14, True, calls=calls)
    assert moved["free"] >= 10
    names = [c[1] for c in calls]
    assert "fence_retire" in names and "decode_n_launch" in names
    assert "radix_evict" not in names          # evicted inside the calls
    follower = Engine(XLA, params, ecfg=dataclasses.replace(CHURN,
                                                            n_pages=14))
    for _, name, a, kw in calls:
        getattr(follower, name)(*a, **kw)
    follower._pt.check()
    assert list(follower._pt._free) == free
    assert follower.radix_pages > 0


# ---------------------------------------------------------------------------
# fallback observability
# ---------------------------------------------------------------------------

def test_async_fallback_counter_preseeded():
    """Every cause label exists at 0 before any fallback fires: alert
    rules rate() over these and a series that first appears AT the first
    fallback hides it."""
    text = METRICS.render()
    for cause in ("grammar", "paged_dp"):
        assert f'tpu_model_async_fallback_total{{cause="{cause}"}}' in text


def test_paged_dp_double_buffers(params):
    """cause="paged_dp" retired: a dp-sharded paged pool keeps async
    dispatch (epochs are global, quarantines per-shard — the fence never
    crosses the shard boundary) and the counter stays at its pre-seeded
    zero. Streams match the sync arm bit-for-bit."""
    from ollama_operator_tpu.parallel.mesh import MeshPlan, make_mesh

    def arm(async_on):
        mesh = make_mesh(MeshPlan(dp=2), jax.devices()[:2])
        eng = Engine(XLA, params, mesh=mesh,
                     ecfg=dataclasses.replace(PAGED, n_pages=8))
        sched = Scheduler(eng, async_dispatch=async_on)
        try:
            assert sched.async_dispatch is async_on
            out = list(sched.submit(PROMPT, max_tokens=6,
                                    opts=GREEDY).tokens())
            _drain(sched)
            return out
        finally:
            sched.shutdown()

    before = METRICS.get("tpu_model_async_fallback_total",
                         '{cause="paged_dp"}')
    assert arm(True) == arm(False)
    assert METRICS.get("tpu_model_async_fallback_total",
                       '{cause="paged_dp"}') == before


def test_grammar_device_dispatch_stays_async(params):
    """cause="grammar" retired for device-table grammars: a constrained
    slot rides the double-buffered chunked dispatch (mask + automaton
    advance on device) and the fallback counter never moves."""
    from ollama_operator_tpu.ops.constrain import (
        INITIAL_STATE, JsonConstraint, advance_bytes)
    from test_constrain import EOS, PIECES, make_table
    table = make_table()
    eng = Engine(XLA, params, ecfg=dataclasses.replace(
        PAGED, max_seq_len=128))
    sched = Scheduler(eng, async_dispatch=True)
    try:
        assert sched.async_dispatch
        before = METRICS.get("tpu_model_async_fallback_total",
                             '{cause="grammar"}')
        req = sched.submit([5, 9, 2],
                           SlotOptions(temperature=0.9, seed=1,
                                       repeat_penalty=1.0),
                           max_tokens=24, eog_ids=frozenset([EOS]),
                           constraint=JsonConstraint(table))
        toks = list(req.tokens())
        assert len(toks) >= 1
        data = b"".join(PIECES[t] for t in toks)
        assert advance_bytes(INITIAL_STATE, data) is not None
        assert METRICS.get("tpu_model_async_fallback_total",
                           '{cause="grammar"}') == before
        _drain(sched)
    finally:
        sched.shutdown()


def test_grammar_host_fallback_still_counts(params, monkeypatch):
    """TPU_GRAMMAR_DEVICE=0 reverts constrained slots to the host-masked
    sync path — and the retired counter proves it is the knob, not a
    silent regression, by moving again."""
    from ollama_operator_tpu.ops.constrain import JsonConstraint
    from test_constrain import EOS, make_table
    eng = Engine(XLA, params, ecfg=dataclasses.replace(
        PAGED, max_seq_len=128))
    monkeypatch.setattr(eng, "_grammar_device", False)
    sched = Scheduler(eng, async_dispatch=True)
    try:
        assert sched.async_dispatch
        before = METRICS.get("tpu_model_async_fallback_total",
                             '{cause="grammar"}')
        req = sched.submit([5, 9, 2],
                           SlotOptions(temperature=0.9, seed=1,
                                       repeat_penalty=1.0),
                           max_tokens=12, eog_ids=frozenset([EOS]),
                           constraint=JsonConstraint(make_table()))
        assert len(list(req.tokens())) >= 1
        assert METRICS.get("tpu_model_async_fallback_total",
                           '{cause="grammar"}') > before
        _drain(sched)
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# chaos: exactly-once errors + clean accounting through restart
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_engine_step_fault_paged_async_exactly_once(params, monkeypatch):
    """CI chaos drill (ISSUE 5): engine.step fail:after=1 in paged+async.
    The first launch succeeds and its in-flight tokens are delivered; the
    second raises with a dispatch pending. Every owner gets exactly ONE
    terminal error, the supervised restart drains the quarantine, and the
    page table checks clean — then serving resumes."""
    # replay off: this drill pins the exactly-once ERROR contract (the
    # zero-error replay drill lives in test_lifecycle.py)
    monkeypatch.setenv("TPU_RESTART_REPLAY_MAX", "0")
    eng = Engine(XLA, params, ecfg=PAGED)
    sched = Scheduler(eng, restart_backoff=0.001, async_dispatch=True)
    try:
        assert sched.async_dispatch
        FAULTS.arm("engine.step", "fail:after=1")
        reqs = [sched.submit(PROMPT + i, max_tokens=48, opts=GREEDY)
                for i in range(2)]
        errs = 0
        for r in reqs:
            try:
                assert len(list(r.tokens())) <= 48
            except RuntimeError as e:
                assert "engine.step" in str(e)
                errs += 1
            # exactly once: nothing queued after the terminal item
            with pytest.raises(queue_mod.Empty):
                r.out.get_nowait()
        assert errs == 2                       # both owners errored
        FAULTS.disarm("engine.step")
        t1 = time.monotonic() + 5
        while sched.n_restarts < 1 and time.monotonic() < t1:
            time.sleep(0.01)
        assert sched.n_restarts >= 1 and not sched.broken
        # the restart drained everything: whole pool reclaimable
        assert eng.quarantined_pages == 0
        assert eng.free_pages == eng._pt.data_pages
        eng._pt.check()
        r2 = sched.submit(PROMPT, max_tokens=6, opts=GREEDY)
        assert len(list(r2.tokens())) == 6
        _drain(sched)
    finally:
        sched.shutdown()
