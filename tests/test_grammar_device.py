"""Device-table grammar decode (the cause="grammar" retirement): the
GrammarTable BFS closure, engine-level device-vs-host bit parity —
including on-device escapes, the host-length rollback, and re-entry —
and the chunk-budget split between device-table and host-masked slots.

The scheduler-level acceptance (constrained traffic double-buffering
with the fallback counter pinned at 0) lives in test_paged_async.py;
this file pins the mechanism underneath it.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.ops.constrain import (
    INITIAL_STATE, GrammarTable, JsonConstraint, advance_bytes)
from ollama_operator_tpu.runtime.engine import Engine, EngineConfig, SlotOptions
from test_constrain import EOS, PIECES, make_table

CHUNK = 4


@pytest.fixture(scope="module")
def table():
    return make_table()


@pytest.fixture(scope="module")
def gt(table):
    return GrammarTable.for_table(table, cap=64)


@pytest.fixture(scope="module")
def params():
    cfg = cfglib.PRESETS["tiny"]
    return decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def _engine(params):
    cfg = cfglib.PRESETS["tiny"]
    return Engine(cfg, params,
                  ecfg=EngineConfig(max_slots=2, max_seq_len=128,
                                    cache_dtype=jnp.float32,
                                    min_prefill_bucket=16,
                                    decode_chunk=CHUNK))


# --- GrammarTable closure ----------------------------------------------------

def test_grammar_table_masks_match_pda(table, gt):
    """Every tabled row is exactly mask_for of its packed state, and the
    BFS root is the start state."""
    assert gt.states[0] == INITIAL_STATE
    assert 1 < gt.n_states <= 64
    for g, st in enumerate(gt.states):
        np.testing.assert_array_equal(gt.mask[g], table.mask_for(st))


def test_grammar_table_transitions_exact(table, gt):
    """trans[g, t] is the id of advance_bytes(state_g, piece_t) for every
    mask-allowed non-EOG token, and -1 (escape) everywhere else."""
    for g, st in enumerate(gt.states):
        allowed = np.asarray(table.mask_for(st))
        for tid, piece in enumerate(table.pieces):
            bit = (allowed[tid >> 5] >> np.uint32(tid & 31)) & 1
            nid = int(gt.trans[g, tid])
            if not bit or tid in set(table.eog_ids) or not piece:
                assert nid == -1, (g, tid)
                continue
            ns = advance_bytes(st, piece)
            if nid < 0:
                # escape: either the PDA rejected it (impossible for a
                # masked-in token) or the target state is beyond cap
                assert ns is not None and gt.state_id(ns) == -1, (g, tid)
            else:
                assert nid < gt.n_states
                assert gt.states[nid] == ns, (g, tid)


def test_grammar_table_cap_and_cache(table):
    small = GrammarTable.for_table(table, cap=4)
    assert small.n_states <= 4
    assert (small.trans < 4).all()           # never points beyond cap
    assert small is GrammarTable.for_table(table, cap=4)   # cached
    assert small is not GrammarTable.for_table(table, cap=64)
    assert small.state_id(INITIAL_STATE) == 0
    assert small.state_id(None) == -1
    assert small.state_id(b"\xff\xff not a state") == -1


def test_install_grammar_guards(params, gt, monkeypatch):
    eng = _engine(params)
    assert eng.install_grammar(("g", 1), gt.mask, gt.trans)
    assert eng.install_grammar(("g", 1), gt.mask, gt.trans)   # same key
    # a DIFFERENT table swaps freely while no slot is in device mode...
    assert eng.install_grammar(("g", 2), gt.mask, gt.trans)
    # ...but not under a live device-mode slot
    eng._gdev_mode[0] = True
    assert not eng.install_grammar(("g", 3), gt.mask, gt.trans)
    assert eng.install_grammar(("g", 2), gt.mask, gt.trans)   # still live
    eng._gdev_mode[0] = False
    monkeypatch.setattr(eng, "_grammar_device", False)
    assert not eng.install_grammar(("g", 4), gt.mask, gt.trans)


def test_step_budgets_split(params, gt):
    """Host-masked constrained slots step 1 token per dispatch;
    device-table slots keep the full chunk."""
    eng = _engine(params)
    eng._constrained[0] = True                 # host-masked
    eng._constrained[1] = True
    eng._gdev_mode[1] = True                   # device-table
    np.testing.assert_array_equal(eng.step_budgets(CHUNK), [1, CHUNK])


# --- engine device-vs-host bit parity ---------------------------------------

def _host_run(params, table, seed, max_steps=63):
    """Reference: host PDA mask refreshed every token (1-token budget
    comes from step_budgets in the scheduler; here we just re-mask per
    chunk row 0 and step chunk-by-chunk on slot 1)."""
    eng = _engine(params)
    opts = SlotOptions(temperature=0.9, seed=seed, repeat_penalty=1.0)
    c = JsonConstraint(table)
    first = eng.admit(1, np.array([7, 7], np.int32), opts,
                      mask_row=c.mask_row())
    assert c.advance(first)
    eng.set_mask(1, c.mask_row())
    out = [int(first)]
    for _ in range(max_steps):
        t = int(eng.decode()[1])
        out.append(t)
        if t == EOS:
            break
        assert c.advance(t), (t, out)
        eng.set_mask(1, c.mask_row())
    return out


def _device_run(params, table, gt, seed, max_toks=64):
    """Device-table run with the scheduler's host mirror: consume chunk
    rows while the device automaton stayed in-table; on escape, roll the
    over-advance back through rollback_lengths and re-install the exact
    mask (re-entering device mode when the PDA state is tabled again).
    Returns (stream, escapes, re-entries)."""
    eng = _engine(params)
    assert eng.install_grammar(("parity", id(gt)), gt.mask, gt.trans)
    opts = SlotOptions(temperature=0.9, seed=seed, repeat_penalty=1.0)
    c = JsonConstraint(table)
    first = eng.admit(1, np.array([7, 7], np.int32), opts,
                      mask_row=c.mask_row())
    assert c.advance(first)
    gid = gt.state_id(c.state)
    assert gid >= 0
    eng.set_mask(1, c.mask_row(), gid=gid)
    dev_mode = True
    out = [int(first)]
    escapes = reentries = 0
    done = False
    while not done and len(out) < max_toks:
        toks = eng.decode_n(CHUNK)
        if not dev_mode:
            # HOST-masked chunk: step_budgets froze the slot after row 0
            # (rows >= 1 are stale-mask resamples, nothing to roll back)
            t = int(toks[0, 1])
            out.append(t)
            if t == EOS:
                break
            assert c.advance(t), (t, out)
            gid = gt.state_id(c.state)
            dev_mode = gid >= 0
            reentries += dev_mode
            eng.set_mask(1, c.mask_row(), gid=gid)
            continue
        st = gt.state_id(c.state)
        for r in range(CHUNK):
            t = int(toks[r, 1])
            if t == EOS:
                out.append(t)
                done = True
                break
            nid = int(gt.trans[st, t]) if st >= 0 else -1
            assert c.advance(t), (r, t, out)
            out.append(t)
            if nid < 0:
                # device escaped after consuming t: remaining rows are
                # garbage — reconcile lengths, re-mask, maybe re-enter
                escapes += 1
                ns = gt.state_id(c.state)
                eng.rollback_lengths(
                    np.array([0, CHUNK - (r + 1)], np.int64))
                dev_mode = ns >= 0
                eng.set_mask(1, c.mask_row(), gid=ns if ns >= 0 else -1)
                break
            st = nid
        # whatever the chunk did, the host's mirror of the slot's length
        # is the device's once the rollback is in (a stream that ended is
        # released, not rolled back)
        assert done or (int(np.asarray(eng._fetch(eng.lengths))[1])
                        == int(eng._host_lengths[1])), out
    return out, escapes, reentries


def _assert_stream_is_json(got):
    data = b"".join(PIECES[t] for t in got if t != EOS)
    assert advance_bytes(INITIAL_STATE, data) is not None
    if got[-1] == EOS:
        json.loads(data.decode())    # EOS stop ⇒ complete JSON value


@pytest.mark.parametrize("seed", [0, 5, 7])
def test_device_grammar_bit_parity(params, table, gt, seed):
    ref = _host_run(params, table, seed)
    got, _, _ = _device_run(params, table, gt, seed)
    assert got == ref, (seed, got, ref)
    _assert_stream_is_json(got)


# A table of 64 states holds every state these seeds' streams visit, so
# the runs above never leave it. The escape is constructed, not hoped for:
# the table is cut to CUT states and the seed is the first whose HOST
# stream (which no table shapes) leaves the cut table and comes back.
CUT = 16
SEEDS_SEARCHED = 32


def _tabled(table, gt, stream):
    """Per token of ``stream``: whether the automaton's state after it is
    in ``gt``."""
    c = JsonConstraint(table)
    flags = []
    for t in stream:
        if t == EOS:
            break
        assert c.advance(t)
        flags.append(gt.state_id(c.state) >= 0)
    return flags


@pytest.fixture(scope="module")
def escaping(params, table):
    """(cut table, seed, host stream) of the first seed whose host stream
    leaves the cut table and is back inside it later."""
    cut = GrammarTable.for_table(table, cap=CUT)
    for seed in range(SEEDS_SEARCHED):
        ref = _host_run(params, table, seed)
        flags = _tabled(table, cut, ref)
        if False in flags and True in flags[flags.index(False):]:
            return cut, seed, ref
    raise AssertionError(
        f"no seed under {SEEDS_SEARCHED} leaves a {CUT}-state table and "
        "returns: cut the table further")


def test_device_grammar_escapes_rolls_back_and_re_enters(params, table,
                                                         escaping):
    """Where the host stream leaves the table the device run must escape
    (frozen rows discarded, the launch's length advance rolled back) and,
    once the state is tabled again, re-enter device mode: the same stream
    bit for bit, with both paths taken at least once."""
    cut, seed, ref = escaping
    got, escapes, reentries = _device_run(params, table, cut, seed)
    assert got == ref, (seed, got, ref)
    _assert_stream_is_json(got)
    assert escapes >= 1 and reentries >= 1, (seed, escapes, reentries)


def test_escape_freezes_slot_on_device(params, table, escaping):
    """After an in-chunk escape the device automaton reports -2 and the
    slot's device length matches the host's post-rollback view — the
    frozen rows never advanced it."""
    gt, seed, _ = escaping
    eng = _engine(params)
    assert eng.install_grammar(("freeze", id(gt)), gt.mask, gt.trans)
    opts = SlotOptions(temperature=0.9, seed=seed, repeat_penalty=1.0)
    c = JsonConstraint(table)
    first = eng.admit(1, np.array([7, 7], np.int32), opts,
                      mask_row=c.mask_row())
    assert c.advance(first)
    eng.set_mask(1, c.mask_row(), gid=gt.state_id(c.state))
    for _ in range(16):
        toks = eng.decode_n(CHUNK)
        gstate = int(np.asarray(eng._fetch(eng._gstate))[1])
        st = gt.state_id(c.state)
        for r in range(CHUNK):
            t = int(toks[r, 1])
            assert t != EOS, "the constructed stream escapes before it ends"
            nid = int(gt.trans[st, t]) if st >= 0 else -1
            assert c.advance(t)
            if nid < 0:
                assert gstate == -2         # frozen on device
                over = CHUNK - (r + 1)
                eng.rollback_lengths(np.array([0, over], np.int64))
                # frozen rows never advanced the device length: after the
                # rollback the host mirror agrees with the device
                lens = np.asarray(eng._fetch(eng.lengths))
                assert int(lens[1]) == int(eng._host_lengths[1])
                return
            st = nid
    raise AssertionError("the constructed stream never escaped")


# --- rollback_lengths, the one call that takes a host length back ----------

PROMPT = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)


@pytest.fixture(scope="module")
def rb_engine(params):
    return _engine(params)


def _rolled(eng, slot, over):
    rb = np.zeros((eng.n_slots,), np.int64)
    rb[slot] = over
    eng.rollback_lengths(rb)
    return int(eng._host_lengths[slot])


@pytest.mark.parametrize("case", ["an_active_slot", "a_slot_released_since",
                                  "a_slot_parked_since", "more_than_it_has"])
def test_rollback_lengths(rb_engine, case):
    """The host length of an ACTIVE slot goes back by what it is told, and
    no further than zero; a slot released (or parked) since the launch is
    left alone: release reset its length, a parked prefix keeps its own,
    and whoever is admitted next starts from neither."""
    eng = rb_engine
    eng.admit(1, PROMPT, GREEDY)
    eng.decode_n(CHUNK)
    at = len(PROMPT) + CHUNK
    assert int(eng._host_lengths[1]) == at
    try:
        if case == "an_active_slot":
            assert _rolled(eng, 1, CHUNK - 1) == at - (CHUNK - 1)
            assert int(eng._host_lengths[0]) == 0      # no one else moved
        elif case == "a_slot_released_since":
            eng.release(1)
            assert _rolled(eng, 1, CHUNK - 1) == 0
        elif case == "a_slot_parked_since":
            eng.release(1, park=True)
            assert _rolled(eng, 1, CHUNK - 1) == at
        else:
            assert _rolled(eng, 1, at + 100) == 0
    finally:
        eng.release(1)


def test_rollback_lengths_is_mirrored_and_replayed_in_place(params, table,
                                                            escaping):
    """A follower never waits a handle, so it learns of an escape only
    from the call stream: rollback_lengths rides it, between the launch it
    corrects and the next one, and a replay ends with the leader's host
    lengths and the device's."""
    import threading

    from ollama_operator_tpu.runtime.follower import MirroredEngine
    assert "rollback_lengths" in MirroredEngine.MIRRORED
    gt, seed, _ = escaping
    calls = []

    class Tape:
        dispatch_lock = threading.Lock()

        def broadcast(self, msg):
            calls.append(msg)

    inner = _engine(params)
    leader = MirroredEngine(inner, Tape())
    assert leader.install_grammar(("mirror", id(gt)), gt.mask, gt.trans)
    opts = SlotOptions(temperature=0.9, seed=seed, repeat_penalty=1.0)
    c = JsonConstraint(table)
    first = leader.admit(1, np.array([7, 7], np.int32), opts,
                         mask_row=c.mask_row())
    assert c.advance(first)
    leader.set_mask(1, c.mask_row(), gid=gt.state_id(c.state))
    over = None
    for _ in range(16):
        toks = leader.decode_n_launch().wait()
        st = gt.state_id(c.state)
        for r in range(CHUNK):
            t = int(toks[r, 1])
            nid = int(gt.trans[st, t]) if st >= 0 else -1
            assert t != EOS and c.advance(t)
            if nid < 0:
                over = CHUNK - (r + 1)
                break
            st = nid
        if over is not None:
            break
    assert over, "the constructed stream escapes inside a chunk"
    leader.rollback_lengths(np.array([0, over], np.int64))
    leader.set_mask(1, c.mask_row(), gid=gt.state_id(c.state))
    leader.decode_n_launch().wait()

    names = [m[1] for m in calls]
    at = names.index("rollback_lengths")
    assert names[at - 1] == "decode_n_launch"
    assert names[at + 1:] == ["set_mask", "decode_n_launch"]
    follower = _engine(params)
    for _, name, a, kw in calls:
        getattr(follower, name)(*a, **kw)
    np.testing.assert_array_equal(follower._host_lengths,
                                  inner._host_lengths)
    np.testing.assert_array_equal(
        np.asarray(follower._fetch(follower.lengths)),
        np.asarray(inner._fetch(inner.lengths)))
    assert (int(np.asarray(follower._fetch(follower.lengths))[1])
            == int(follower._host_lengths[1]))
