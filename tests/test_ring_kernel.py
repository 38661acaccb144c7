"""Window attention's one-position step as one kernel
(``ops/pallas/ring.ring_decode``), beside ``tests/test_smallthinker.py``'s:
the kernel in interpret mode on the CPU, at toy widths, against the same
attention written out in numpy and against ``_ring_attend``'s einsum form
through the layer itself: ragged lengths in one batch, slots with nothing
real, rings that have wrapped, int8 and plain rings, layers of a stack other
than the first, the walk's seams between slots, how the layer chooses the
kernel and says so, and that it changes nothing a sequence sees through the
engine."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.ops.attention import record_kernels
from ollama_operator_tpu.ops.pallas import ring as RK
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)

import test_smallthinker as ST

CFG = cfglib.PRESETS["tiny-smallthinker"]
BIG = cfglib.PRESETS["smallthinker-21b-a3b"]
SHORT = cfglib.PRESETS["k-exaone-236b-a23b"]
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
Lw, BLOCK = 2, 8
H, KVH, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
SCALE = 0.25
# a ring of 32: the first position alone, a block's edge and one to either
# side, the ring's last slot, with a slot that holds nothing between
RAGGED = ([0, 7, 8, 9, 15, 31], [1, 1, 0, 1, 1, 1])


def rings_of(cache: str, B: int, W: int, seed: int = 0):
    """K and V rings [Lw, B, KvH, W, hd] that are full of something, as the
    cache keeps them: int8 codes with a scale a head a slot, or plain rows in
    ``cache``'s type; and the same as float64 (K, V) with the scales in."""
    rng = np.random.default_rng([seed, 52])
    leaves, plain = [], []
    for _ in range(2):
        if cache == "int8":
            q = rng.integers(-127, 128, (Lw, B, KVH, W, HD)).astype(np.int8)
            s = rng.uniform(0.004, 0.02, (Lw, B, KVH, W)).astype(np.float32)
            leaves.append({"q": jnp.asarray(q), "s": jnp.asarray(s)})
            plain.append(q.astype(np.float64) * s[..., None])
        else:
            x = jnp.asarray(rng.normal(size=(Lw, B, KVH, W, HD)),
                            getattr(jnp, cache))
            leaves.append(x)
            plain.append(np.asarray(x, np.float64))
    return tuple(leaves), tuple(plain)


def queries(B: int, seed: int = 0):
    return np.random.default_rng([seed, 53]).normal(
        size=(B, H, HD)).astype(np.float32)


def written_out(q, plain, row, lengths, W):
    """The step in float64, a slot at a time over its own visible ring
    slots: the first min(lengths + 1, W) of its ring."""
    k, v = plain
    out = np.zeros(q.shape, np.float64)
    G = H // KVH
    for b, n in enumerate(lengths):
        n = min(n + 1, W)
        for h in range(H):
            s = k[row, b, h // G, :n] @ q[b, h].astype(np.float64) * SCALE
            e = np.exp(s - s.max())
            out[b, h] = e / e.sum() @ v[row, b, h // G, :n]
    return out


def run(rings, row, q, lengths, live, block=BLOCK):
    return np.asarray(jax.jit(lambda k, v, *a: RK.ring_decode(
        k, v, jnp.int32(row), *a, SCALE, block=block, interpret=True))(
        *rings, jnp.asarray(q), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(live, jnp.int32)))


def close(got, want, tol):
    return np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("row", [0, 1])
@pytest.mark.parametrize("cache", ["int8", "bfloat16", "float32"])
def test_the_kernel_is_the_step_written_out(cache, row):
    """Ragged lengths in one batch, a slot that is not live among them,
    either layer of a stack, each type of ring: every live slot's output is
    the attention over its own visible ring slots and no others."""
    lengths, live = RAGGED
    rings, plain = rings_of(cache, len(lengths), 32, seed=row)
    q = queries(len(lengths), seed=row)
    got = run(rings, row, q, lengths, live)
    want = written_out(q, plain, row, lengths, 32)
    m = np.asarray(live, bool)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    assert close(got[m], want[m], 2e-5)
    assert not got[~m].any()


@pytest.mark.parametrize("live", [
    [1, 1, 1, 1, 1, 1], [0, 1, 1, 1, 1, 1], [1, 0, 0, 0, 1, 1],
    [1, 1, 1, 1, 1, 0], [0, 0, 0, 1, 0, 0], [0, 0, 0, 0, 0, 0]],
    ids=["all", "not-the-first", "a-run-of-three", "not-the-last",
         "one-alone", "none"])
def test_a_slot_with_nothing_real_reads_nothing_and_says_zero(live):
    """A slot that is not live walks no block: zeros out, no NaN (0 / 0), and
    the slots around it read their own rings whichever of them it is: the
    walk's next block in flight skips it."""
    lengths = [17, 2, 31, 8, 40, 15]
    rings, plain = rings_of("int8", 6, 32, seed=3)
    q = queries(6, seed=3)
    got = run(rings, 1, q, lengths, live)
    want = written_out(q, plain, 1, lengths, 32)
    m = np.asarray(live, bool)
    assert np.isfinite(got).all() and not got[~m].any()
    assert not m.any() or close(got[m], want[m], 2e-5)


@pytest.mark.parametrize("cache", ["int8", "float32"])
@pytest.mark.parametrize("W", [8, 32])
def test_a_ring_that_has_wrapped_is_read_whole(cache, W):
    """Lengths at the ring's end, one past it, twice and many times around:
    every ring slot holds a position inside the window and all W are read,
    whichever slot the newest one lies in."""
    lengths = [W - 2, W - 1, W, W + 1, 2 * W + 3, 9 * W + W // 2]
    rings, plain = rings_of(cache, len(lengths), W, seed=W)
    q = queries(len(lengths), seed=W)
    got = run(rings, 0, q, lengths, [1] * len(lengths), block=4)
    assert close(got, written_out(q, plain, 0, lengths, W), 2e-5)


@pytest.mark.parametrize("depth", [2, 3, 4])
@pytest.mark.parametrize("block", [4, 8, 16])
def test_the_walk_crosses_slots_at_any_depth(monkeypatch, depth, block):
    """Buffers in flight ahead of the block scored, across slots' ends: two
    to four of them over blocks of four slots to half a ring (a slot of one
    block has its successor's blocks in flight behind it)."""
    monkeypatch.setattr(RK, "_walk_depth", lambda block_bytes: depth)
    lengths, live = [31, 0, 9, 70, 5, 20, 1, 12], [1, 1, 1, 1, 0, 1, 1, 1]
    rings, plain = rings_of("int8", 8, 32, seed=depth)
    q = queries(8, seed=block)
    got = run(rings, 1, q, lengths, live, block=block)
    want = written_out(q, plain, 1, lengths, 32)
    m = np.asarray(live, bool)
    assert close(got[m], want[m], 2e-5) and not got[~m].any()


@pytest.mark.parametrize("B, H_, KvH, hd, W, interpret, ok", [
    (64, 28, 4, 128, 4096, False, True), (8, 28, 4, 128, 1024, False, True),
    (64, 64, 8, 128, 128, False, True), (64, 28, 4, 64, 4096, False, False),
    (64, 28, 5, 128, 4096, False, False), (64, 28, 4, 128, 4000, False, False),
    (64, 28, 4, 128, 96, False, False), (4, 28, 4, 128, 4096, False, False),
    (3, 4, 2, 16, 8, True, True), (3, 4, 3, 16, 8, True, False)],
    ids=["published", "a-shorter-ring", "one-block", "half-a-lane-tile",
         "heads-not-groups", "ring-not-blocks", "under-a-tile",
         "slots-not-a-sublane-tile", "toy-interpreted",
         "toy-heads-not-groups"])
def test_the_shapes_the_kernel_takes(B, H_, KvH, hd, W, interpret, ok):
    assert RK.ring_decode_tileable(B, H_, KvH, hd, W, interpret) is ok
    if ok and not interpret:
        assert RK._block_rows(W, RK._BLOCK_ROWS, False) % 128 == 0


def test_a_shape_that_does_not_tile_returns_none():
    rings, _ = rings_of("int8", 2, 32)
    assert RK.ring_decode(
        *rings, jnp.int32(0), jnp.asarray(queries(2)),
        jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32), SCALE,
        interpret=False) is None
    assert RK.ring_decode_tileable(64, BIG.n_heads, BIG.n_kv_heads,
                                   BIG.head_dim, BIG.sliding_window, False)


# -- through the layer -----------------------------------------------------

def layer(cfg, rings, row, lengths, nv, T=1, depth=32, seed=0):
    """``_ring_attend`` on B slots' new positions: (out, rings), and what
    the layer said it chose."""
    B = len(lengths)
    cfg_w = decoder._kind_cfgs(cfg)[1]
    rng = np.random.default_rng([seed, 54])
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, n, HD)), jnp.float32)
               for n in (H, KVH, KVH))
    fn = jax.jit(lambda q, k, v, win, row, lengths, nv: decoder._ring_attend(
        cfg_w, q, k, v, win, row, lengths, nv, SCALE, depth))
    with record_kernels() as picked:
        out = fn(q, k, v, rings, jnp.int32(row),
                 jnp.asarray(lengths, jnp.int32), jnp.asarray(nv, jnp.int32))
    return out, sorted(picked)


def long_ring(monkeypatch, kernels="interpret", W=32):
    """The toy with a ring of W that counts as long, and small blocks."""
    monkeypatch.setattr(decoder, "_RING_SELECT_MAX", 0)
    monkeypatch.setattr(RK, "_BLOCK_ROWS", BLOCK)
    return (dataclasses.replace(CFG, sliding_window=W),
            dataclasses.replace(CFG, sliding_window=W, kernels=kernels))


@pytest.mark.parametrize("row", [0, 1])
@pytest.mark.parametrize("cache", ["int8", "float32"])
def test_the_layer_through_the_kernel_is_the_einsum_form(monkeypatch, cache,
                                                         row):
    """``_ring_attend`` at T == 1 with the kernel against the same call in
    the einsum form: ragged lengths on both sides of the ring's end, a slot
    that holds nothing; the same outputs for the live slots, and the rings
    come back bit for bit the einsum form's: written at the new position,
    untouched by the read."""
    plain, kernel = long_ring(monkeypatch)
    lengths, nv = [0, 7, 8, 31, 32, 100], [1, 1, 0, 1, 1, 1]
    rings, _ = rings_of(cache, len(lengths), 32, seed=row)
    (want, win0), said0 = layer(plain, rings, row, lengths, nv)
    (got, win1), said1 = layer(kernel, rings, row, lengths, nv)
    assert said0 == [("window", "einsum", False)]
    assert said1 == [("window", "ring_decode", False)]
    m = np.asarray(nv, bool)
    assert np.isfinite(np.asarray(got)).all()
    assert close(np.asarray(got)[m], np.asarray(want)[m], 2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(win0),
                    jax.tree_util.tree_leaves(win1)):
        assert np.array_equal(a, b)
    was = jax.tree_util.tree_leaves(rings)[0]
    now = jax.tree_util.tree_leaves(win1)[0]
    assert np.array_equal(was[1 - row], now[1 - row])
    assert not np.array_equal(was[row], now[row])


def test_several_positions_keep_the_einsum_form(monkeypatch):
    """T > 1 (an extend piece, an admission, the probe's prefill) is the
    einsum form by design: said so, and not as a fallback, with the einsum
    configuration's very outputs."""
    plain, kernel = long_ring(monkeypatch)
    rings, _ = rings_of("int8", 2, 32)
    (got, _), said = layer(kernel, rings, 0, [3, 20], [4, 4], T=4)
    (want, _), _ = layer(plain, rings, 0, [3, 20], [4, 4], T=4)
    assert said == [("window", "einsum", False)]
    assert np.array_equal(got, want)


def test_a_wanted_kernel_that_cannot_tile_is_a_fallback(monkeypatch):
    """``pallas`` at widths Mosaic cannot tile (the toy's 16-channel heads):
    the einsum form serves, flagged, so the harness's ``kernel_fallback``
    check still guards the served path."""
    plain, kernel = long_ring(monkeypatch, kernels="pallas")
    rings, _ = rings_of("int8", 2, 32)
    (got, _), said = layer(kernel, rings, 0, [3, 20], [1, 1])
    (want, _), _ = layer(plain, rings, 0, [3, 20], [1, 1])
    assert said == [("window", "einsum", True)]
    assert np.array_equal(got, want)


@pytest.mark.parametrize("kernels", ["pallas", "interpret"])
def test_a_short_ring_keeps_the_select_and_flags_nothing(kernels):
    """A ring of at most ``_RING_SELECT_MAX`` slots (K-EXAONE's 128) never
    wanted the kernel: nothing is flagged, whatever the kernels' mode, and
    the program lowered is the einsum configuration's, the select over the
    whole ring."""
    assert (SHORT.sliding_window <= decoder._RING_SELECT_MAX
            < BIG.sliding_window)
    cfg_w = decoder._kind_cfgs(CFG)[1]
    rings, _ = rings_of("int8", 2, CFG.sliding_window)
    args = (jnp.zeros((2, 1, H, HD)), jnp.zeros((2, 1, KVH, HD)),
            jnp.zeros((2, 1, KVH, HD)), rings, jnp.int32(0),
            jnp.array([3, 20], jnp.int32), jnp.ones(2, jnp.int32))

    def lowered(mode):
        cfg = dataclasses.replace(cfg_w, kernels=mode)
        with record_kernels() as picked:
            text = jax.jit(lambda *a: decoder._ring_attend(
                cfg, *a, SCALE, 8)).lower(*args).as_text()
        return picked, text
    picked, text = lowered(kernels)
    assert picked == [("window", "einsum", False)]
    assert text == lowered("xla")[1]
    assert "stablehlo.select" in text and not re.search(r"scatter", text)


# -- through the engine ----------------------------------------------------

def test_the_engine_serves_the_references_greedy_stream_through_the_kernel(
        monkeypatch):
    """``tests/test_smallthinker.py``'s toy through admission and six decode
    chunks with the kernel reading the rings, three wraps: the greedy stream
    is the reference's, token by token; the engine says ``ring_decode`` for
    its decode programs' window layers and the einsum form for an extend (an
    admission's prefill attends over no ring)."""
    monkeypatch.setattr(decoder, "_RING_SELECT_MAX", 0)
    monkeypatch.setattr(RK, "_BLOCK_ROWS", 4)
    params = decoder.init_params(CFG, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    cfg = dataclasses.replace(CFG, kernels="interpret")
    eng = Engine(cfg, params, ecfg=EngineConfig(
        max_slots=4, max_seq_len=128, cache_dtype=jnp.float32,
        decode_chunk=4, min_prefill_bucket=16))
    prompt = ST.tokens(21, seed=3)
    got = [eng.admit(1, prompt, GREEDY)]
    eng.admit(3, prompt[:16], GREEDY)
    eng.release(3, park=True)
    eng.extend(3, prompt, 16, GREEDY)
    eng.release(3)
    for _ in range(6):
        got += [int(t) for t in eng.decode_n(4)[:, 1]]
    ref = ST.server_child.load_reference(ST.work.load_conf(ST.CONF_PATH))
    conf = ST.conf_of(CFG)
    fwd = jax.jit(lambda p, t: ref.forward(p, conf, t))
    seq, want = np.zeros((48,), np.int32), []
    seq[:21] = prompt
    for n in range(21, 21 + len(got)):
        want.append(int(jnp.argmax(fwd(params, jnp.asarray(seq))[n - 1])))
        seq[n] = want[-1]
    assert got == want
    said = eng.kernels_by_kind()
    assert "window=ring_decode" in said["decode"]
    assert "window=einsum" not in said["decode"]
    assert "window=einsum" in said["extend"]
    assert not any("ring_decode" in pick for kind in ("admit", "extend")
                   for pick in said[kind])
