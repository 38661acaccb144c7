"""The gated delta rule's one-position step as one kernel
(``ops/pallas/delta.delta_update``), beside ``tests/test_olmo_hybrid.py``'s:
the kernel in interpret mode on the CPU against the recurrence as written
(``decoder._delta_rule``'s T == 1 branch, which stays the compiler's form for
a shape the kernel cannot tile) and against the blocked form over hundreds
of positions, what it must leave alone (other rows of the carried leaf,
slots with nothing real), how the mixer chooses it and says so, and that it
changes nothing a sequence sees through the engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.ops.attention import record_kernels
from ollama_operator_tpu.ops.pallas import delta as D
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)

from test_olmo_hybrid import CONF_PATH, conf_of, state_of, tokens

CFG = cfglib.PRESETS["tiny-olmo-hybrid"]
KERNEL_CFG = dataclasses.replace(CFG, kernels="interpret")
PRESET = cfglib.PRESETS["olmo-hybrid-7b"]
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
# (rows of the leaf, slots, heads, key width, value width), the row updated
TOY = (3, 3, CFG.delta_heads, CFG.delta_key_dim, CFG.delta_value_dim)
PUBLISHED = (2, 2, PRESET.delta_heads, PRESET.delta_key_dim,
             PRESET.delta_value_dim)


def inputs(shape, seed=0, T=1):
    """A leaf that is not empty and T positions' q, k, v, g, beta as
    ``_delta_mixer`` hands them to the rule: unit keys, decays in (0, 1),
    beta in (0, 2)."""
    Ld, B, H, dk, dv = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, H, dk)) * dk ** -0.5
    k = jax.random.normal(ks[1], (B, T, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    ssm = jax.random.normal(ks[5], shape) * 0.3
    return ssm, q, k, v, g, beta


def kernel_step(ssm, row, q, k, v, g, beta, live, **kw):
    """One position through the kernel, from [B, 1, ...] inputs."""
    return jax.jit(lambda *a: D.delta_update(*a, interpret=True, **kw))(
        ssm, jnp.int32(row), q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
        beta[:, 0], jnp.asarray(live, jnp.int32))


@pytest.mark.parametrize("shape, row, hb", [
    (TOY, 0, 0), (TOY, 2, 0), (TOY, 1, 2), (PUBLISHED, 0, 0),
    (PUBLISHED, 1, 10)], ids=["toy", "toy-last-row", "toy-two-heads-a-block",
                              "published", "published-ten-heads-a-block"])
def test_the_kernel_is_the_recurrence_as_written(shape, row, hb):
    """Read-outs and new state of one position against ``_delta_rule``'s
    T == 1 branch from the same row, to float32's rounding: the toy's heads
    (4 x 8 x 16), the published head (30 x 96 x 192, two slots), rows of the
    leaf other than the first, blocks of some heads and of all."""
    ssm, *seqs = inputs(shape, seed=row + 1)
    live = np.ones(shape[1], np.int32)
    want_o, want_S = decoder._delta_rule(CFG, ssm[row], *seqs)
    o, out = kernel_step(ssm, row, *seqs, live, hb=hb)
    assert o.shape == want_o[:, 0].shape
    assert np.allclose(o, want_o[:, 0], atol=2e-5, rtol=1e-5)
    assert np.allclose(out[row], want_S, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("row", [0, 1, 2])
def test_the_leaf_is_updated_at_its_row_alone(row):
    ssm, *seqs = inputs(TOY, seed=7)
    _, out = kernel_step(ssm, row, *seqs, np.ones(TOY[1], np.int32))
    others = [r for r in range(TOY[0]) if r != row]
    assert np.array_equal(out[jnp.array(others)], ssm[jnp.array(others)])
    assert not np.array_equal(out[row], ssm[row])


@pytest.mark.parametrize("live", [(1, 0, 1), (0, 1, 0), (0, 0, 0)])
def test_a_slot_with_nothing_real_keeps_its_bits(live):
    """``n_valid == 0``: the slot's matrices keep their very bits (a -0.0
    among them too, which ``1 * S0 + k * 0`` would turn into +0.0) and its
    read-out is zeros, while its neighbours move as if it were not there."""
    ssm, *seqs = inputs(TOY, seed=11)
    ssm = ssm.at[1, :, 0, 0, 0].set(-0.0)
    want_o, want_S = decoder._delta_rule(CFG, ssm[1], *seqs)
    o, out = kernel_step(ssm, 1, *seqs, live)
    for b, alive in enumerate(live):
        if alive:
            assert np.allclose(out[1, b], want_S[b], atol=2e-5)
            assert np.allclose(o[b], want_o[b, 0], atol=2e-5)
        else:
            assert np.array_equal(np.asarray(out[1, b]).view(np.uint32),
                                  np.asarray(ssm[1, b]).view(np.uint32))
            assert not np.asarray(o[b]).any()


@pytest.mark.parametrize("steps, chunk", [(300, 64), (97, 8)])
def test_hundreds_of_steps_from_one_state_are_the_blocked_form(steps, chunk):
    """The kernel fed its own state position after position (what a decode
    chunk after decode chunk does to a slot) against the blocked form over
    the same positions at once: no drift beyond float32's rounding."""
    shape = (2, 2, 4, 8, 16)
    cfg = dataclasses.replace(CFG, delta_chunk=chunk)
    ssm, q, k, v, g, beta = inputs(shape, seed=steps, T=steps)
    with jax.default_matmul_precision("highest"):
        want_o, want_S = jax.jit(lambda *a: decoder._delta_rule(cfg, *a))(
            ssm[1], q, k, v, g, beta)
    live = jnp.ones((shape[1],), jnp.int32)

    def step(leaf, xs):
        q, k, v, g, beta = xs
        o, leaf = D.delta_update(leaf, jnp.int32(1), q, k, v, jnp.exp(g),
                                 beta, live, interpret=True)
        return leaf, o

    out, os_ = jax.jit(lambda leaf, xs: jax.lax.scan(step, leaf, xs))(
        ssm, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    assert np.allclose(jnp.moveaxis(os_, 0, 1), want_o, atol=5e-5, rtol=1e-4)
    assert np.allclose(out[1], want_S, atol=5e-5, rtol=1e-4)
    assert np.array_equal(out[0], ssm[0])


@pytest.mark.parametrize("H, dk, dv, tiles", [
    (30, 96, 192, True), (4, 8, 16, True), (4, 12, 16, False),
    (2, 4096, 1024, False)])
def test_the_tile_rule(H, dk, dv, tiles):
    """Whole float32 sublane tiles along dk, and a head that fits the
    kernel's share of VMEM; in interpret mode anything goes."""
    assert D.delta_tileable(H, dk, dv) == tiles
    assert D.delta_tileable(H, dk, dv, interpret=True)
    hb = D.heads_per_block(H, dk, dv)
    assert (hb > 0) == (tiles or dk % 8 != 0)
    assert hb == 0 or H % hb == 0


def test_the_published_heads_go_ten_a_block():
    """Ten heads' matrices in and out, double-buffered, are 4 MB of VMEM,
    on either layout: five of the fifteen pairs, or ten of the thirty heads
    padded to 256 lanes."""
    assert D.heads_per_block(15, 96, 384) == 5
    assert D.heads_per_block(30, 96, 192) == 10


@pytest.mark.parametrize("kernels, dk, took, fell_back", [
    ("interpret", 8, "delta_update", False),
    ("xla", 8, "xla_recurrence", False),
    ("pallas", 12, "xla_recurrence", True)])
def test_the_mixer_says_which_form_it_took(kernels, dk, took, fell_back):
    """The kernel where the configuration's kernels resolve to one and the
    heads tile; the four passes otherwise, flagged where the kernel was
    wanted (``Engine._compile`` raises ``kernel_fallback`` from that)."""
    cfg = dataclasses.replace(CFG, kernels=kernels, delta_key_dim=dk)
    shape = (3, 2, cfg.delta_heads, dk, cfg.delta_value_dim)
    ssm, *seqs = inputs(shape, seed=5)
    n_valid = jnp.ones((2,), jnp.int32)
    with record_kernels() as picked:
        out = jax.eval_shape(
            lambda *a: decoder._delta_step(cfg, *a), ssm, jnp.int32(1),
            *seqs, n_valid)
    assert picked == [("delta.update", took, fell_back)]
    assert (out is None) == (took != "delta_update")


def test_more_than_one_position_keeps_the_blocked_form(monkeypatch):
    """T > 1 (admission, pieces, extend) never reaches the kernel."""
    def refuse(*a, **kw):
        raise AssertionError("the kernel was given more than one position")
    monkeypatch.setattr(D, "delta_update", refuse)
    params = decoder.init_params(KERNEL_CFG, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    toks = jnp.arange(3, 19, dtype=jnp.int32)[None]
    with record_kernels() as picked:
        decoder.prefill_chunk(params, KERNEL_CFG, toks)
    assert not [p for p in picked if p[0] == "delta.update"]


# -- through the model and the engine ------------------------------------

@pytest.fixture(scope="module")
def params():
    return decoder.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def cache_after_prefill(params, B=2, S=64):
    kc = jnp.zeros((CFG.n_full_layers, B, CFG.n_kv_heads, S, CFG.head_dim))
    K, V = decoder.join_state(kc, kc, decoder.empty_state(CFG, B))
    _, K, V = decoder.forward_with_cache(
        params, CFG, tokens(8 * B, seed=12).reshape(B, 8), K, V,
        jnp.zeros((B,), jnp.int32))
    return K, V


def test_a_decode_step_with_the_kernel_is_the_step_without(params):
    """Logits, state and convolution inputs of one decode step from a state
    a prefill left, the kernel against the compiler's form."""
    K, V = cache_after_prefill(params)
    args = (tokens(2, seed=3)[:, None], K, V, jnp.full((2,), 8, jnp.int32))
    want, Kw, Vw = jax.jit(lambda p, *a: decoder.forward_with_cache(
        p, CFG, *a))(params, *args)
    got, Kg, Vg = jax.jit(lambda p, *a: decoder.forward_with_cache(
        p, KERNEL_CFG, *a))(params, *args)
    assert np.allclose(got, want, atol=2e-5, rtol=1e-5)
    assert np.allclose(Kg["ssm"], Kw["ssm"], atol=2e-5, rtol=1e-5)
    assert np.allclose(Vg["conv"], Vw["conv"], atol=1e-6)
    assert not np.allclose(Kg["ssm"], K["ssm"], atol=1e-3)


def test_a_decode_row_with_nothing_real_keeps_its_bits(params):
    """``test_a_row_with_nothing_real_keeps_its_bits`` for one position
    through the kernel: n_valid 0 beside a row that moves."""
    K, V = cache_after_prefill(params)
    _, K1, V1 = jax.jit(lambda p, *a, n: decoder.forward_with_cache(
        p, KERNEL_CFG, *a, n_valid=n))(
        params, tokens(2, seed=4)[:, None], K, V,
        jnp.full((2,), 8, jnp.int32), n=jnp.array([0, 1], jnp.int32))
    assert np.array_equal(K1["ssm"][:, 0], K["ssm"][:, 0])
    assert np.array_equal(V1["conv"][:, 0], V["conv"][:, 0])
    assert not np.array_equal(K1["ssm"][:, 1], K["ssm"][:, 1])


def make_engine(cfg, params, **kw):
    return Engine(cfg, params, ecfg=EngineConfig(
        max_slots=4, max_seq_len=128, cache_dtype=jnp.float32,
        decode_chunk=4, min_prefill_bucket=16, **kw))


def test_the_engine_names_the_kernel_for_its_decode_program(params):
    """``kernels_by_kind`` (the ``warm_plan`` event's content) says the
    decode program took the kernel and no admission did; nothing fell back."""
    from ollama_operator_tpu.runtime.trace import FLIGHT

    def fallbacks():
        return [e for e in FLIGHT.snapshot() if e["kind"] == "kernel_fallback"
                and e.get("site") == "delta.update"]
    before = len(fallbacks())
    eng = make_engine(KERNEL_CFG, params)
    eng.admit(0, tokens(10), GREEDY)
    eng.decode_n(4)
    by_kind = eng.kernels_by_kind()
    assert "delta.update=delta_update" in by_kind["decode"]
    assert not any("delta.update" in pick for kind, picks in by_kind.items()
                   if kind != "decode" for pick in picks)
    assert "delta.update=xla_recurrence" in make_plain(params)["decode"]
    assert len(fallbacks()) == before


def make_plain(params):
    eng = make_engine(CFG, params)
    eng.admit(0, tokens(10), GREEDY)
    eng.decode_n(4)
    return eng.kernels_by_kind()


def test_the_engines_stream_and_idle_slots_with_the_kernel(params):
    """A greedy stream of three chunks is the plain engine's, and the slots
    that do not decode (parked, released, never used) keep their bits
    through the kernel's chunks."""
    eng, plain = make_engine(KERNEL_CFG, params), make_engine(CFG, params)
    for e in (eng, plain):
        e.admit(0, tokens(10), GREEDY)
        e.admit(1, tokens(16, seed=6), GREEDY)
        e.release(1, park=True)
    before = [state_of(eng, s) for s in range(4)]
    got = [np.asarray(eng.decode_n(4))[:, 0] for _ in range(3)]
    want = [np.asarray(plain.decode_n(4))[:, 0] for _ in range(3)]
    assert np.array_equal(np.concatenate(got), np.concatenate(want))
    for s in (1, 2, 3):
        for b, a in zip(before[s], state_of(eng, s)):
            assert np.array_equal(b, a), s
    for a, b in zip(state_of(eng, 0), state_of(plain, 0)):
        assert np.allclose(a, b, atol=5e-5)
    assert not np.array_equal(before[0][0], state_of(eng, 0)[0])


# -- two heads side by side along lanes -----------------------------------

# the toy with a head's values 64 wide: two heads' rows fill a 128-lane tile,
# as the published head's 192 fill three with its neighbour's
WIDE = dataclasses.replace(CFG, delta_value_dim=64)
WIDE_KERNEL = dataclasses.replace(WIDE, kernels="interpret")


@pytest.mark.parametrize("dv, H, want", [
    (192, 30, 2), (64, 4, 2), (16, 4, 1), (128, 4, 1), (64, 3, 1),
    (96, 4, 1), (320, 8, 2)])
def test_the_layout_is_read_from_the_shape(dv, H, want):
    """Two heads a row of the leaf where one head's values would lie padded
    in 128-lane tiles and two heads' fill whole ones; no knob."""
    cfg = dataclasses.replace(CFG, delta_value_dim=dv, delta_heads=H)
    assert decoder._delta_pack(cfg) == want
    ssm, conv, _ = jax.eval_shape(lambda: decoder.empty_state(cfg, 3))
    assert ssm.shape == (6, 3, H // want, 8, want * dv)
    assert ssm.size * 4 + conv.size * 4 == 3 * cfg.ssm_state_bytes


def test_the_published_state_keeps_its_bytes():
    """``tpu_model_cache_bytes{kind="state"}`` a slot (the benchmark's
    ``state_mb_per_slot``): 21.151 MB, float32, whichever way it lies."""
    assert decoder._delta_pack(PRESET) == 2
    ssm, conv, _ = jax.eval_shape(lambda: decoder.empty_state(PRESET, 32))
    assert ssm.shape == (9, 32, 15, 96, 384) and ssm.dtype == jnp.float32
    per_slot = (ssm.size + conv.size) * 4 / 32
    assert per_slot == PRESET.ssm_state_bytes == 21_150_720


def test_packing_lays_a_pair_of_heads_side_by_side():
    S = jnp.arange(2 * 4 * 8 * 16, dtype=jnp.float32).reshape(2, 4, 8, 16)
    packed = decoder._delta_packed(S, 2)
    assert packed.shape == (2, 2, 8, 32)
    assert np.array_equal(packed[:, 1, :, :16], S[:, 2])
    assert np.array_equal(packed[:, 1, :, 16:], S[:, 3])
    assert np.array_equal(decoder._delta_unpacked(packed, 2), S)
    assert decoder._delta_packed(S, 1) is S


@pytest.mark.parametrize("shape, hb", [
    ((3, 3, 4, 8, 64), 0), ((3, 3, 4, 8, 64), 2), (PUBLISHED, 0),
    (PUBLISHED, 10)], ids=["wide-toy", "wide-toy-a-pair-a-block",
                           "published", "published-five-pairs-a-block"])
def test_the_kernel_on_paired_heads_is_the_recurrence(shape, hb):
    ssm, *seqs = inputs(shape, seed=21)
    live = np.ones(shape[1], np.int32)
    want_o, want_S = decoder._delta_rule(CFG, ssm[1], *seqs)
    leaf = jax.vmap(lambda S: decoder._delta_packed(S, 2))(ssm)
    assert leaf.shape[2:] == (shape[2] // 2, shape[3], 2 * shape[4])
    o, out = kernel_step(leaf, 1, *seqs, live, hb=hb)
    assert np.allclose(o, want_o[:, 0], atol=2e-5, rtol=1e-5)
    assert np.allclose(decoder._delta_unpacked(out[1], 2), want_S,
                       atol=2e-5, rtol=1e-5)
    assert np.array_equal(out[0], leaf[0])
    assert np.array_equal(out[2:], leaf[2:])


@pytest.fixture(scope="module")
def wide_params():
    return decoder.init_params(WIDE, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.mark.parametrize("cfg", [WIDE, WIDE_KERNEL], ids=["xla", "kernel"])
def test_paired_heads_against_the_reference(wide_params, cfg):
    """Prefill 24 positions, then 16 decode steps through the cache, with
    the state two heads a row: each position's logits against the
    benchmark's reference, which knows no layout."""
    from benchmark import server_child, work
    ref = server_child.load_reference(work.load_conf(CONF_PATH))
    toks = tokens(40)
    want = np.asarray(ref.forward(wide_params, conf_of(WIDE),
                                  jnp.asarray(toks)))
    scale = np.abs(want).max()
    logits, ks, vs = jax.jit(lambda p, t: decoder.prefill_chunk(p, cfg, t))(
        wide_params, toks[None, :24])
    assert ks["ssm"].shape == (6, 1, 2, 8, 128)
    assert np.abs(np.asarray(logits[0]) - want[:24]).max() < 2e-4 * scale
    kc = jnp.zeros((WIDE.n_full_layers, 1, WIDE.n_kv_heads, 64,
                    WIDE.head_dim))
    K, V = decoder.join_state(kc.at[:, :, :, :24].set(ks["kv"]),
                              kc.at[:, :, :, :24].set(vs["kv"]),
                              (ks["ssm"], vs["conv"], None))
    step = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
        p, cfg, t, K, V, n))
    for i in range(24, 40):
        lg, K, V = step(wide_params, toks[None, i:i + 1], K, V,
                        jnp.array([i], jnp.int32))
        assert np.abs(np.asarray(lg[0, 0]) - want[i]).max() < 2e-4 * scale, i


@pytest.mark.parametrize("pieces", [(16, 24), (1, 1, 38), (7, 33)])
def test_paired_heads_in_pieces_equal_one_piece(wide_params, pieces):
    toks = tokens(40, seed=1)
    want_l, ks, vs = jax.jit(lambda p, t: decoder.prefill_chunk(p, WIDE, t))(
        wide_params, toks[None])
    kc = jnp.zeros((WIDE.n_full_layers, 1, WIDE.n_kv_heads, 64,
                    WIDE.head_dim))
    K, V = decoder.join_state(kc, kc, decoder.empty_state(WIDE, 1))
    at = 0
    for n in pieces:
        lg, K, V = decoder.forward_with_cache(
            wide_params, WIDE_KERNEL, toks[None, at:at + n], K, V,
            jnp.array([at], jnp.int32))
        at += n
    assert np.allclose(lg[0, -1], want_l[0, -1], atol=1e-5)
    assert np.allclose(K["ssm"], ks["ssm"], atol=1e-5)
    assert np.allclose(V["conv"], vs["conv"], atol=1e-5)


def test_a_paired_row_with_nothing_real_keeps_its_bits(wide_params):
    """The blocked form converts at its border: a row with no real position
    goes through unpack and pack and keeps its very bits all the same."""
    kc = jnp.zeros((WIDE.n_full_layers, 2, WIDE.n_kv_heads, 64,
                    WIDE.head_dim))
    K, V = decoder.join_state(kc, kc, decoder.empty_state(WIDE, 2))
    _, K, V = decoder.forward_with_cache(
        wide_params, WIDE, tokens(16, seed=12).reshape(2, 8), K, V,
        jnp.zeros((2,), jnp.int32))
    _, K1, V1 = decoder.forward_with_cache(
        wide_params, WIDE, tokens(16, seed=13).reshape(2, 8), K, V,
        jnp.full((2,), 8, jnp.int32), n_valid=jnp.array([0, 5], jnp.int32))
    assert np.array_equal(K1["ssm"][:, 0], K["ssm"][:, 0])
    assert not np.array_equal(K1["ssm"][:, 1], K["ssm"][:, 1])


def test_the_engine_carries_paired_heads_unchanged(wide_params):
    """Admission, a parked slot, chunks of the kernel and the gauge: the
    engine sees the leaf by its first two axes and its bytes. The stream is
    the plain engine's, the parked slot resumes as a fresh prefill would,
    and the state gauge reads what the configuration's arithmetic says."""
    eng = make_engine(WIDE_KERNEL, wide_params)
    plain = make_engine(WIDE, wide_params)
    assert eng.cache_bytes["state"] == 4 * WIDE.ssm_state_bytes
    for e in (eng, plain):
        e.admit(0, tokens(10), GREEDY)
        e.admit(1, tokens(16, seed=6), GREEDY)
        e.release(1, park=True)
    got = [np.asarray(eng.decode_n(4))[:, 0] for _ in range(3)]
    want = [np.asarray(plain.decode_n(4))[:, 0] for _ in range(3)]
    assert np.array_equal(np.concatenate(got), np.concatenate(want))
    t = eng.extend(1, tokens(30, seed=6), 16, GREEDY)
    fresh = make_engine(WIDE_KERNEL, wide_params)
    assert t == fresh.admit(1, tokens(30, seed=6), GREEDY)
    _, _, (a, _, _) = decoder.split_state(eng.k_cache, eng.v_cache)
    _, _, (b, _, _) = decoder.split_state(fresh.k_cache, fresh.v_cache)
    assert a.shape == (6, 4, 2, 8, 128)
    assert np.allclose(a[:, 1], b[:, 1], atol=1e-5)
