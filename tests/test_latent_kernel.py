"""Latent attention's one-position step as one kernel
(``ops/pallas/latent.latent_decode``), beside ``tests/test_glm5.py``'s: the
kernel in interpret mode on the CPU, at rehearsal widths, against the
absorbed mathematics written out in numpy and against ``_latent_absorbed``'s
einsum form through the layer itself (``_latent_cached``): ragged lengths in
one batch, slots with nothing real, the indexer's selection as an operand,
int8 and bfloat16 caches, rows of a stacked leaf other than the first, the
walk's seams between slots, how the layer chooses the kernel and says so, and
that it changes nothing a sequence sees through the engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.ops import quant_cache as QC
from ollama_operator_tpu.ops.attention import record_kernels
from ollama_operator_tpu.ops.pallas import latent as LK
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)

CFG = cfglib.PRESETS["tiny-glm5"]
KERNEL_CFG = dataclasses.replace(CFG, kernels="interpret")
BIG = cfglib.PRESETS["glm-5"]
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
La, S, A, BLOCK = 2, 96, 64, 16    # slots of six blocks, a bucket of four
C, DR, H = CFG.kv_latent_dim, CFG.qk_rope_dim, CFG.n_heads
SCALE = decoder._latent_scale(CFG)
# a block's edge and the bucket's end, with a slot that holds nothing between
RAGGED = ([0, 14, 15, 16, 17, 63], [1, 1, 0, 1, 1, 1])


def leaf_of(cache: str, B: int, seed: int = 0, pad: int = 0):
    """A rows' leaf [La, B, 1, S, C + DR + pad] that is full of something,
    as the cache keeps it: int8 codes with two scales a position, or plain
    rows in ``cache``'s type; and the same as float64 (rows, scales)."""
    rng = np.random.default_rng([seed, 51])
    W = C + DR + pad
    if cache == "int8":
        q = rng.integers(-127, 128, (La, B, 1, S, W)).astype(np.int8)
        q[..., C + DR:] = 0
        s = rng.uniform(0.004, 0.02, (La, B, 2, S)).astype(np.float32)
        return {"q": jnp.asarray(q), "s": jnp.asarray(s)}, (q, s)
    x = rng.normal(size=(La, B, 1, S, W)).astype(np.float32)
    x[..., C + DR:] = 0
    x = jnp.asarray(x, getattr(jnp, cache))
    return x, (np.asarray(x, np.float32), None)


def queries(B: int, seed: int = 0):
    rng = np.random.default_rng([seed, 52])
    return (rng.normal(size=(B, H, C)).astype(np.float32),
            rng.normal(size=(B, H, DR)).astype(np.float32))


def written_out(q_abs, q_rope, rows, scales, row, q_pos, keep):
    """The absorbed step in float64, a slot at a time over its own rows."""
    out = np.zeros(q_abs.shape, np.float64)
    for b, p in enumerate(q_pos):
        x = rows[row, b, 0, :p + 1].astype(np.float64)
        lat, kr = x[:, :C], x[:, C:C + DR]
        s_lat = s_rot = 1.0
        if scales is not None:
            s_lat, s_rot = scales[row, b, :, :p + 1].astype(np.float64)
        s = (q_abs[b] @ lat.T * s_lat + q_rope[b] @ kr.T * s_rot) * SCALE
        if keep is not None:
            s = np.where(keep[b, :p + 1], s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (e / e.sum(-1, keepdims=True) * s_lat) @ lat
    return out


def run(leaf, row, q_abs, q_rope, q_pos, live, keep=None, block=BLOCK):
    return np.asarray(jax.jit(lambda leaf, *a: LK.latent_decode(
        leaf, jnp.int32(row), *a, SCALE, block=block, interpret=True))(
        leaf, jnp.asarray(q_abs), jnp.asarray(q_rope),
        jnp.asarray(q_pos, jnp.int32), jnp.asarray(live, jnp.int32),
        None if keep is None else jnp.asarray(keep)))


def close(got, want, tol):
    return np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("row", [0, 1])
@pytest.mark.parametrize("cache", ["int8", "bfloat16", "float32"])
def test_the_kernel_is_the_absorbed_step_written_out(cache, row):
    """Ragged lengths in one batch (the first position alone, a block's edge
    and one to either side, the bucket's last position), a slot that is not
    live among them, either row of a stacked leaf, each type of cache: every
    live slot's output is the step over its own rows and no others."""
    q_pos, live = RAGGED
    leaf, (rows, scales) = leaf_of(cache, len(q_pos), seed=row)
    q_abs, q_rope = queries(len(q_pos), seed=row)
    got = run(leaf, row, q_abs, q_rope, q_pos, live)
    want = written_out(q_abs, q_rope, rows, scales, row, q_pos, None)
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
    the slots around it read their own rows whichever of them it is: the
    walk's next block in flight skips it."""
    q_pos = [33, 2, 47, 16, 63, 31]
    leaf, (rows, scales) = leaf_of("int8", 6, seed=3)
    q_abs, q_rope = queries(6, seed=3)
    got = run(leaf, 1, q_abs, q_rope, q_pos, live)
    want = written_out(q_abs, q_rope, rows, scales, 1, q_pos, None)
    m = np.asarray(live, bool)
    assert np.isfinite(got).all() and not got[~m].any()
    assert not m.any() or close(got[m], want[m], 2e-5)


@pytest.mark.parametrize("cache", ["int8", "bfloat16"])
@pytest.mark.parametrize("kept", [1, 5, 16])
def test_the_selection_rides_in_as_an_operand(cache, kept):
    """A keep mask drops rows the query could see: only the kept positions
    (the query's own always among them) are scored and summed, whole blocks
    of dropped positions included."""
    q_pos, live = [63, 40, 17, 63], [1, 1, 1, 1]
    rng = np.random.default_rng(kept)
    keep = np.zeros((4, A), bool)
    for b, p in enumerate(q_pos):
        keep[b, rng.permutation(p)[:kept - 1]] = True
        keep[b, p] = True
    keep[3] = False
    keep[3, 48:64] = True                 # three whole blocks dropped
    leaf, (rows, scales) = leaf_of(cache, 4, seed=kept)
    q_abs, q_rope = queries(4, seed=kept)
    got = run(leaf, 0, q_abs, q_rope, q_pos, live, keep)
    want = written_out(q_abs, q_rope, rows, scales, 0, q_pos, keep)
    assert close(got, want, 2e-5)
    seen = run(leaf, 0, q_abs, q_rope, q_pos, live)
    assert not close(seen, want, 1e-3)


@pytest.mark.parametrize("depth", [2, 3, 4])
@pytest.mark.parametrize("block", [8, 16, 48])
def test_the_walk_crosses_slots_at_any_depth(monkeypatch, depth, block):
    """Buffers in flight ahead of the block scored, across slots' ends: two
    to four of them over blocks of eight positions to half a slot, which end
    past the bucket (a slot of one block has its successor's blocks in
    flight behind it)."""
    monkeypatch.setattr(LK, "_walk_depth", lambda block_bytes: depth)
    q_pos, live = [63, 0, 9, 63, 5, 40, 1, 24], [1, 1, 1, 1, 0, 1, 1, 1]
    leaf, (rows, scales) = leaf_of("int8", 8, seed=depth)
    q_abs, q_rope = queries(8, seed=block)
    got = run(leaf, 1, q_abs, q_rope, q_pos, live, block=block)
    want = written_out(q_abs, q_rope, rows, scales, 1, q_pos, None)
    m = np.asarray(live, bool)
    assert close(got[m], want[m], 2e-5) and not got[~m].any()


def test_a_row_of_whole_lane_tiles_reads_past_its_key():
    """The published row's shape in small: zeros behind the rotated key up
    to a whole tile, which the rotated query's padding meets."""
    q_pos, live = [20, 63], [1, 1]
    leaf, (rows, scales) = leaf_of("int8", 2, seed=8, pad=24)
    q_abs, q_rope = queries(2, seed=8)
    got = run(leaf, 0, q_abs, q_rope, q_pos, live)
    assert close(got, written_out(q_abs, q_rope, rows, scales, 0, q_pos,
                                  None), 2e-5)


@pytest.mark.parametrize("H, C, W, S, interpret, ok", [
    (64, 512, 640, 4096, False, True), (64, 512, 640, 1024, False, True),
    (64, 512, 576, 4096, False, False), (64, 500, 640, 4096, False, False),
    (4, 512, 640, 4096, False, False), (64, 512, 640, 4000, False, False),
    (64, 512, 512, 4096, False, False), (4, 32, 40, 48, True, True),
    (4, 32, 32, 48, True, False)],
    ids=["published-deep", "published-shallow", "row-not-padded",
         "latent-not-tiles", "four-heads", "slot-not-blocks", "no-key",
         "toy-interpreted", "toy-no-key"])
def test_the_shapes_the_kernel_takes(H, C, W, S, interpret, ok):
    assert LK.latent_decode_tileable(H, C, W, S, interpret) is ok
    if ok and not interpret:
        assert LK._block_rows(S, LK._BLOCK_ROWS, False) % 128 == 0


def test_a_shape_that_does_not_tile_returns_none():
    leaf, _ = leaf_of("int8", 2)
    q_abs, q_rope = queries(2)
    assert LK.latent_decode(
        leaf, jnp.int32(0), jnp.asarray(q_abs), jnp.asarray(q_rope),
        jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32), None, SCALE,
        interpret=False) is None
    assert BIG.cache_row_dims[1] == 640 and LK.latent_decode_tileable(
        BIG.n_heads, BIG.kv_latent_dim, 640, 4096, False)


# -- through the layer -----------------------------------------------------

@pytest.fixture(scope="module")
def params():
    return decoder.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def layer_inputs(params, cache, lengths, seed=0):
    """One routed layer's attention weights, B normed inputs of one position
    and both caches, full of something up to each slot's length."""
    B = len(lengths)
    ap = {k: v[1] for k, v in params["layers"].items()
          if k in decoder._ATTN_STACK}
    rng = np.random.default_rng([seed, 53])
    h = jnp.asarray(rng.normal(size=(B, 1, CFG.dim)), jnp.float32)
    _, kd, vd = CFG.cache_row_dims
    dt = getattr(jnp, cache)
    kc = jnp.asarray(rng.normal(size=(La, B, 1, S, kd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(La, B, 1, S, vd)), jnp.float32)
    if cache == "int8":
        kq, ks = QC.quantize_latent(kc[:, :, 0], C)
        kc = {"q": kq[:, :, None], "s": jnp.moveaxis(ks, -1, -2)}
        vq, vs = QC.quantize_kv(vc)
        vc = {"q": vq, "s": vs}
    else:
        kc, vc = kc.astype(dt), vc.astype(dt)
    return ap, h, kc, vc


def layer(cfg, ap, h, kc, vc, row, lengths, nv, T=1):
    lengths = jnp.asarray(lengths, jnp.int32)
    pos = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    cos, sin = decoder.rope_angles(pos, cfg.qk_rope_dim, cfg.rope_theta)
    fn = jax.jit(lambda ap, h, kc, vc, row, nv: decoder._latent_cached(
        cfg, ap, h, kc, vc, row, pos, nv, A, cos, sin))
    with record_kernels() as picked:
        out = fn(ap, h, kc, vc, jnp.int32(row), jnp.asarray(nv, jnp.int32))
    return out, sorted(picked)


@pytest.mark.parametrize("row", [0, 1])
@pytest.mark.parametrize("cache", ["int8", "bfloat16"])
def test_the_layer_through_the_kernel_is_the_einsum_form(monkeypatch, params,
                                                         cache, row):
    """``_latent_cached`` at T == 1 with the kernel against the same call in
    the einsum form: ragged lengths on both sides of ``index_topk`` (past it
    the indexer's keep mask goes to the kernel; the toy keeps 16), a slot
    that holds nothing; the same outputs for the live slots, and both caches
    come back bit for bit the einsum form's: written at the new position,
    untouched by the read."""
    monkeypatch.setattr(LK, "_BLOCK_ROWS", BLOCK)
    lengths, nv = RAGGED
    args = layer_inputs(params, cache, lengths, seed=row)
    (want, kc0, vc0), said0 = layer(CFG, *args, row, lengths, nv)
    (got, kc1, vc1), said1 = layer(KERNEL_CFG, *args, row, lengths, nv)
    assert said0 == [("decode", "einsum", False)]
    assert said1 == [("decode", "latent_decode", False)]
    m = np.asarray(nv, bool)
    assert np.isfinite(np.asarray(got)).all()
    assert close(np.asarray(got)[m], np.asarray(want)[m], 2e-5)
    for a, b in zip(jax.tree_util.tree_leaves((kc0, vc0)),
                    jax.tree_util.tree_leaves((kc1, vc1))):
        assert np.array_equal(a, b)
    was = jax.tree_util.tree_leaves(args[2])[0]
    now = jax.tree_util.tree_leaves(kc1)[0]
    assert np.array_equal(was[1 - row], now[1 - row])
    assert not np.array_equal(was[row], now[row])


def test_several_positions_keep_the_einsum_form(params):
    """T > 1 (an extend piece, the probe's prefill) is the einsum form by
    design: said so, and not as a fallback."""
    lengths, nv = [3, 20], [4, 4]
    ap, _, kc, vc = layer_inputs(params, "int8", lengths)
    h = jnp.ones((2, 4, CFG.dim), jnp.float32)
    _, said = layer(KERNEL_CFG, ap, h, kc, vc, 0, lengths, nv, T=4)
    assert said == [("decode", "einsum", False)]


def test_a_wanted_kernel_that_cannot_tile_is_a_fallback(params):
    """``pallas`` at widths Mosaic cannot tile (the toy's 40-channel row):
    the einsum form serves, flagged, so the harness's ``kernel_fallback``
    check still guards the served path."""
    lengths, nv = [3, 20], [1, 1]
    args = layer_inputs(params, "int8", lengths)
    cfg = dataclasses.replace(CFG, kernels="pallas")
    (got, _, _), said = layer(cfg, *args, 0, lengths, nv)
    (want, _, _), _ = layer(CFG, *args, 0, lengths, nv)
    assert said == [("decode", "einsum", True)]
    assert np.array_equal(got, want)


@pytest.mark.parametrize("cache", ["int8", "float32"])
def test_the_engine_serves_the_same_stream_through_the_kernel(params, cache):
    """Two requests of unlike lengths through admission, an extend and two
    decode chunks: the kernel's engine says ``latent_decode`` for its decode
    programs and ``einsum`` for its extend, and streams the einsum engine's
    tokens."""
    def engine(cfg):
        return Engine(cfg, params, ecfg=EngineConfig(
            max_slots=3, max_seq_len=128, cache_dtype=getattr(jnp, cache),
            decode_chunk=4, min_prefill_bucket=16))

    def stream(eng):
        toks = np.random.default_rng(5).integers(
            3, CFG.vocab_size, (40,)).astype(np.int32)
        out = [eng.admit(0, toks[:22], GREEDY)]
        eng.admit(2, toks[:16], GREEDY)
        eng.release(2, park=True)
        out.append(eng.extend(2, toks, 16, GREEDY))
        for _ in range(2):
            out.append(np.asarray(eng.decode_n(4)).tolist())
        return out

    plain, kernel = engine(CFG), engine(KERNEL_CFG)
    assert stream(kernel) == stream(plain)
    said = kernel.kernels_by_kind()
    assert "decode=latent_decode" in said["decode"]
    assert "decode=einsum" in said["extend"]
    assert "decode=einsum" in plain.kernels_by_kind()["decode"]
