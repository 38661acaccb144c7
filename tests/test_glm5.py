"""Latent attention with its indexer (glm_moe_dsa): a cache of one row [latent
| rotated key] a position and one indexer key beside it, attention over the
``index_topk`` positions the indexer keeps, decoded in absorbed form, in the
hybrid scan beside k-exaone's routed feed-forward. CPU, the toy of the same
shape (``tiny-glm5``: the indexer keeps 16 positions, so a prompt of a few
dozen tokens chooses at every later position), seeded weights; the plain
reference is the benchmark's (``benchmark/configs/glm-5.reference.py``:
expanded keys and values, no cache), read at the toy's sizes through the
configuration file's own ``holds``."""

import dataclasses
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.ops import quant_cache as QC
from ollama_operator_tpu.runtime import accounting
from ollama_operator_tpu.runtime import engine as englib
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

from benchmark import index_choices, server_child, work
from test_hybrid import drain, make_stack, manual, run_to_end, uninterrupted

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONF_PATH = os.path.join(REPO, "benchmark", "configs", "glm-5.json")
CELL = "glm-5.decode-deep"
CFG = cfglib.PRESETS["tiny-glm5"]
BIG = cfglib.PRESETS["glm-5"]
TOPK = CFG.index_topk
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
SEEDED = SlotOptions(temperature=0.9, seed=1234, repeat_penalty=1.0)
NEW_READERS = ("decode_index_ms_per_step", "index_select_roofline",
               "index_kept_share", "index_cache_mb_per_slot")


def conf_of(cfg):
    """The configuration file's dict at ``cfg``'s sizes: each key the file
    holds the preset to, read back from the config."""
    conf = work.load_conf(CONF_PATH)
    for ours, theirs in conf["holds"]:
        conf[theirs] = getattr(cfg, ours)
    return conf


@pytest.fixture(scope="module")
def ref():
    return server_child.load_reference(work.load_conf(CONF_PATH))


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, CFG.vocab_size, (n,)
                                                ).astype(np.int32)


def make_engine(params, slots=4, cache=jnp.float32, **kw):
    return Engine(CFG, params, ecfg=EngineConfig(
        max_slots=slots, max_seq_len=128, cache_dtype=cache, decode_chunk=4,
        min_prefill_bucket=16, **kw))


def rows_of(eng, slot):
    """Every leaf of one slot's two caches (rows then indexer keys; codes
    and scales where the cache is int8), as host arrays."""
    return [np.asarray(a[:, slot]) for a in
            jax.tree_util.tree_leaves((eng.k_cache, eng.v_cache))]


def empty_cache(B, S, cache="float32"):
    """(rows, indexer keys) of ``B`` empty slots of ``S`` positions."""
    La = CFG.n_full_layers
    _, kd, vd = CFG.cache_row_dims
    if cache == "int8":
        kc = QC.empty_cache(La, B, 1, S, kd)
        kc["s"] = jnp.zeros((La, B, 2, S), jnp.float32)
        return kc, QC.empty_cache(La, B, 1, S, vd)
    return jnp.zeros((La, B, 1, S, kd)), jnp.zeros((La, B, 1, S, vd))


def kept_by_the_program(fn, *args):
    """``fn(*args)`` run under the indexer's tap: (its result, the masks it
    handed out)."""
    with index_choices.record_index() as kept:
        out = jax.block_until_ready(jax.jit(fn)(*args))
        return out, kept.masks()


# -- the configuration ---------------------------------------------------

def test_preset_is_the_published_shape():
    """The served preset against the configuration's file, key by key (the
    benchmark's own check), every width against the catalog's row, the cut's
    floors and the issue's arithmetic."""
    conf = server_child.load_conf(CONF_PATH, False)
    cfg = server_child.model_config(conf, False)
    assert cfg is BIG and cfg.layer_kinds == "AAAAAAA"
    assert (cfg.n_full_layers, cfg.n_dense_layers, cfg.n_routed_layers) == (
        7, 1, 6)
    # the row's rotated key rounded up to a whole lane tile where the latent
    # fills whole ones; the toy's row is as it is
    assert cfg.cache_row_dims == (1, 576 + 64, 128)
    assert (cfg.latent_row_pad, CFG.latent_row_pad) == (64, 0)
    assert CFG.cache_row_dims == (1, 40, 16)
    # every width is the published one
    assert (cfg.dim, cfg.n_heads, cfg.q_latent_dim, cfg.kv_latent_dim) == (
        6144, 64, 2048, 512)
    assert (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (
        192, 64, 256)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (
        32, 128, 2048)
    assert (cfg.dense_ffn_dim, cfg.ffn_dim, cfg.n_shared_ffn) == (
        12288, 2048, 2048)
    assert (cfg.n_experts, cfg.n_experts_used, cfg.moe_scale) == (256, 8, 2.5)
    assert cfg.rope_interleave and not cfg.tie_embeddings
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
        assert row["source_url"] == conf["source"]
        differ = {k for k, v in row["config"].items() if conf.get(k) != v}
        assert differ == set(conf["reduced"])
    # the floors of a cut, and its reasons
    assert cfg.n_routed_layers >= 4 and cfg.experts_held >= 8
    assert conf["published"]["num_hidden_layers"] == 78
    assert set(conf["reduced"]) == set(conf["reduced_why"])
    held = {ours for ours, _ in conf["holds"]}
    assert {"kv_latent_dim", "q_latent_dim", "qk_nope_dim", "qk_rope_dim",
            "v_head_dim", "index_heads", "index_head_dim", "index_topk",
            "rope_interleave"} <= held
    # the issue's count: attention 165.02M, the indexer 9.37M, an expert
    # 37.75M, 16 held, the shared expert, the router; the dense layer; the
    # held rows twice
    attn = (6144 * 2048 + 2048 * 16384 + 6144 * 576 + 512 * 28672
            + 16384 * 6144)
    index = 2048 * 4096 + 6144 * 128 + 6144 * 32
    expert = 3 * 6144 * 2048
    assert (attn, index) == (165_019_648, 9_371_648)
    assert cfg.n_params == (7 * (attn + index) + 3 * 6144 * 12288
                            + 6 * (17 * expert + 6144 * 256)
                            + 2 * 19360 * 6144)
    assert 11.08e9 < 2 * cfg.n_params < 11.10e9


def test_n_params_counts_what_init_params_makes():
    """The sizing formula against the leaves themselves: every matrix of
    the stack exactly; what it leaves out is the norms' vectors and the
    router's bias."""
    shapes = jax.eval_shape(lambda k: decoder.init_params(CFG, k),
                            jax.random.PRNGKey(0))
    every = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    matrices = sum(int(np.prod(a.shape)) for k, a in shapes["layers"].items()
                   if a.ndim >= 3) + sum(
        int(np.prod(a.shape)) for k, a in shapes.items()
        if k != "layers" and a.ndim == 2)
    assert CFG.n_params == matrices
    assert abs(CFG.n_params - every) < 0.02 * every
    assert not {"wq", "wk", "wv"} & set(shapes["layers"])


def test_validate_accepts_and_refuses():
    """Latent attention in a stack of its own, with its indexer, rotating
    at one theta; each refusal names what it refused."""
    for bad, msg in (
            (dict(layer_kinds="AAwA", sliding_window=8), "stack of its own"),
            (dict(layer_kinds="AAcA"), "stack of its own"),
            (dict(index_topk=0), "or there is none"),
            (dict(index_heads=0), "or there is none"),
            (dict(q_latent_dim=0), "needs q_latent_dim"),
            (dict(qk_rope_dim=7), "pair up"),
            (dict(qk_rope_dim=32), "pair up"),
            (dict(rope=False), "rope_theta alone"),
            (dict(rope_scaling_type="linear", rope_scaling=2.0),
             "rope_theta alone"),
            (dict(qk_norm=True), "no qk_norm"),
            (dict(attn_bias=True), "no attn_bias"),
            (dict(layer_kinds=""), "belongs to latent attention")):
        with pytest.raises(AssertionError, match=msg):
            dataclasses.replace(CFG, **bad).validate()
    with pytest.raises(AssertionError, match="belongs to latent attention"):
        dataclasses.replace(cfglib.PRESETS["tiny-exaone"],
                            index_topk=4).validate()
    with pytest.raises(AssertionError, match="belongs to latent attention"):
        dataclasses.replace(cfglib.PRESETS["tiny"],
                            kv_latent_dim=32).validate()


def test_yarns_two_magnitudes_at_their_defaults_change_nothing():
    """``rope_yarn_mscale`` / ``rope_yarn_mscale_all_dim`` are 0 on both
    presets: cos / sin are the plain rotary's bit for bit (the call that
    makes them is the one there was) and the softmax scale is 1 / sqrt(dn +
    dr) to the last bit."""
    from ollama_operator_tpu.ops import rope
    for cfg in (CFG, BIG):
        assert (cfg.rope_yarn_mscale, cfg.rope_yarn_mscale_all_dim) == (0, 0)
        assert rope.yarn_softmax_factor(cfg) == 1.0
        assert decoder._latent_scale(cfg) == (
            cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
        pos = jnp.array([[0, 1, 17, 4095, 150000]], jnp.int32)
        cos, sin = decoder._latent_rope(cfg, pos)
        c0, s0 = rope.rope_angles(pos, cfg.qk_rope_dim, cfg.rope_theta)
        assert np.array_equal(cos, c0) and np.array_equal(sin, s0)
    assert BIG.cache_row_dims == (1, 640, 128)


# -- the model against the reference -----------------------------------

@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_prefill_then_decode_through_the_cache(ref, params, cache):
    """Prefill 28 positions (12 of them already choose), then 28 decode
    steps, absorbed, each position's logits AND each layer's kept positions
    against the reference's full forward pass with expanded keys and
    values. Float32 on both sides differs by the order of sums. Through the
    int8 cache a row carries 1/254 of its parts' largest entries, and a
    score that close to the last place may change a set: there the logits
    are held under the path's own sets (``forward_chosen``) and the
    shortfall to the harness's limit."""
    toks = tokens(56)
    conf = conf_of(CFG)
    want, own = ref.forward_sets(params, conf, jnp.asarray(toks))
    want, own = np.asarray(want), np.asarray(own["attn.index"])
    scale = np.abs(want).max()
    (logits, ks, vs), masks = kept_by_the_program(
        lambda p, t: decoder.prefill_chunk(p, CFG, t), params,
        toks[None, :28])
    assert ks.shape == (CFG.n_full_layers, 1, 1, 28, 40)
    assert vs.shape == (CFG.n_full_layers, 1, 1, 28, 16)
    assert np.abs(np.asarray(logits[0]) - want[:28]).max() < 2e-4 * scale
    kept = [np.stack([index_choices.sets_of(m[0], TOPK) for m in masks])]
    assert np.array_equal(kept[0], own[:, :28])
    K, V = empty_cache(1, 64, cache)
    if cache == "int8":
        q, s = QC.quantize_latent(ks, CFG.kv_latent_dim)
        K = {"q": K["q"].at[:, :, :, :28].set(q),
             "s": K["s"].at[:, :, :, :28].set(
                 jnp.moveaxis(s[:, :, 0], -1, 2))}
        q, s = QC.quantize_kv(vs)
        V = {"q": V["q"].at[:, :, :, :28].set(q),
             "s": V["s"].at[:, :, :, :28].set(s)}
    else:
        K, V = K.at[:, :, :, :28].set(ks), V.at[:, :, :, :28].set(vs)
    got = []
    with index_choices.record_index() as tap:
        step = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
            p, CFG, t, K, V, n))
        for i in range(28, 56):
            lg, K, V = step(params, toks[None, i:i + 1], K, V,
                            jnp.array([i], jnp.int32))
            got.append(np.asarray(lg[0, 0]))
        masks = tap.masks()
    assert len(masks) == 28 * CFG.n_full_layers
    L = CFG.n_full_layers
    kept.append(np.stack([np.concatenate(
        [index_choices.sets_of(masks[j * L + i][0], TOPK)
         for j in range(28)]) for i in range(L)]))
    kept = np.concatenate(kept, axis=1)
    assert (kept[:, TOPK:, -1] >= 0).all()      # sixteen, no fewer
    got = np.stack(got)
    if cache == "float32":
        assert np.array_equal(kept, own)
        assert np.abs(got - want[28:]).max() < 2e-4 * scale
        return
    under, short = ref.forward_chosen(params, conf, jnp.asarray(toks),
                                      {"attn.index": kept})
    assert np.abs(got - np.asarray(under)[28:]).max() < 3e-2 * scale
    assert float(np.asarray(short).max()) <= server_child.CHOICE_TOL
    assert (kept == own).mean() > 0.9


def test_each_new_part_moves_the_logits(ref, params):
    """The tolerance above can tell: an indexer that keeps one position
    more, or every position, no rotation, pairs half-split, or no scaling of
    the gates lies far outside it."""
    toks = jnp.asarray(tokens(40, seed=15))
    conf = conf_of(CFG)
    want = np.asarray(ref.forward(params, conf, toks))
    scale = np.abs(want).max()
    for other in ({**conf, "index_topk": TOPK + 1},
                  {**conf, "index_topk": 64},
                  {**conf, "routed_scaling_factor": 1.0}):
        got = np.asarray(ref.forward(params, other, toks))
        assert np.abs(got - want).max() > 1e-2 * scale
    run = jax.jit(lambda p, t, cfg: decoder.prefill_chunk(p, cfg, t)[0],
                  static_argnums=2)
    for other in (dataclasses.replace(CFG, rope_interleave=False),
                  dataclasses.replace(CFG, index_topk=TOPK + 1),
                  dataclasses.replace(CFG, rope_theta=10000.0)):
        got = np.asarray(run(params, toks[None], other)[0])
        assert np.abs(got - want).max() > 1e-3 * scale
    assert np.abs(np.asarray(run(params, toks[None], CFG)[0]) - want
                  ).max() < 2e-4 * scale


@pytest.mark.parametrize("levels", [1, 2, 5, 0],
                         ids=["all-tied", "two-scores", "five-scores",
                              "distinct"])
def test_a_tie_on_the_last_place_goes_to_the_earlier_position(levels):
    """``_index_keep`` against the rule said plainly: sort a query's visible
    positions by (score down, position up), keep the first ``index_topk``.
    Scores of a few levels tie in crowds (the ReLU leaves many at 0); slots
    of every depth, one with fewer visible than are kept."""
    A, rng = 40, np.random.default_rng(7 + levels)
    score = (rng.integers(0, levels, (6, 3, A)) if levels
             else rng.standard_normal((6, 3, A))).astype(np.float32)
    q_pos = rng.integers(0, A, (6, 3))
    q_pos[0] = [TOPK - 2, TOPK - 1, TOPK]
    visible = np.arange(A)[None, None, :] <= q_pos[:, :, None]
    want = np.zeros_like(visible)
    for b, t in np.ndindex(6, 3):
        seen = np.flatnonzero(visible[b, t])
        order = seen[np.lexsort((seen, -score[b, t, seen]))]
        want[b, t, order[:TOPK]] = True
    got = np.asarray(jax.jit(lambda s, v: decoder._index_keep(CFG, s, v))(
        score, visible))
    assert np.array_equal(got, want)
    assert (got.sum(-1) == np.minimum(q_pos + 1, TOPK)).all()


@pytest.mark.parametrize("T", [12, 40])
def test_absorbed_is_expanded(params, T):
    """The two forms are one function: a fresh chunk's expanded attention,
    and the same chunk through the cache, absorbed, below ``index_topk``
    positions and past it, layer by layer."""
    ap = {k: v[1] for k, v in params["layers"].items()
          if k in decoder._ATTN_STACK}
    h = jax.random.normal(jax.random.PRNGKey(5), (2, T, CFG.dim))
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T))
    cos, sin = decoder.rope_angles(pos, CFG.qk_rope_dim, CFG.rope_theta)
    q_nope, q_rope, row, cq = decoder._latent_project(CFG, ap, h, cos, sin)
    qi, ki, w = decoder._index_project(CFG, ap, h, cq, cos, sin)
    a = decoder._latent_expanded(CFG, ap, q_nope, q_rope, row, qi, w, ki, pos)
    b = decoder._latent_absorbed(CFG, ap, q_nope, q_rope, row, qi, w, ki, pos)
    assert a.shape == (2, T, CFG.n_heads * CFG.v_head_dim)
    assert np.allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("B", [1, 2])
def test_query_blocks_are_the_whole_chunk(params, monkeypatch, B):
    """A long chunk's attention runs a block of queries at a time (the
    scores of four rows of 4,096 whole do not fit the chip): a fresh chunk
    and an extend in blocks of 16 are the chunk whole, kept sets and all."""
    toks = np.stack([tokens(64, seed=20 + b) for b in range(B)])
    run = lambda: (  # noqa: E731
        decoder.prefill_chunk(params, CFG, toks),
        decoder.forward_with_cache(
            params, CFG, toks[:, 8:56], *jax.tree_util.tree_map(
                lambda a: a[:, :B], cache), jnp.full((B,), 8, jnp.int32)))
    cache = empty_cache(2, 64)
    _, K, V = decoder.forward_with_cache(
        params, CFG, np.stack([tokens(8, seed=30), tokens(8, seed=31)]),
        *cache, jnp.zeros((2,), jnp.int32))
    cache = (K, V)
    whole = run()
    monkeypatch.setattr(decoder, "_LATENT_Q_BLOCK", 16)
    jaxpr = str(jax.make_jaxpr(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks))
    blocks = run()
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(blocks)):
        assert np.allclose(a, b, atol=2e-6)
    monkeypatch.undo()
    assert jaxpr.count("scan") > str(jax.make_jaxpr(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks)
    ).count("scan")


@pytest.mark.parametrize("pieces", [(44,), (20, 24), (5, 3, 36), (16, 16, 12),
                                    (1, 1, 42), (15, 1, 1, 27), (30, 14)])
def test_extends_that_cross_index_topk_equal_one_prefill(params, pieces):
    """One prefill, and the same prompt through extends of the cache, the
    pieces ending before, at and after the sixteenth position: rows, keys
    and the last logits agree."""
    toks = tokens(44, seed=1)
    want_l, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None])
    K, V = empty_cache(1, 64)
    at = 0
    for n in pieces:
        lg, K, V = decoder.forward_with_cache(
            params, CFG, toks[None, at:at + n], K, V,
            jnp.array([at], jnp.int32))
        at += n
    assert np.allclose(lg[0, -1], want_l[0, -1], atol=2e-6)
    assert np.allclose(K[:, :, :, :44], ks, atol=1e-5)
    assert np.allclose(V[:, :, :, :44], vs, atol=1e-5)
    assert not np.asarray(K[:, :, :, 44:]).any()


@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_a_latent_of_whole_lane_tiles_pads_its_key(cache):
    """Where the latent fills whole 128-lane tiles the cached row's rotated
    key is rounded up to whole ones, zeros behind it (``latent_row_pad``,
    read from the shape): the cache is wider, the mathematics the same:
    pieces through the padded cache are the one prefill, and the engine
    serves its stream."""
    cfg = dataclasses.replace(CFG, kv_latent_dim=128).validate()
    assert (cfg.latent_row_pad, cfg.cache_row_dims) == (120, (1, 256, 16))
    p = decoder.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    toks = tokens(44, seed=9)
    want, ks, _ = decoder.prefill_chunk(p, cfg, toks[None])
    assert ks.shape[-1] == 256 and not np.asarray(ks[..., 136:]).any()
    eng = Engine(cfg, p, ecfg=EngineConfig(
        max_slots=2, max_seq_len=64, cache_dtype=getattr(jnp, cache),
        decode_chunk=4, min_prefill_bucket=16))
    rows = jax.tree_util.tree_leaves(eng.k_cache)[0]
    assert rows.shape == (4, 2, 1, 64, 256)
    first = eng.admit(0, toks[:16], GREEDY)
    eng.release(0, park=True)
    got = eng.extend(0, toks, 16, GREEDY)
    assert got == int(jnp.argmax(want[0, -1])) or cache == "int8"
    assert first == int(jnp.argmax(want[0, 15])) or cache == "int8"
    rows = np.asarray(jax.tree_util.tree_leaves(eng.k_cache)[0])
    assert rows[:, 0, 0, :44, :136].any() and not rows[..., 136:].any()
    if cache == "float32":
        assert np.allclose(rows[:, 0, 0, :44], ks[:, 0, 0], atol=1e-5)
    eng.decode_n(4)
    assert not np.asarray(
        jax.tree_util.tree_leaves(eng.k_cache)[0])[..., 136:].any()


@pytest.mark.parametrize("n_valid", [1, 5, 16, 17, 31])
def test_padded_positions_never_reach_the_caches(params, n_valid):
    """A prefill bucket pads the prompt: the last real position's logits
    are the unpadded prompt's. An extend's padding writes nothing: the
    positions past ``n_valid`` keep their bits in both caches, codes and
    scales, whatever the padding holds."""
    toks = tokens(32, seed=2)
    f = jax.jit(lambda p, t, n: decoder.prefill_chunk(p, CFG, t, n_valid=n))
    lg, ks, vs = f(params, toks[None], jnp.int32(n_valid))
    lg0, ks0, vs0 = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, toks[None, :n_valid])
    assert np.allclose(lg[0, 0], lg0[0, -1], atol=2e-6)
    assert np.allclose(ks[:, :, :, :n_valid], ks0, atol=1e-6)
    assert np.allclose(vs[:, :, :, :n_valid], vs0, atol=1e-6)
    for cache in ("float32", "int8"):
        K, V = empty_cache(1, 64, cache)
        _, K, V = decoder.forward_with_cache(
            params, CFG, toks[None, :6], K, V, jnp.array([0], jnp.int32))
        g = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
            p, CFG, t, K, V, jnp.array([6], jnp.int32), n_valid=n))
        n = min(n_valid, 20)
        block, noise = toks[6:], toks[6:].copy()
        noise[n:] = (noise[n:] + 7) % CFG.vocab_size
        _, Ka, Va = g(params, block[None], K, V, jnp.array([n]))
        _, Kb, Vb = g(params, noise[None], K, V, jnp.array([n]))
        leaves = jax.tree_util.tree_leaves
        for a, b, was in zip(leaves((Ka, Va)), leaves((Kb, Vb)),
                             leaves((K, V))):
            assert np.array_equal(a, b)
            assert np.array_equal(a[..., 6 + n:, :] if a.ndim == 5
                                  else a[..., 6 + n:],
                                  was[..., 6 + n:, :] if a.ndim == 5
                                  else was[..., 6 + n:])
        assert not np.array_equal(leaves(Ka)[0], leaves(K)[0])


@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_a_decode_step_leaves_inactive_slots_caches_alone(params, cache):
    """Slot 0 decodes; slot 1 is parked between prefill pieces, slot 2 was
    released, slot 3 never held anything: their rows and indexer keys keep
    their bits through a whole chunk, codes and scales."""
    eng = make_engine(params, cache=getattr(jnp, cache))
    eng.admit(0, tokens(10), GREEDY)
    eng.admit(1, tokens(16, seed=6), GREEDY)
    eng.release(1, park=True)
    eng.admit(2, tokens(5, seed=7), GREEDY)
    eng.release(2)
    before = [rows_of(eng, s) for s in range(4)]
    eng.decode_n(4)
    after = [rows_of(eng, s) for s in range(4)]
    for s in (1, 2, 3):
        for a, b in zip(before[s], after[s]):
            assert np.array_equal(a, b), s
    assert not all(np.array_equal(a, b)
                   for a, b in zip(before[0], after[0]))
    # and the parked slot goes on as if nothing had happened in between
    t = eng.extend(1, tokens(30, seed=6), 16, GREEDY)
    fresh = make_engine(params, cache=getattr(jnp, cache))
    t_fresh = fresh.admit(1, tokens(30, seed=6), GREEDY)
    assert t == t_fresh or cache == "int8"


def test_admit_many_rows_keep_their_own_rows(params):
    """Batched admission: each row's prompt lands in its own slot."""
    eng = make_engine(params)
    a, b = tokens(9, seed=4), tokens(14, seed=5)
    eng.admit_many([0, 2], [a, b], [GREEDY, GREEDY])
    one = make_engine(params)
    one.admit(1, b, GREEDY)
    for x, y in zip(rows_of(eng, 2), rows_of(one, 1)):
        assert np.allclose(x[:, :, :14], y[:, :, :14], atol=1e-6)
    assert not np.allclose(rows_of(eng, 0)[0][:, :, :9],
                           rows_of(eng, 2)[0][:, :, :9], atol=1e-3)


def test_the_engine_serves_the_references_greedy_stream(ref, params):
    """admit + chunked decode through the engine's own programs, the
    indexer choosing from the prompt's seventeenth position on: the greedy
    stream is the reference's, token by token."""
    eng = make_engine(params)
    prompt = tokens(21, seed=3)
    got = [eng.admit(1, prompt, GREEDY)]
    for _ in range(6):
        got += [int(t) for t in eng.decode_n(4)[:, 1]]
    conf = conf_of(CFG)
    fwd = jax.jit(lambda p, t: ref.forward(p, conf, t))
    seq, want = np.zeros((48,), np.int32), []
    seq[:21] = prompt
    for n in range(21, 21 + len(got)):
        want.append(int(jnp.argmax(fwd(params, jnp.asarray(seq))[n - 1])))
        seq[n] = want[-1]
    assert got == want


def test_the_benchmarks_probe_passes_on_the_toy():
    """``server_child.probe`` as the cell runs it (both paths under their own
    router's sets, the decode step through the engine's own cache trees;
    the indexer's site is absent, so the reference takes its own top-k), on
    the CPU at the toy's sizes, with the selection live (64 positions, 16
    kept)."""
    conf = server_child.load_conf(CONF_PATH, True)
    cfg = server_child.model_config(conf, True)
    assert cfg.index_topk == 16 and conf["index_topk"] == 16
    assert cfg.kv_latent_dim == conf["kv_lora_rank"] == 32
    p = decoder.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=4, max_seq_len=128, decode_chunk=4,
                        cache_dtype=jnp.int8, paged=False,
                        min_prefill_bucket=16)
    assert server_child.probe(cfg, ecfg, p, conf, seed=7)
    said = server_child.COMPARED
    assert said["shortfall_served_vs_reference"]["value"] <= 0.08
    assert said["prefill_served_vs_reference"]["value"] < 0.03


def test_the_check_at_width_passes_on_the_toy(monkeypatch, tmp_path, capsys):
    """``benchmark/checks/sparse_at_width.py`` in rehearsal: the engine's own
    pieces across ``index_topk``, the decode steps, both sites' sets handed
    to the reference; the float8 control fails."""
    import importlib.util
    path = os.path.join(REPO, "benchmark", "checks", "sparse_at_width.py")
    spec = importlib.util.spec_from_file_location("sparse_at_width", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "BENCH", str(tmp_path / "benchmark"))
    os.makedirs(tmp_path / "benchmark")
    os.symlink(os.path.join(REPO, "benchmark", "configs"),
               tmp_path / "benchmark" / "configs")
    monkeypatch.setattr("sys.argv", [
        path, "--config", "glm-5", "--rehearse", "--prompt", "64", "--piece",
        "16", "--steps", "4", "--seeds", "1"])
    assert mod.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["passes"] == 1 and out["control_passes"] == 0
    assert out["logits_rel"][1] <= 0.03 and out["shortfall"][1] <= 0.08
    assert os.path.exists(tmp_path / "chiprun_out"
                          / "sparse_at_width.glm-5.json")


@pytest.mark.parametrize("program", ["prefill", "decode", "extend"])
def test_lowered_programs_carry_the_new_scope(params, program):
    """``attn.index`` around the indexer's projections, its key's write,
    its scores and the top-k, beside latent attention under the scopes
    there were and the expert scopes: what ``index_spans.py`` and
    ``trace_spans.py`` find."""
    from ollama_operator_tpu.runtime.trace import DEVICE_SCOPES
    if program == "prefill":
        low = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t)).lower(
            params, tokens(32)[None])
    else:
        T = 1 if program == "decode" else 4
        K, V = empty_cache(2, 32, "int8")
        low = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
            p, CFG, t, K, V, n, route_live=n)).lower(
            params, tokens(2 * T).reshape(2, T), K, V,
            jnp.array([3, 0], jnp.int32))
    text = low.as_text(debug_info=True)
    found = {s for s in DEVICE_SCOPES
             if re.search(r'[/"]' + re.escape(s) + r'[/"]', text)}
    assert found >= {"attn.index", "attn.qkv", "attn.core", "attn.out",
                     "mlp", "moe.route", "moe.experts", "lm_head", "embed"}
    assert ("attn.kv_write" in found) == (program != "prefill")
    assert "top_k" in text or "sort" in text
    assert not {s for s in found
                if s.startswith(("ssm.", "conv.", "delta."))
                or s == "attn.window"}


def test_the_stack_is_one_scan_a_span_and_no_branch(params):
    """Latent attention's leaves are stacked over the layers, the dense
    layer's scan and the routed layers' each get their own feed-forward,
    and a stack of attention alone traces no ``cond``."""
    lp = params["layers"]
    assert lp["wkv_a"].shape == (4, CFG.dim, 40)
    assert lp["w_uk"].shape == (4, CFG.n_heads, 16, 32)
    assert lp["w_uv"].shape == (4, CFG.n_heads, 32, 24)
    assert lp["we_gate"].shape[:2] == (3, CFG.experts_held)
    assert decoder.empty_state(CFG, 2) is None
    jaxpr = jax.make_jaxpr(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, tokens(8)[None])
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [1, 3]
    assert "cond" not in str(jaxpr)


# -- the chip's share -----------------------------------------------------

@pytest.mark.parametrize("who", ["program", "reference"])
@pytest.mark.parametrize("shares", [2, 16])
def test_the_shares_add_up_to_the_uncut_layer(ref, who, shares):
    """The toy's 16 experts in ``shares`` equal shares (16 shares of one
    expert: the deployment's count), each with the shared expert added
    whole: their sum, the shared expert counted once, is the uncut layer of
    the reference. Attention and the indexer are replicated, so they are
    counted once by construction: a share's attention is the layer's."""
    full = dataclasses.replace(CFG, n_experts_held=CFG.n_experts)
    p = decoder.init_params(full, jax.random.PRNGKey(2), dtype=jnp.float32)
    lp_all, i, r = p["layers"], 2, 1
    h = jax.random.normal(jax.random.PRNGKey(3), (11, CFG.dim), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.expert_layer(lp_all, conf_of(full), h, i, r)
    held = CFG.n_experts // shares

    def share(first):
        cfg = dataclasses.replace(CFG, n_experts_held=held,
                                  expert_first=first)
        cut = {k: (v[:, first:first + held]
                   if k in ("we_gate", "we_up", "we_down") else v)
               for k, v in lp_all.items()}
        if who == "reference":
            with jax.default_matmul_precision("highest"):
                return ref.expert_layer(cut, conf_of(cfg), h, i, r)[0]
        lp = {k: v[r] for k, v in cut.items()
              if v.shape[0] == CFG.n_routed_layers
              and k not in decoder._ATTN_STACK}
        u = decoder._norm(cfg, h[None], lp_all["mlp_norm_w"][i])
        return decoder._moe_mlp(cfg, lp, u)[0]

    u = np.asarray(decoder._norm(CFG, h, lp_all["mlp_norm_w"][i]))
    shared = (jax.nn.silu(u @ lp_all["we_sh_gate"][r])
              * (u @ lp_all["we_sh_up"][r])) @ lp_all["we_sh_down"][r]
    parts = [share(first) for first in range(0, CFG.n_experts, held)]
    got = sum(parts) - (shares - 1) * shared
    assert np.abs(np.asarray(got - want)).max() < 1e-5 * np.abs(want).max()
    assert np.abs(np.asarray(parts[0] - want)).max() > 1e-3 * np.abs(
        want).max()


# -- the scheduler ------------------------------------------------------

@pytest.fixture(scope="module")
def shared_engine(params):
    """One two-slot engine for the scheduler tests: its programs compile
    once; every test leaves its slots released."""
    return make_engine(params, slots=2)


@pytest.mark.parametrize("opts", [GREEDY, SEEDED], ids=["greedy", "seeded"])
def test_preempt_and_resume_give_the_uninterrupted_stream(shared_engine, opts):
    prompt = tokens(19, seed=8)
    want = uninterrupted(shared_engine, prompt, opts, 30)
    eng, sched = make_stack(shared_engine)
    manual(sched)
    try:
        r = sched.submit(prompt, opts, max_tokens=30)
        got = {r: []}
        for _ in range(3):
            sched._step()
        sched._drain_pending()
        got[r] += drain(r)
        assert 0 < len(got[r]) < 30
        sched._preempt_slot(r.slot, cause="test")
        run_to_end(sched, [r], got)
        assert sched.n_preemptions == 1
        assert got[r] == want
    finally:
        sched.shutdown()


def test_chunked_prefill_through_the_scheduler(shared_engine):
    """A prompt admitted in 16-token pieces (``index_topk`` each: every
    piece after the first chooses), decode dispatches of another stream in
    between: the one-shot stream."""
    long, short = tokens(50, seed=10), tokens(6, seed=11)
    want = uninterrupted(shared_engine, long, GREEDY, 8)
    eng, sched = make_stack(shared_engine, prefill_chunk=16)
    try:
        other = sched.submit(short, GREEDY, max_tokens=40)
        r = sched.submit(long, GREEDY, max_tokens=8)
        assert list(r.tokens()) == want
        list(other.tokens())
    finally:
        sched.shutdown()


def test_the_index_counter_counts_seen_and_kept(params):
    """``tpu_model_index_positions_total``: a chunk of 4 steps of a slot of
    n positions sees n + 1 .. n + 4 and keeps 16 of each past sixteen; from
    the host's lengths, the active slots alone."""
    from benchmark import prom
    eng = make_engine(params)
    eng.admit(0, tokens(10), GREEDY)
    eng.admit(1, tokens(30, seed=1), GREEDY)
    eng.admit(2, tokens(7, seed=2), GREEDY)
    eng.release(2, park=True)
    before = prom.parse(METRICS.render())
    eng.decode_n(4)
    after = prom.parse(METRICS.render())
    name = "tpu_model_index_positions_total"
    assert prom.delta(before, after, name, what="seen") == sum(
        range(11, 15)) + sum(range(31, 35))
    assert prom.delta(before, after, name, what="kept") == sum(
        range(11, 15)) + 4 * 16


# -- what the layout cannot do yet ------------------------------------------

@pytest.mark.parametrize("what, kw", [
    ("a page pool", dict(paged=True, page_size=16)),
    ("a mesh", dict(mesh=True)),
    ("the host tier", dict(env=("TPU_HOST_CACHE_GB", "1"))),
    ("export_request_kv", dict(call="export")),
])
def test_what_latent_rows_cannot_do_yet_is_refused_by_name(params, what, kw,
                                                           monkeypatch):
    """Each thing the latent rows have no form for yet raises, and the error
    names the layout."""
    kw = dict(kw)
    call, env, mesh = kw.pop("call", None), kw.pop("env", None), None
    if kw.pop("mesh", False):
        from ollama_operator_tpu.parallel import MeshPlan, make_mesh
        mesh = make_mesh(MeshPlan(dp=1, sp=1, tp=2))
    if env:
        monkeypatch.setenv(*env)
    with pytest.raises(ValueError, match="latent rows") as err:
        eng = Engine(CFG, params, mesh=mesh, ecfg=EngineConfig(
            max_slots=2, max_seq_len=64, cache_dtype=jnp.float32,
            decode_chunk=4, min_prefill_bucket=16, **kw))
        if call == "export":
            eng.export_request_kv(tokens(20))
        else:
            raise AssertionError("the engine was built")
    assert what.split(" (")[0] in str(err.value)


def test_the_rules_of_a_stack_with_layer_kinds_apply(shared_engine):
    """A parked prefix reused only whole: the rule every contiguous stack
    with ``layer_kinds`` has."""
    eng = shared_engine
    assert eng.recurrent
    eng.admit(0, tokens(20), GREEDY)
    eng.release(0, park=True)
    with pytest.raises(ValueError, match="cannot be cut back"):
        eng.extend(0, tokens(30), 12, GREEDY)
    eng.release(0)


# -- serving defaults, accounting, metrics ------------------------------

def test_zero_config_resolution_on_the_chip(monkeypatch):
    """bfloat16 weights, int8 contiguous cache, chunk 32 and the slots
    ``_recurrent_slots`` gives from the model alone: four tokens an expert
    a step at 8 of 256 kept would be 128, capped at 64; where k-exaone
    lands."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert englib.resolve_engine_dtype(BIG, "tpu") == "bfloat16"
    ecfg = englib.resolve_serving_defaults(
        EngineConfig(max_slots=0, max_seq_len=4096, decode_chunk=0,
                     paged=None, page_size=0, n_pages=None,
                     cache_dtype=jnp.int8), BIG, None)
    assert (ecfg.paged, ecfg.max_slots, ecfg.decode_chunk) == (False, 64, 32)
    assert BIG.window_ring_bytes == 0 and BIG.ssm_state_bytes == 0
    conf = work.load_conf(CONF_PATH)
    want = conf["expected_resolution"]
    assert (want["paged"], want["max_slots"], want["decode_chunk"]) == (
        ecfg.paged, ecfg.max_slots, ecfg.decode_chunk)
    assert (want["weights"], want["kv"]) == ("bfloat16", "int8")
    assert conf["saturating_clients"] == ecfg.max_slots


def test_accounting_prices_latent_attention():
    d = 6144
    moe = (8 * 16 / 256) * 6 * d * 2048 + 2 * d * 256 + 6 * d * 2048
    assert accounting.per_token_flops(BIG) == pytest.approx(
        7 * 2 * BIG.attn_params + 6 * d * 12288 + 6 * moe + 2 * d * 19360)
    assert accounting._layer_split(BIG) == (7, 0)
    # a kept position: a head's dot over the row and its sum over the
    # latent; every position before the query: the indexer's heads' dots
    pair, seen = 2.0 * 64 * (2 * 512 + 64), 2.0 * 32 * 128
    assert accounting.attn_span_flops(BIG, 999, 1) == 7 * 1000 * (pair + seen)
    assert accounting.attn_span_flops(BIG, 2999, 1) == 7 * (
        2048 * pair + 3000 * seen)
    # the stacks that were there keep their count
    exa = cfglib.PRESETS["k-exaone-236b-a23b"]
    assert exa.attn_params == 2 * 6144 * 8192 + 2 * 6144 * 1024


def test_cache_gauge_kinds(params, monkeypatch):
    monkeypatch.setenv("TPU_WARM_BUCKETS", "0")   # nothing is served here
    from ollama_operator_tpu.runtime.service import LoadedModel
    from ollama_operator_tpu.tokenizer.tokenizer import Tokenizer
    tok = Tokenizer("llama", [f"t{i}" for i in range(CFG.vocab_size)],
                    bos_id=1, eos_id=2)
    lm = LoadedModel("glm5", CFG, params, tok, ecfg=EngineConfig(
        max_slots=2, max_seq_len=64, cache_dtype=jnp.int8,
        min_prefill_bucket=16))
    try:
        want = {"full": 2 * 4 * 64 * (40 + 8), "window": 0, "state": 0,
                "index": 2 * 4 * 64 * (16 + 4)}
        assert lm.engine.cache_bytes == want
        assert lm.engine.kv_bytes == want["full"] + want["index"]
        text = METRICS.render().replace(".0", "")
        for kind, n in want.items():
            assert f'tpu_model_cache_bytes{{kind="{kind}"}} {n}' in text
        for what in ("seen", "kept"):
            assert (f'tpu_model_index_positions_total{{what="{what}"}}'
                    in text)
    finally:
        lm.unload()
    assert not re.search(r"^tpu_model_cache_bytes", METRICS.render(), re.M)


def test_the_served_cache_by_shapes_alone():
    """Nothing is allocated: a slot of the published model holds 7 layers x
    4,096 positions of a 576-byte row with two scales and a 128-byte key
    with one: 20.5 MB, where keys and values a head would be 7 x 4,096 x
    2 x 64 x (256 + 4) = 954 MB."""
    a_position = (576 + 8) + (128 + 4)
    assert 7 * 4096 * a_position == 20_529_152
    conf = work.load_conf(CONF_PATH)
    assert work.kv_bytes_per_token(conf, "int8") == 7 * 584


# -- the benchmark's readers and arithmetic ------------------------------

def reader_ctx(conf, before=None, after=None, live=None):
    return types.SimpleNamespace(
        conf=conf, notes={}, resolved={"decode_chunk": 2, "max_slots": 4,
                                       "weights": "bfloat16",
                                       "kv_dtype": "int8"},
        peaks={"hbm_bytes_per_s": 819e9}, trace_before=before or {},
        trace_after=after or {}, before=before or {}, after=after or {},
        live_tokens=live)


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_none_on_the_parents_program(name, tmp_path,
                                                    monkeypatch):
    """The driver runs the new readers on the parent's program too, which has
    neither the scope, the counter nor the gauge's kind: nothing to read is
    None, no error."""
    from benchmark import prom, run, trace_spans
    from ollama_operator_tpu.server.metrics import Metrics
    monkeypatch.setattr(trace_spans.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    reg = Metrics()
    for kind, n in (("full", 64e6), ("window", 8e6), ("state", 0.0)):
        reg.gauge_fn("tpu_model_cache_bytes", lambda n=n: n,
                     f'{{kind="{kind}"}}')
    scrape = prom.parse(reg.render())
    assert run.layer_reader(name).read(reader_ctx(
        work.load_conf(CONF_PATH), scrape, scrape, live=1000.0)) is None


def test_index_spans_and_the_roofline_read_a_trace(tmp_path, monkeypatch):
    """Two complete runs of a decode module of two steps each: self time
    under ``attn.index`` over the steps, and the indexer's bytes over it;
    latent attention's ``attn.core`` is not among it; a trace without the
    scope reads None."""
    from benchmark import index_spans, prom, run, trace_spans
    from ollama_operator_tpu.server.metrics import Metrics
    meta = {1: ("jit__decode_n(7)", ""),
            2: ("%fusion.1 = f32[] fusion()",
                "jit(_decode_n)/attn.index/dot_general"),
            3: ("%fusion.2 = f32[] fusion()",
                "jit(_decode_n)/attn.index/top_k"),
            4: ("%fusion.3 = f32[] fusion()",
                "jit(_decode_n)/attn.core/dot_general")}

    def planes(with_index):
        ops = []
        for t0 in (0, 2000):
            ops += [(t0 + 100, t0 + 400, 2 if with_index else 4),
                    (t0 + 400, t0 + 600, 3 if with_index else 4),
                    (t0 + 600, t0 + 900, 4)]
        return [{"name": "/device:TPU:0", "meta": meta, "lines": [
            {"name": "XLA Modules", "events": [(0, 1000, 1), (2000, 3000, 1)]},
            {"name": "XLA Ops", "events": ops}]}]

    reg = Metrics()
    before = prom.parse(reg.render())
    for _ in range(4):
        reg.observe("tpu_model_dispatch_seconds", 0.01, '{kind="decode"}')
    reg.inc("tpu_model_useful_tokens_total", 24.0, '{kind="decode"}')
    after = prom.parse(reg.render())
    conf = work.load_conf(CONF_PATH)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    for with_index in (True, False):
        index_spans._CACHE.clear()
        pl = planes(with_index)
        monkeypatch.setattr(trace_spans, "find_trace", lambda w=None: str(path))
        monkeypatch.setattr(trace_spans, "reduce",
                            lambda w=None, pl=pl: trace_spans.reduce_planes(pl))
        monkeypatch.setattr(trace_spans, "read_planes", lambda p, pl=pl: pl)
        got = index_spans.step_seconds(2)
        ctx = reader_ctx(conf, before, after, live=3000.0)
        ms = run.layer_reader("decode_index_ms_per_step").read(ctx)
        share = run.layer_reader("index_select_roofline").read(ctx)
        if with_index:
            assert got == pytest.approx({"attn.index": 250e-12})
            assert ms == pytest.approx(250e-9)
            nbytes = 7 * (9_371_648 * 2 + (3000 + 3) * 132)
            assert ctx.notes["index_select_roofline"][
                "index_bytes"] == nbytes
            assert share == pytest.approx(100 * nbytes / 819e9 / 250e-12)
        else:
            assert got is None and ms is None and share is None


def test_the_counter_and_gauge_readers_read_a_scrape():
    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    before = prom.parse(reg.render())
    reg.inc("tpu_model_index_positions_total", 1000.0, '{what="seen"}')
    reg.inc("tpu_model_index_positions_total", 940.0, '{what="kept"}')
    for kind, n in (("full", 64e6), ("index", 16e6)):
        reg.gauge_fn("tpu_model_cache_bytes", lambda n=n: n,
                     f'{{kind="{kind}"}}')
    ctx = reader_ctx({}, before, prom.parse(reg.render()))
    assert run.layer_reader("index_kept_share").read(ctx) == 94.0
    assert run.layer_reader("index_cache_mb_per_slot").read(ctx) == 4.0
    assert run.layer_reader("kv_cache_mb_per_slot").read(ctx) == 16.0


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    """One configuration, one cell under the new mix, the four new metrics
    on it alone, and the accepted expert and cache metrics extended to it."""
    from benchmark import run, traffic_gen
    cell = run.find_cell(CELL)
    assert (cell.chips, cell.mix_name) == (1, "decode-deep")
    assert cell.conf["preset"] == "glm-5"
    assert cell.mix["clients"] == "saturating_clients"
    assert cell.conf["saturating_clients"] == 64
    # the mix is the issue's, parameter for parameter
    assert cell.mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 1.2, "lo": 64,
        "hi": 2816}
    assert cell.mix["output_tokens"] == {"dist": "uniform", "lo": 512,
                                         "hi": 896}
    assert (cell.mix["kind"], cell.mix["sharing"], cell.mix["pool"],
            cell.mix["block"], cell.mix["trace_seconds"]) == (
        "closed", "none", 4096, 32, 5)
    # a block's longest prompts, and the contexts that pass index_topk
    block = sorted(traffic_gen.quantiles(cell.mix["prompt_tokens"], 32))
    assert block[-4:] == [1120, 1403, 1913, 2816] and block[15] < 256 < block[16]
    reqs = traffic_gen.make_requests(cell.mix, 7, max_seq_len=4096,
                                     seconds=51.0)
    deep = [r for r in reqs
            if r.prompt_tokens + r.output_tokens > cell.conf["index_topk"]]
    assert 0.05 < len(deep) / len(reqs) < 0.25
    assert max(r.prompt_tokens + r.output_tokens for r in reqs) < 4096 - 16
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) | {
        "decode_moe_ms_per_step", "moe_experts_roofline",
        "moe_expert_load_spread", "pass_filled_share", "late_launch_share",
        "decode_kv_write_ms_per_step", "kv_cache_mb_per_slot",
        "paged_attn_roofline", "decode_attn_ms_per_step"} <= names
    assert not {"decode_ssm_ms_per_step", "decode_window_attn_ms_per_step",
                "decode_delta_ms_per_step", "state_mb_per_slot"} & names
    for other in ("k-exaone-236b-a23b.decode-long",
                  "olmo-hybrid-7b.decode-saturated",
                  "starcoder2-3b.decode-saturated"):
        assert not set(NEW_READERS) & {
            m["name"] for m in run.find_cell(other).per_layer}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended in PR 46's turn: what PR 50 added stands behind them
    assert bench["configs"][6]["name"] == "glm-5"
    assert bench["workloads"][6]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][47:51]] == list(NEW_READERS)


def test_the_configurations_work_arithmetic():
    """The sizes the issue reckons with, from the configuration's own file."""
    conf = work.load_conf(CONF_PATH)
    w = work.load_module(os.path.join(conf["_dir"], conf["work"]))
    assert w.n_routed(conf) == 6
    assert w.attention_params(conf) == 165_019_648
    assert w.index_params(conf) == 9_371_648
    assert w.expert_params(conf) == w.shared_params(conf) == 37_748_736
    assert w.dense_params(conf) == 226_492_416
    assert w.router_params(conf) == 1_572_864
    assert w.attention_params(conf) + w.index_params(conf) == BIG.attn_params
    # a row and its two scales, a key and its one
    assert (w.row_bytes(conf, "int8"), w.index_key_bytes(conf, "int8")) == (
        584, 132)
    assert work.kv_bytes_per_token(conf, "int8") == 7 * 584
    assert work.attn_flops_per_pair(conf) == 7 * 64 * (576 + 512) * 2
    # 64 tokens of 8 picks over 256 experts touch 86.9% of the 16 held
    assert w.distinct_experts(conf, 64) == pytest.approx(
        16 * (1 - (1 - 8 / 256) ** 64))
    assert w.experts_bytes_step(conf, 1e9, "bfloat16") == pytest.approx(
        6 * 16 * 37_748_736 * 2)
    assert work.weight_bytes_step(conf, 1e9, "bfloat16") == pytest.approx(
        2 * (BIG.n_params - 19360 * 6144), rel=1e-9)  # the embedding: a lookup
    # the issue's step: 3.60 GB of fixed weights, 6.3 GB of experts at 64
    assert 3.59e9 < 2 * w.fixed_params(conf) < 3.61e9
    assert 6.2e9 < w.experts_bytes_step(conf, 64, "bfloat16") < 6.4e9
    # a token keeps 8 of 256 and one in sixteen of those is held here
    assert work.matmul_flops_per_token(conf) == pytest.approx(
        2 * (w.fixed_params(conf) + 6 * 0.5 * w.expert_params(conf)))
    assert w.index_bytes_step(conf, 60, 60000, "bfloat16", "int8") == 7 * (
        9_371_648 * 2 + 60060 * 132)
