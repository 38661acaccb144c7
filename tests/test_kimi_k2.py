"""Latent attention WITHOUT an indexer, under YaRN (kimi_k2): a cache of one
row [latent | rotated key] a position and nothing where values ride,
attention over every earlier position, rotary at YaRN's scaled frequencies
with the DeepSeek-V3 convention's two magnitudes (cos / sin as they are, the
scores scaled), decoded in absorbed form, beside glm-5's routed feed-forward.
CPU, the toy of the same shape (``tiny-kimi-k2``: four shares of a 16-wide
router, YaRN over 32 original positions), seeded weights; the plain reference
is the benchmark's (``benchmark/configs/kimi-k2.7-code.reference.py``: expanded
keys and values, no cache, YaRN by its own arithmetic), read at the toy's sizes
through the configuration file's own ``holds``."""

import dataclasses
import json
import math
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
from ollama_operator_tpu.ops import rope
from ollama_operator_tpu.ops.attention import record_kernels
from ollama_operator_tpu.ops.pallas import latent as LK
from ollama_operator_tpu.runtime import accounting
from ollama_operator_tpu.runtime import engine as englib
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

from benchmark import server_child, work
from test_hybrid import make_stack, uninterrupted

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONF_PATH = os.path.join(REPO, "benchmark", "configs", "kimi-k2.7-code.json")
CELL = "kimi-k2.7-code.decode-deep"
CFG = cfglib.PRESETS["tiny-kimi-k2"]
BIG = cfglib.PRESETS["kimi-k2.7-code"]
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
NEW_READERS = ("latent_attn_roofline", "latent_live_share")
# the file's restated rotary keys, and the nested group the reference reads
ROPE_KEYS = (("factor", "rope_factor"),
             ("original_max_position_embeddings",
              "rope_original_max_position_embeddings"),
             ("beta_fast", "rope_beta_fast"), ("beta_slow", "rope_beta_slow"),
             ("mscale", "rope_mscale"),
             ("mscale_all_dim", "rope_mscale_all_dim"))


def conf_of(cfg):
    """The configuration file's dict at ``cfg``'s sizes: each key the file
    holds the preset to, read back from the config, and the nested
    ``rope_scaling`` group rebuilt from its restated keys."""
    conf = work.load_conf(CONF_PATH)
    for ours, theirs in conf["holds"]:
        conf[theirs] = getattr(cfg, ours)
    conf["rope_scaling"] = {"type": conf["rope_scaling_type"],
                            **{k: conf[top] for k, top in ROPE_KEYS}}
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


def make_engine(params, slots=4, cache=jnp.float32, cfg=CFG, **kw):
    return Engine(cfg, params, ecfg=EngineConfig(
        max_slots=slots, max_seq_len=128, cache_dtype=cache, decode_chunk=4,
        min_prefill_bucket=16, **kw))


def rows_of(eng, slot):
    """Every leaf of one slot's cache (codes and scales where it is int8),
    as host arrays."""
    return [np.asarray(a[:, slot]) for a in
            jax.tree_util.tree_leaves((eng.k_cache, eng.v_cache))]


def empty_rows(B, S, cache="float32", cfg=CFG):
    """The rows of ``B`` empty slots of ``S`` positions."""
    La = cfg.n_full_layers
    _, kd, _ = cfg.cache_row_dims
    if cache == "int8":
        kc = QC.empty_cache(La, B, 1, S, kd)
        kc["s"] = jnp.zeros((La, B, 2, S), jnp.float32)
        return kc
    return jnp.zeros((La, B, 1, S, kd), getattr(jnp, cache))


def yarn_by_hand(d, theta, factor, orig, beta_fast, beta_slow):
    """ISSUE 53's formulas, written out: the pairs' frequencies."""
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)

    def corr(n):
        return d * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), d - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp), low, high


# -- the configuration ---------------------------------------------------

def test_preset_is_the_published_shape():
    """The served preset against the configuration's file, key by key (the
    benchmark's own check), every width against the catalog's row, the cut's
    floors and the issue's arithmetic."""
    conf = server_child.load_conf(CONF_PATH, False)
    cfg = server_child.model_config(conf, False)
    assert cfg is BIG and cfg.layer_kinds == "A" * 8
    assert (cfg.n_full_layers, cfg.n_dense_layers, cfg.n_routed_layers) == (
        8, 1, 7)
    # one row a position, its rotated key rounded up to a whole lane tile
    # where the latent fills whole ones, and NO second row
    assert cfg.cache_row_dims == (1, 576 + 64, 0)
    assert CFG.cache_row_dims == (1, 40, 0)
    assert (cfg.dim, cfg.n_heads, cfg.q_latent_dim, cfg.kv_latent_dim) == (
        7168, 64, 1536, 512)
    assert (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (
        128, 64, 128)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (0, 0, 0)
    assert (cfg.dense_ffn_dim, cfg.ffn_dim, cfg.n_shared_ffn) == (
        18432, 2048, 2048)
    assert (cfg.n_experts, cfg.n_experts_used, cfg.experts_held,
            cfg.moe_scale) == (384, 8, 12, 2.827)
    assert cfg.rope_interleave and not cfg.tie_embeddings
    rs = conf["rope_scaling"]
    assert (cfg.rope_scaling_type, cfg.rope_scaling, cfg.rope_orig_ctx,
            cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow,
            cfg.rope_yarn_mscale, cfg.rope_yarn_mscale_all_dim,
            cfg.rope_theta) == (
        rs["type"], rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"],
        conf["rope_theta"])
    # the restated keys are the nested group's
    assert all(conf[top] == rs[k] for k, top in ROPE_KEYS)
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-K2.7-Code")
        assert row["source_url"] == conf["source"]
        differ = {k for k, v in row["config"].items() if conf.get(k) != v}
        assert differ == set(conf["reduced"])
    # the floors of a cut, and its reasons
    assert cfg.n_routed_layers >= 4 and cfg.experts_held >= 8
    assert cfg.vocab_size * 8 == conf["published"]["vocab_size"]
    assert conf["published"]["num_hidden_layers"] == 61
    assert set(conf["reduced"]) == set(conf["reduced_why"])
    held = {ours for ours, _ in conf["holds"]}
    assert {"kv_latent_dim", "q_latent_dim", "qk_nope_dim", "qk_rope_dim",
            "v_head_dim", "index_heads", "index_head_dim", "index_topk",
            "rope_scaling_type", "rope_scaling", "rope_orig_ctx",
            "rope_yarn_mscale", "rope_yarn_mscale_all_dim"} <= held
    # the issue's count: attention 101.12M, an expert 44.04M, 12 held, the
    # shared expert, the router; the dense layer; the held rows twice
    attn = (7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384
            + 8192 * 7168)
    expert = 3 * 7168 * 2048
    assert (attn, expert) == (101_122_048, 44_040_192)
    assert cfg.attn_params == attn
    assert cfg.n_params == (8 * attn + 3 * 7168 * 18432
                            + 7 * (13 * expert + 7168 * 384)
                            + 2 * 20480 * 7168)
    assert 11.04e9 < 2 * cfg.n_params < 11.06e9


def test_n_params_counts_what_init_params_makes():
    """The sizing formula against the leaves themselves, and no leaf of an
    indexer among them."""
    shapes = jax.eval_shape(
        lambda k: decoder.init_params(CFG, k, dtype=jnp.float32),
        jax.random.key(0))
    matrices = sum(math.prod(a.shape) for name, a in (
        list(shapes["layers"].items()) + [(k, v) for k, v in shapes.items()
                                          if k != "layers"])
        if not name.endswith(("norm_w", "norm_b", "router_bias")))
    assert matrices == CFG.n_params
    assert not [k for k in shapes["layers"] if k.startswith("idx_")]
    assert {"wq_a", "wq_b", "wkv_a", "w_uk", "w_uv", "wo"} <= set(
        shapes["layers"])


def test_validate_accepts_and_refuses():
    """Latent attention with an indexer or without one, at rope_theta alone
    or under yarn; each refusal names what it refused."""
    for bad, msg in (
            (dict(index_topk=16), "or there is none"),
            (dict(index_heads=4, index_head_dim=16), "or there is none"),
            (dict(rope_scaling_type="linear", rope_yarn_mscale=0.0,
                  rope_yarn_mscale_all_dim=0.0), "under yarn"),
            (dict(rope_scaling_type="llama3", rope_yarn_mscale=0.0,
                  rope_yarn_mscale_all_dim=0.0), "under yarn"),
            (dict(rope_scaling_type="none", rope_scaling=1.0),
             "mscale and mscale_all_dim are yarn's"),
            (dict(rope_orig_ctx=0), "requires rope_orig_ctx"),
            (dict(rope=False), "rope_theta alone"),
            (dict(q_latent_dim=0), "needs q_latent_dim"),
            (dict(layer_kinds="AAwA", sliding_window=8), "stack of its own"),
            (dict(layer_kinds=""), "belongs to latent attention")):
        with pytest.raises(AssertionError, match=msg):
            dataclasses.replace(CFG, **bad).validate()
    # the indexer whole, beside yarn: nothing forbids the pair
    dataclasses.replace(CFG, index_heads=4, index_head_dim=16,
                        index_topk=16).validate()
    # yarn in the Llama convention on a latent stack
    dataclasses.replace(CFG, rope_yarn_mscale=0.0,
                        rope_yarn_mscale_all_dim=0.0).validate()
    with pytest.raises(AssertionError, match="yarn's"):
        dataclasses.replace(cfglib.PRESETS["tiny"],
                            rope_yarn_mscale=1.0).validate()


# -- YaRN -------------------------------------------------------------------

def test_yarn_frequencies_and_magnitudes_at_the_published_sizes():
    """``inv_freq`` of the 32 pairs, the factor on cos / sin and the softmax
    scale of the served preset against ISSUE 53's formulas written out."""
    want, low, high = yarn_by_hand(64, 50000.0, 64.0, 4096, 32.0, 1.0)
    assert (low, high) == (8, 20)
    inv, mag = rope.scaled_inv_freq(
        64, BIG.rope_theta, scaling_type="yarn", factor=BIG.rope_scaling,
        orig_ctx=BIG.rope_orig_ctx, beta_fast=32.0, beta_slow=1.0,
        yarn_mscale=1.0, yarn_mscale_all_dim=1.0)
    assert np.allclose(inv, want, rtol=1e-6, atol=0)
    # the first pairs keep their frequency, the last turn 64 times slower
    assert np.allclose(inv[:9], 50000.0 ** (-np.arange(9) / 32), rtol=1e-6)
    assert np.allclose(inv[20:], 50000.0 ** (-np.arange(20, 32) / 32) / 64,
                       rtol=1e-6)
    assert mag == 1.0
    m = 0.1 * math.log(64.0) + 1.0
    assert rope.yarn_softmax_factor(BIG) == pytest.approx(m * m, rel=1e-12)
    assert m * m == pytest.approx(2.00474, abs=1e-5)
    assert decoder._latent_scale(BIG) == pytest.approx(
        2.00474 * 192 ** -0.5, rel=1e-5)
    # cos / sin come out at magnitude 1: cos^2 + sin^2 of every pair
    pos = jnp.array([[0, 5, 4095, 4096, 100000]], jnp.int32)
    cos, sin = decoder._latent_rope(BIG, pos)
    assert cos.shape == (1, 5, 32)
    assert np.allclose(np.asarray(cos) ** 2 + np.asarray(sin) ** 2, 1.0,
                       atol=1e-6)
    assert np.allclose(np.asarray(cos)[0, 1], np.cos(5 * want), atol=1e-6)


def test_the_llama_convention_puts_the_magnitude_on_cos_and_sin():
    """The same frequencies with the two fields at their defaults: cos / sin
    times 0.1 ln(factor) + 1 and the softmax scale untouched; with unlike
    magnitudes their ratio on cos / sin."""
    plain = dataclasses.replace(CFG, rope_yarn_mscale=0.0,
                                rope_yarn_mscale_all_dim=0.0)
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    c0, s0 = decoder._latent_rope(plain, pos)
    c1, s1 = decoder._latent_rope(CFG, pos)
    m = 0.1 * math.log(CFG.rope_scaling) + 1.0
    assert np.allclose(c0, np.asarray(c1) * m, atol=1e-6)
    assert np.allclose(s0, np.asarray(s1) * m, atol=1e-6)
    assert rope.yarn_softmax_factor(plain) == 1.0
    assert decoder._latent_scale(plain) == 24 ** -0.5
    assert decoder._latent_scale(CFG) == pytest.approx(m * m * 24 ** -0.5)
    unlike = dataclasses.replace(CFG, rope_yarn_mscale=0.707,
                                 rope_yarn_mscale_all_dim=1.0)
    c2, _ = decoder._latent_rope(unlike, pos)
    ratio = (0.0707 * math.log(8.0) + 1.0) / (0.1 * math.log(8.0) + 1.0)
    assert np.allclose(c2, np.asarray(c1) * ratio, atol=1e-6)


def test_the_toys_yarn_is_live_and_the_reference_computes_it_itself(ref):
    """The toy's four pairs: the first keeps its frequency, the others turn
    8 times slower; the reference's own arithmetic gives the program's."""
    conf = conf_of(CFG)
    inv, mag, soft = ref.yarn(conf)
    want, low, high = yarn_by_hand(8, 50000.0, 8.0, 32, 32.0, 1.0)
    assert (low, high) == (0, 1)
    assert np.allclose(inv, want, rtol=1e-6)
    assert want[0] == 1.0 and np.allclose(want[1:] * 8, 50000.0 ** (
        -np.arange(1, 4) / 4))
    ours, mscale = rope.scaled_inv_freq(
        8, 50000.0, scaling_type="yarn", factor=8.0, orig_ctx=32,
        yarn_mscale=1.0, yarn_mscale_all_dim=1.0)
    assert np.allclose(ours, inv, rtol=1e-6) and mscale == mag == 1.0
    assert soft == pytest.approx(rope.yarn_softmax_factor(CFG))
    # and the published group, from the file as it stands
    inv, mag, soft = ref.yarn(work.load_conf(CONF_PATH))
    assert inv.shape == (32,) and mag == 1.0
    assert soft == pytest.approx(2.00474, abs=1e-5)


# -- the model against the reference -----------------------------------

@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_prefill_then_decode_through_the_cache(ref, params, cache):
    """Prefill 28 positions, then 28 decode steps through the cache,
    absorbed, past the toy's 32 original positions: each position's logits
    against the reference's full forward pass with expanded keys and values.
    Float32 on both sides differs by the order of sums; an int8 row carries
    1/254 of its parts' largest entries."""
    toks = tokens(56)
    conf = conf_of(CFG)
    want = np.asarray(ref.forward(params, conf, jnp.asarray(toks)))
    scale = np.abs(want).max()
    logits, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None, :28])
    assert ks.shape == (CFG.n_full_layers, 1, 1, 28, 40) and vs is None
    assert np.abs(np.asarray(logits[0]) - want[:28]).max() < 2e-4 * scale
    K = empty_rows(1, 64, cache)
    if cache == "int8":
        q, s = QC.quantize_latent(ks, CFG.kv_latent_dim)
        K = {"q": K["q"].at[:, :, :, :28].set(q),
             "s": K["s"].at[:, :, :, :28].set(
                 jnp.moveaxis(s[:, :, 0], -1, 2))}
    else:
        K = K.at[:, :, :, :28].set(ks)
    step = jax.jit(lambda p, t, K, n: decoder.forward_with_cache(
        p, CFG, t, K, None, n))
    got = []
    for i in range(28, 56):
        lg, K, V = step(params, toks[None, i:i + 1], K,
                        jnp.array([i], jnp.int32))
        assert V is None
        got.append(np.asarray(lg[0, 0]))
    tol = 2e-4 if cache == "float32" else 3e-2
    assert np.abs(np.stack(got) - want[28:]).max() < tol * scale


def test_each_new_part_moves_the_logits(ref, params):
    """The reference with YaRN's frequencies, its softmax factor or the
    router's scaling taken out differs from the model: the agreement above
    is not vacuous."""
    toks = jnp.asarray(tokens(48))
    conf = conf_of(CFG)
    want = np.asarray(ref.forward(params, conf, toks))
    scale = np.abs(want).max()
    for change in (
            dict(rope_scaling=dict(conf["rope_scaling"], factor=1.0)),
            dict(rope_scaling=dict(conf["rope_scaling"], mscale_all_dim=0.0,
                                   mscale=0.0)),
            dict(routed_scaling_factor=1.0)):
        other = np.asarray(ref.forward(params, {**conf, **change}, toks))
        assert np.abs(other - want).max() > 1e-3 * scale, change


@pytest.mark.parametrize("T", [12, 40])
def test_absorbed_is_expanded(params, T):
    """The two forms are one function: a fresh chunk's expanded attention,
    and the same chunk through the cache, absorbed, with no indexer's
    arrays on either side."""
    ap = {k: v[1] for k, v in params["layers"].items()
          if k in decoder._ATTN_STACK}
    h = jax.random.normal(jax.random.PRNGKey(5), (2, T, CFG.dim))
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T))
    cos, sin = decoder._latent_rope(CFG, pos)
    q_nope, q_rope, row, _ = decoder._latent_project(CFG, ap, h, cos, sin)
    a = decoder._latent_expanded(CFG, ap, q_nope, q_rope, row, None, None,
                                 None, pos)
    b = decoder._latent_absorbed(CFG, ap, q_nope, q_rope, row, None, None,
                                 None, pos)
    assert a.shape == (2, T, CFG.n_heads * CFG.v_head_dim)
    assert np.allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("B", [1, 2])
def test_query_blocks_are_the_whole_chunk(params, monkeypatch, B):
    """A long chunk's attention runs a block of queries at a time, the
    indexer's arrays absent from the blocks: a fresh chunk and an extend in
    blocks of 16 are the chunk whole."""
    toks = np.stack([tokens(64, seed=20 + b) for b in range(B)])
    _, K, _ = decoder.forward_with_cache(
        params, CFG, np.stack([tokens(8, seed=30 + b) for b in range(B)]),
        empty_rows(B, 64), None, jnp.zeros((B,), jnp.int32))
    run = lambda: (  # noqa: E731
        decoder.prefill_chunk(params, CFG, toks),
        decoder.forward_with_cache(params, CFG, toks[:, 8:56], K, None,
                                   jnp.full((B,), 8, jnp.int32)))
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


# a toy whose heads are the published ones' size: 128 un-rotated channels,
# values 128, a latent of one whole lane tile; the row stays 128 + 8 + pad
WIDE = dataclasses.replace(CFG, qk_nope_dim=128, v_head_dim=128,
                           kv_latent_dim=128, n_layers=2, layer_kinds="AA",
                           n_dense_layers=1).validate()
RAGGED = ([0, 5, 31, 32, 47, 20], [1, 1, 1, 1, 1, 0])


@pytest.mark.parametrize("cache", ["int8", "float32"])
def test_expanded_absorbed_and_the_kernel_agree_at_the_published_head_sizes(
        monkeypatch, cache):
    """``_latent_cached`` at T == 1, heads of 128 + 8 query channels and
    values of 128: the kernel (interpret mode, blocks of 16, NO keep mask at
    any depth) against the einsum form of the same call, and both against
    the expanded form over the whole sequence (int8: the rotated key's
    second code rides in the row's padding); ragged lengths on both sides
    of a block's edge and of YaRN's original context, a slot not live."""
    monkeypatch.setattr(LK, "_BLOCK_ROWS", 16)
    S, A, row_i = 64, 64, 1
    C = WIDE.kv_latent_dim
    p = decoder.init_params(WIDE, jax.random.PRNGKey(4), dtype=jnp.float32)
    ap = {k: v[row_i] for k, v in p["layers"].items()
          if k in decoder._ATTN_STACK}
    lengths, nv = (jnp.asarray(x, jnp.int32) for x in RAGGED)
    B = lengths.shape[0]
    rng = np.random.default_rng(11)
    hs = jnp.asarray(rng.normal(size=(B, S, WIDE.dim)), jnp.float32)
    # every slot's earlier positions through the layer's own projection
    pos_all = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cos, sin = decoder._latent_rope(WIDE, pos_all)
    qn, qr, rows, _ = decoder._latent_project(WIDE, ap, hs, cos, sin)
    assert qn.shape[-1] == 128 and WIDE.cache_row_dims == (1, C + 128, 0)
    want = decoder._latent_expanded(WIDE, ap, qn, qr, rows, None, None, None,
                                    pos_all)
    want = np.stack([np.asarray(want)[b, int(lengths[b])] for b in range(B)])
    live = jnp.arange(S)[None, :] < lengths[:, None]
    rows = jnp.where(live[..., None], rows, 0.0)
    rows = jnp.pad(rows, [(0, 0), (0, 0), (0, WIDE.latent_row_pad)])
    kc = jnp.zeros((2, B, 1, S, rows.shape[-1]), jnp.float32
                   ).at[row_i, :, 0].set(rows)
    if cache == "int8":
        assert WIDE.latent_key_residual == 8
        q, s = QC.quantize_latent(kc[:, :, 0], C, WIDE.latent_key_residual)
        assert s.shape[-1] == 2 and q.shape == kc[:, :, 0].shape
        kc = {"q": q[:, :, None], "s": jnp.moveaxis(s, -1, -2)}
    h1 = jnp.stack([hs[b, int(lengths[b])] for b in range(B)])[:, None]
    pos = lengths[:, None]
    cos, sin = decoder._latent_rope(WIDE, pos)

    def layer(cfg):
        fn = jax.jit(lambda ap, h, kc, nv: decoder._latent_cached(
            cfg, ap, h, kc, None, jnp.int32(row_i), pos, nv, A, cos, sin))
        with record_kernels() as picked:
            out, kc1, vc1 = fn(ap, h1, kc, nv)
        assert vc1 is None
        return np.asarray(out)[:, 0], kc1, sorted(picked)

    got_e, kc_e, said_e = layer(WIDE)
    got_k, kc_k, said_k = layer(dataclasses.replace(WIDE,
                                                    kernels="interpret"))
    assert said_e == [("decode", "einsum", False)]
    assert said_k == [("decode", "latent_decode", False)]
    m = np.asarray(nv, bool)
    top = np.abs(want[m]).max()
    assert np.abs(got_k[m] - got_e[m]).max() < 2e-5 * top
    for a, b in zip(jax.tree_util.tree_leaves(kc_e),
                    jax.tree_util.tree_leaves(kc_k)):
        assert np.array_equal(a, b)
    # the projection out of the heads' sums: compare before it, head by head
    want_out = np.asarray(decoder._proj_out(
        WIDE, ap, jnp.asarray(want)[:, None], B, 1))[:, 0]
    tol = 2e-5 if cache == "float32" else 1.5e-2
    assert np.abs(got_e[m] - want_out[m]).max() < tol * np.abs(
        want_out[m]).max()
    assert np.abs(got_k[m] - want_out[m]).max() < tol * np.abs(
        want_out[m]).max()


def test_the_keys_second_code_is_read_from_the_widths():
    """A scaled-up softmax and room in the row's padding: the rotated key
    keeps a second code there. Every other latent row stays one code a
    channel; the row's bytes and its two scales are what they were."""
    assert BIG.latent_key_residual == 64 and WIDE.latent_key_residual == 8
    assert CFG.latent_key_residual == 0              # no padding to use
    assert cfglib.PRESETS["glm-5"].latent_key_residual == 0
    llama = dataclasses.replace(BIG, rope_yarn_mscale=0.0,
                                rope_yarn_mscale_all_dim=0.0).validate()
    assert rope.yarn_softmax_factor(llama) == 1.0
    assert llama.latent_key_residual == 0
    assert BIG.cache_row_dims == (1, 512 + 64 + 64, 0)


@pytest.mark.parametrize("residual", [0, 64])
def test_quantize_latent_with_and_without_the_second_code(residual):
    """``quantize_latent`` at the published row, read back by hand: the
    latent under one scale, the key as code + code' / 254 under the other;
    the second code takes the key's rounding from 0.6% of its size to
    nothing, the padding passes."""
    C, dr, pad = 512, 64, 64
    rng = np.random.default_rng(5)
    lat = rng.normal(size=(3, 7, C)).astype(np.float32)
    key = 1.7 * rng.normal(size=(3, 7, dr)).astype(np.float32)
    row = jnp.asarray(np.concatenate(
        [lat, key, np.zeros((3, 7, pad), np.float32)], -1))
    q, s = QC.quantize_latent(row, C, residual)
    q0, s0 = QC.quantize_latent(row[..., :C + dr], C, residual)
    assert q.dtype == jnp.int8 and q.shape == row.shape
    assert s.shape == (3, 7, 2) and s.dtype == jnp.float32
    assert np.array_equal(q0, q[..., :q0.shape[-1]]) and np.array_equal(s0, s)
    assert q0.shape[-1] == C + dr + residual
    q, s = np.asarray(q, np.float32), np.asarray(s)
    assert not q[..., C + dr + residual:].any()
    got_lat = q[..., :C] * s[..., :1]
    got_key = q[..., C:C + dr]
    if residual:
        got_key = got_key + q[..., C + dr:C + 2 * dr] / QC.RESIDUAL_STEPS
    got_key = got_key * s[..., 1:]
    err_lat = np.sqrt(np.mean((got_lat - lat) ** 2))
    err_key = np.sqrt(np.mean((got_key - key) ** 2)) / 1.7
    # a step is the largest value / 127; a rounding's RMS a step / sqrt(12)
    assert 0.0070 < err_lat < 0.0080
    assert err_key < 3e-5 if residual else 0.0058 < err_key < 0.0068


def test_the_second_code_serves_the_same_stream_through_the_kernel():
    """An int8 engine whose rows keep the key's second code (a latent of one
    lane tile): admission writes it (``_insert_prefilled``), an extend and
    the decode chunks read it; the kernel's engine streams the einsum
    engine's tokens, and its leaves are the two there were."""
    p = decoder.init_params(WIDE, jax.random.PRNGKey(2), dtype=jnp.float32)
    streams = []
    for cfg in (WIDE, dataclasses.replace(WIDE, kernels="interpret")):
        eng = make_engine(p, slots=2, cache=jnp.int8, cfg=cfg)
        assert eng.k_cache["q"].shape[-1] == 128 + 128
        assert eng.k_cache["s"].shape[2] == 2 and eng.v_cache is None
        more = np.concatenate([tokens(20, seed=2), tokens(13, seed=3)])
        out = [eng.admit(0, tokens(9, seed=1), GREEDY)]
        eng.admit(1, more[:20], GREEDY)
        eng.release(1, park=True)
        out.append(eng.extend(1, more, 20, GREEDY))
        for _ in range(3):
            out += [int(t) for t in eng.decode_n(4).reshape(-1)]
        codes = np.asarray(eng.k_cache["q"][:, 1, 0, :33])
        assert codes[..., 128 + 8:128 + 16].any()       # the second code
        assert not codes[..., 128 + 16:].any()
        streams.append(out)
    assert streams[0] == streams[1]


def test_the_kernel_tiles_at_the_served_shapes():
    """64 heads, a latent of 512, a row of 576 padded to 640, 4,096
    positions a slot: what ``_latent_kernel`` asks on the chip."""
    assert LK.latent_decode_tileable(BIG.n_heads, BIG.kv_latent_dim,
                                     BIG.cache_row_dims[1], 4096, False)
    assert BIG.cache_row_dims[1] == 640 and BIG.latent_row_pad == 64


def test_the_engine_serves_the_references_greedy_stream(ref, params):
    """admit + chunked decode through the engine's own programs, across the
    toy's 32 original positions: the greedy stream is the reference's, token
    by token."""
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


@pytest.mark.parametrize("cache", ["int8", "float32"])
def test_the_engine_serves_the_same_stream_through_the_kernel(params, cache):
    """Admission, an extend and decode chunks: the kernel's engine says
    ``latent_decode`` for its decode programs and ``einsum`` for its extend,
    and streams the einsum engine's tokens."""
    streams = []
    for cfg in (CFG, dataclasses.replace(CFG, kernels="interpret")):
        eng = make_engine(params, slots=2, cache=getattr(jnp, cache), cfg=cfg)
        out = [eng.admit(0, tokens(9, seed=1), GREEDY)]
        eng.admit(1, tokens(20, seed=2), GREEDY)
        out += [int(t) for t in eng.decode_n(4)[:, 0]]
        eng.release(1, park=True)
        more = np.concatenate([tokens(20, seed=2), tokens(13, seed=3)])
        # the parked slot holds 24 positions: its 4 decoded tokens are not
        # the continuation's, so the prefix is reused only as far as 20
        with pytest.raises(ValueError, match="cannot be cut back"):
            eng.extend(1, more, 20, GREEDY)
        eng.release(1)
        eng.admit(1, more[:20], GREEDY)
        eng.release(1, park=True)
        out.append(eng.extend(1, more, 20, GREEDY))
        for _ in range(3):
            out += [int(t) for t in eng.decode_n(4).reshape(-1)]
        streams.append(out)
        kinds = eng.kernels_by_kind()
        if cfg.kernels == "interpret":
            assert "decode=latent_decode" in kinds["decode"]
            assert "decode=einsum" in kinds["extend"]
        else:
            assert "decode=einsum" in kinds["decode"]
    assert streams[0] == streams[1]


def test_the_benchmarks_probe_passes_on_the_toy():
    """``server_child.probe`` as the cell runs it (both paths under their own
    router's sets, the decode step through the engine's own cache tree with
    nothing where values ride), on the CPU at the rehearsal's sizes."""
    conf = server_child.load_conf(CONF_PATH, True)
    cfg = server_child.model_config(conf, True)
    assert cfg.index_topk == 0 and cfg.kv_latent_dim == conf[
        "kv_lora_rank"] == 32
    assert cfg.rope_scaling_type == "yarn" and cfg.rope_scaling == conf[
        "rope_scaling"]["factor"]
    p = decoder.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=4, max_seq_len=128, decode_chunk=4,
                        cache_dtype=jnp.int8, paged=False,
                        min_prefill_bucket=16)
    assert server_child.probe(cfg, ecfg, p, conf, seed=7)
    said = server_child.COMPARED
    assert said["shortfall_served_vs_reference"]["value"] <= 0.08
    assert said["prefill_served_vs_reference"]["value"] < 0.03
    assert said["decode_served_vs_reference"]["value"] < 0.03


# -- no indexer anywhere ------------------------------------------------

@pytest.mark.parametrize("program", ["prefill", "decode", "extend"])
def test_lowered_programs_carry_no_indexer(params, program):
    """Latent attention under the scopes there were and the expert scopes,
    and NO ``attn.index`` scope, no top-k and no sort of positions: there
    is no selection to make."""
    from ollama_operator_tpu.runtime.trace import DEVICE_SCOPES
    if program == "prefill":
        low = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t)).lower(
            params, tokens(32)[None])
    else:
        T = 1 if program == "decode" else 4
        low = jax.jit(lambda p, t, K, n: decoder.forward_with_cache(
            p, CFG, t, K, None, n, route_live=n)).lower(
            params, tokens(2 * T).reshape(2, T), empty_rows(2, 32, "int8"),
            jnp.array([3, 0], jnp.int32))
    text = low.as_text(debug_info=True)
    found = {s for s in DEVICE_SCOPES
             if re.search(r'[/"]' + re.escape(s) + r'[/"]', text)}
    assert found >= {"attn.qkv", "attn.core", "attn.out", "mlp", "moe.route",
                     "moe.experts", "lm_head", "embed"}
    assert ("attn.kv_write" in found) == (program != "prefill")
    assert not {s for s in found
                if s.startswith(("ssm.", "conv.", "delta."))
                or s in ("attn.window", "attn.index")}
    # the router's top-k is the program's only one
    assert len(re.findall(r"chlo\.top_k|stablehlo\.sort", text)) <= 2 * 2


def test_the_stack_is_one_scan_a_span_and_no_branch(params):
    lp = params["layers"]
    assert lp["wkv_a"].shape == (4, CFG.dim, 40)
    assert lp["w_uk"].shape == (4, CFG.n_heads, 16, 32)
    assert lp["w_uv"].shape == (4, CFG.n_heads, 32, 16)
    assert lp["we_gate"].shape[:2] == (3, CFG.experts_held)
    assert decoder.empty_state(CFG, 2) is None
    jaxpr = jax.make_jaxpr(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, tokens(8)[None])
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [1, 3]
    assert "cond" not in str(jaxpr)


def test_cache_gauge_kinds_and_the_positions_gauge(params, monkeypatch):
    """One row class: ``tpu_model_cache_bytes`` has no ``index`` kind, the
    indexer's counter is not seeded by this model, and
    ``tpu_model_latent_positions{what}`` follows the host's lengths; gone
    with the model."""
    monkeypatch.setenv("TPU_WARM_BUCKETS", "0")   # nothing is served here
    from benchmark import prom
    from ollama_operator_tpu.runtime.service import LoadedModel
    from ollama_operator_tpu.runtime.trace import CACHE_GAUGES
    from ollama_operator_tpu.tokenizer.tokenizer import Tokenizer
    tok = Tokenizer("llama", [f"t{i}" for i in range(CFG.vocab_size)],
                    bos_id=1, eos_id=2)
    seeded = prom.total(prom.parse(METRICS.render()),
                        "tpu_model_index_positions_total")
    lm = LoadedModel("kimi", CFG, params, tok, ecfg=EngineConfig(
        max_slots=2, max_seq_len=64, cache_dtype=jnp.int8,
        min_prefill_bucket=16))
    try:
        eng = lm.engine
        want = {"full": 2 * 4 * 64 * (40 + 8), "window": 0, "state": 0}
        assert eng.cache_bytes == want and eng.v_cache is None
        assert eng.kv_bytes == want["full"]
        assert eng.latent_positions == {"live": 0, "allocated": 4 * 2 * 64}
        eng.admit(0, tokens(5), GREEDY)
        eng.admit(1, tokens(20, seed=1), GREEDY)
        assert eng.latent_positions["live"] == 4 * 25
        eng.decode_n(4)
        assert eng.latent_positions["live"] == 4 * 33
        text = METRICS.render().replace(".0", "")
        for kind, n in want.items():
            assert f'tpu_model_cache_bytes{{kind="{kind}"}} {n}' in text
        assert 'kind="index"' not in text
        assert f'tpu_model_latent_positions{{what="live"}} {4 * 33}' in text
        assert (f'tpu_model_latent_positions{{what="allocated"}} {4 * 2 * 64}'
                in text)
        # every label the scrape shows is one the vocabulary lists
        for name, (key, values) in CACHE_GAUGES.items():
            for labels, _v in prom.select(prom.parse(METRICS.render()), name):
                assert labels[key] in values
        eng.release(1)
        assert eng.latent_positions["live"] == 4 * 9
        # a decode chunk of this model moves no counter of an indexer
        assert prom.total(prom.parse(METRICS.render()),
                          "tpu_model_index_positions_total") == seeded
    finally:
        lm.unload()
    assert not re.search(r"^tpu_model_latent_positions", METRICS.render(),
                         re.M)


def test_other_models_have_no_positions_gauge():
    """The gauge is latent attention's: a ring model and a dense one have
    none; glm-5's toy, whose rows are latent too, has it."""
    for name, has in (("tiny-smallthinker", False), ("tiny", False),
                      ("tiny-glm5", True)):
        cfg = cfglib.PRESETS[name]
        p = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                dtype=jnp.float32)
        eng = Engine(cfg, p, ecfg=EngineConfig(
            max_slots=2, max_seq_len=64, cache_dtype=jnp.float32,
            decode_chunk=4, min_prefill_bucket=16))
        assert bool(eng.latent_positions) == has, name


# -- the chip's share -----------------------------------------------------

@pytest.mark.parametrize("who", ["program", "reference"])
@pytest.mark.parametrize("shares", [2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(ref, who, shares):
    """The toy's 16 experts in ``shares`` equal shares (4: the toy's own
    cut; 16 shares of one expert), each with the shared expert added whole:
    their sum, the shared expert counted once, is the uncut layer of the
    reference. Attention is replicated, so it is counted once by
    construction: a share's attention is the layer's."""
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


def test_a_shares_whole_forward_is_the_references_share(ref, params):
    """The toy's own cut (experts 0-3 of 16) through the whole stack:
    attention counted once in every layer, the held experts' part and the
    shared expert: the reference given the same share."""
    toks = tokens(24, seed=4)
    want = np.asarray(ref.forward(params, conf_of(CFG), jnp.asarray(toks)))
    got = np.asarray(jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, toks[None])[0][0])
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()


# -- admission, extend, parking, release ----------------------------------

@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_admit_extend_park_release_round_trip(params, cache):
    """A prompt admitted whole against the same prompt admitted as a parked
    prefix and an extend: the same first token, bit-equal rows for the
    prefix, the same greedy stream; a released slot's rows stay where they
    were (parking is leaving them) and another slot's are never touched."""
    dt = getattr(jnp, cache)
    whole, pieces = make_engine(params, cache=dt), make_engine(params,
                                                               cache=dt)
    prompt = tokens(40, seed=6)
    first = whole.admit(0, prompt, GREEDY)
    other = pieces.admit(2, tokens(11, seed=7), GREEDY)
    bystander = rows_of(pieces, 2)
    pieces.admit(0, prompt[:24], GREEDY)
    pieces.release(0, park=True)
    parked = rows_of(pieces, 0)
    assert pieces.latent_positions["live"] == 4 * 11
    got = pieces.extend(0, prompt, 24, GREEDY)
    assert got == first
    for a, b in zip(parked, rows_of(pieces, 0)):
        assert np.array_equal(a[..., :24, :] if a.ndim == 4 else a[..., :24],
                              b[..., :24, :] if b.ndim == 4 else b[..., :24])
    for a, b in zip(bystander, rows_of(pieces, 2)):
        assert np.array_equal(a, b)
    assert isinstance(other, int)
    pieces.release(2)
    a = [int(t) for _ in range(3) for t in whole.decode_n(4)[:, 0]]
    b = [int(t) for _ in range(3) for t in pieces.decode_n(4)[:, 0]]
    assert a == b
    assert whole.v_cache is None and pieces.v_cache is None
    before = rows_of(pieces, 0)
    pieces.release(0)
    assert pieces.latent_positions["live"] == 0
    for x, y in zip(before, rows_of(pieces, 0)):
        assert np.array_equal(x, y)


def test_admit_many_rows_keep_their_own_rows(params):
    """A batched admission of two prompts of one bucket against each admitted
    alone: the same rows, the same first tokens."""
    one, many = make_engine(params), make_engine(params)
    a, b = tokens(13, seed=8), tokens(15, seed=9)
    want = [one.admit(0, a, GREEDY), one.admit(1, b, GREEDY)]
    got = many.admit_many([0, 1], [a, b], [GREEDY, GREEDY])
    assert [int(t) for t in got] == want
    for s, n in ((0, 13), (1, 15)):
        for x, y in zip(rows_of(one, s), rows_of(many, s)):
            assert np.allclose(x[..., :n, :], y[..., :n, :], atol=1e-6)


def test_chunked_prefill_through_the_scheduler(params):
    """A prompt admitted in 16-token pieces with another stream's decode
    dispatches in between: the one-shot stream."""
    eng = make_engine(params, slots=2)
    long, short = tokens(50, seed=10), tokens(6, seed=11)
    want = uninterrupted(eng, long, GREEDY, 8)
    eng, sched = make_stack(eng, prefill_chunk=16)
    try:
        other = sched.submit(short, GREEDY, max_tokens=40)
        r = sched.submit(long, GREEDY, max_tokens=8)
        assert list(r.tokens()) == want
        list(other.tokens())
    finally:
        sched.shutdown()


@pytest.mark.parametrize("what, kw", [
    ("a page pool", dict(paged=True, page_size=16)),
    ("a mesh", dict(mesh=True)),
    ("the host tier", dict(env=("TPU_HOST_CACHE_GB", "1"))),
    ("export_request_kv", dict(call="export")),
])
def test_what_latent_rows_cannot_do_yet_is_refused_by_name(params, what, kw,
                                                           monkeypatch):
    """``Engine._refuse_for_latent_rows`` stands for a model without an
    indexer as it does for one with."""
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


# -- serving defaults, accounting -----------------------------------------

def test_zero_config_resolution_on_the_chip(monkeypatch):
    """bfloat16 weights, int8 contiguous cache, chunk 32 and the slots
    ``_recurrent_slots`` gives from the model alone: four tokens an expert a
    step at 8 of 384 kept would be 192, capped at 64; 4,096 positions."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert englib.resolve_engine_dtype(BIG, "tpu") == "bfloat16"
    assert englib._recurrent_slots(BIG) == 64
    ecfg = englib.resolve_serving_defaults(
        EngineConfig(max_slots=0, max_seq_len=4096, decode_chunk=0,
                     paged=None, page_size=0, n_pages=None,
                     cache_dtype=englib.resolve_cache_dtype(
                         englib.resolve_kv_dtype_default("tpu"))), BIG, None)
    assert (ecfg.paged, ecfg.max_slots, ecfg.decode_chunk) == (False, 64, 32)
    assert jnp.dtype(ecfg.cache_dtype) == jnp.int8
    assert min(ecfg.max_seq_len, BIG.max_seq_len) == 4096
    conf = work.load_conf(CONF_PATH)
    want = conf["expected_resolution"]
    assert (want["paged"], want["max_slots"], want["decode_chunk"]) == (
        ecfg.paged, ecfg.max_slots, ecfg.decode_chunk)
    assert (want["weights"], want["kv"]) == ("bfloat16", "int8")
    assert conf["saturating_clients"] == ecfg.max_slots


def test_accounting_prices_latent_attention_without_an_indexer():
    d = 7168
    moe = (8 * 12 / 384) * 6 * d * 2048 + 2 * d * 384 + 6 * d * 2048
    assert accounting.per_token_flops(BIG) == pytest.approx(
        8 * 2 * BIG.attn_params + 6 * d * 18432 + 7 * moe + 2 * d * 20480)
    # every position before the query, at any depth: a head's dot over the
    # row and its sum over the latent; nothing for an indexer
    pair = 2.0 * 64 * (2 * 512 + 64)
    assert accounting.attn_span_flops(BIG, 999, 1) == 8 * 1000 * pair
    assert accounting.attn_span_flops(BIG, 2999, 1) == 8 * 3000 * pair


def test_the_served_cache_by_shapes_alone():
    """Nothing is allocated: a slot of the published model holds 8 layers x
    4,096 positions of a 640-byte row (the key's second code where the
    padding was) with two scales, 21.2 MB, 1.36 GB at 64 slots, and the
    step reads all 648 bytes of it a live position a layer."""
    _, kd, vd = BIG.cache_row_dims
    assert 8 * 4096 * (kd + 2 * 4) == 8 * 4096 * 648 == 21_233_664
    assert vd == 0 and 1.35e9 < 64 * 21_233_664 < 1.37e9
    conf = work.load_conf(CONF_PATH)
    assert conf["kv_row_key_codes"] == 1 + (
        BIG.latent_key_residual // BIG.qk_rope_dim) == 2
    assert work.kv_bytes_per_token(conf, "int8") == 8 * 648
    assert work.kv_bytes_per_token(conf, "bfloat16") == 8 * 1152


# -- the benchmark's readers and arithmetic ------------------------------

def reader_ctx(conf, before=None, after=None, live=None):
    return types.SimpleNamespace(
        conf=conf, notes={}, resolved={"decode_chunk": 2, "max_slots": 4,
                                       "weights": "bfloat16",
                                       "kv_dtype": "int8"},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace_before=before or {}, trace_after=after or {},
        before=before or {}, after=after or {}, live_tokens=live)


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_none_on_the_parents_program(name, tmp_path,
                                                    monkeypatch):
    """The driver runs the new readers on the parent's program too, which has
    neither the gauge nor (in another cell) the kernel: nothing to read is
    None, no error; and on a configuration without the work functions."""
    from benchmark import prom, run, trace_spans
    from ollama_operator_tpu.server.metrics import Metrics
    monkeypatch.setattr(trace_spans.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    reg = Metrics()
    reg.gauge_fn("tpu_model_cache_bytes", lambda: 64e6, '{kind="full"}')
    scrape = prom.parse(reg.render())
    for path in (CONF_PATH, CONF_PATH.replace("kimi-k2.7-code", "glm-5")):
        assert run.layer_reader(name).read(reader_ctx(
            work.load_conf(path), scrape, scrape, live=1000.0)) is None


def test_kernel_spans_and_the_roofline_read_a_trace(tmp_path, monkeypatch):
    """Two complete runs of a decode module of two steps each: self time of
    the operations whose path names ``latent_decode`` over the steps (the
    einsums beside it under ``attn.core`` are not among it), and the larger of the rows'
    bytes and the absorbed dots over it; a trace without the kernel reads
    None."""
    from benchmark import kernel_spans, run, trace_spans
    meta = {1: ("jit__decode_n(7)", ""),
            2: ("%latent_decode.22 = bf16[] custom-call()",
                "jit(_decode_n)/while/body/attn.core/latent_decode/pallas_call:"),
            3: ("%latent_decode.21 = bf16[] custom-call()",
                "jit(_decode_n)/while/body/attn.core/latent_decode/pallas_call:"),
            4: ("%fusion.3 = f32[] fusion()",
                "jit(_decode_n)/attn.core/dot_general"),
            5: ("%fusion.4 = f32[] fusion()",
                "jit(_decode_n)/moe.experts/dot_general")}

    def planes(with_kernel):
        ops = []
        for t0 in (0, 2000):
            ops += [(t0 + 100, t0 + 400, 2 if with_kernel else 4),
                    (t0 + 400, t0 + 600, 3 if with_kernel else 5),
                    (t0 + 600, t0 + 900, 4)]
        return [{"name": "/device:TPU:0", "meta": meta, "lines": [
            {"name": "XLA Modules", "events": [(0, 1000, 1), (2000, 3000, 1)]},
            {"name": "XLA Ops", "events": ops}]}]

    conf = work.load_conf(CONF_PATH)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    for with_kernel in (True, False):
        kernel_spans._CACHE.clear()
        pl = planes(with_kernel)
        monkeypatch.setattr(trace_spans, "find_trace", lambda w=None: str(path))
        monkeypatch.setattr(trace_spans, "reduce",
                            lambda w=None, pl=pl: trace_spans.reduce_planes(pl))
        monkeypatch.setattr(trace_spans, "read_planes", lambda p, pl=pl: pl)
        got = kernel_spans.step_seconds(2, "latent_decode")
        ctx = reader_ctx(conf, live=3000.0)
        share = run.layer_reader("latent_attn_roofline").read(ctx)
        if with_kernel:
            assert got == pytest.approx(250e-12)
            note = ctx.notes["latent_attn_roofline"]
            assert note["row_bytes"] == 3000 * 8 * 648
            assert note["flops"] == 3000 * 8 * 64 * (576 + 512) * 2
            least = max(note["row_bytes"] / 819e9, note["flops"] / 197e12)
            assert least == note["row_bytes"] / 819e9   # 215 < 240.5 a byte
            assert share == pytest.approx(100 * least / 250e-12)
        else:
            assert got is None and share is None


def test_the_gauge_reader_reads_a_scrape():
    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    reg.gauge_fn("tpu_model_latent_positions", lambda: 500e3,
                 '{what="live"}')
    reg.gauge_fn("tpu_model_latent_positions", lambda: 2e6,
                 '{what="allocated"}')
    reg.gauge_fn("tpu_model_cache_bytes", lambda: 64e6, '{kind="full"}')
    scrape = prom.parse(reg.render())
    ctx = reader_ctx({}, {}, scrape)
    assert run.layer_reader("latent_live_share").read(ctx) == 25.0
    assert run.layer_reader("kv_cache_mb_per_slot").read(ctx) == 16.0
    assert run.layer_reader("index_cache_mb_per_slot").read(ctx) is None


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    """One configuration, one cell under glm-5's mix, the two new metrics on
    it alone, the accepted expert, cache and hold metrics extended to it and
    the indexer's four not."""
    from benchmark import run
    cell = run.find_cell(CELL)
    assert (cell.chips, cell.mix_name) == (1, "decode-deep")
    assert cell.conf["preset"] == "kimi-k2.7-code"
    assert cell.mix["clients"] == "saturating_clients"
    assert cell.conf["saturating_clients"] == 64
    assert cell.mix == run.find_cell("glm-5.decode-deep").mix
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) | {
        "decode_moe_ms_per_step", "moe_experts_roofline",
        "moe_expert_load_spread", "pass_filled_share", "late_launch_share",
        "decode_kv_write_ms_per_step", "kv_cache_mb_per_slot",
        "paged_attn_roofline", "decode_attn_ms_per_step",
        "decode_step_roofline"} <= names
    assert not {"decode_index_ms_per_step", "index_select_roofline",
                "index_kept_share", "index_cache_mb_per_slot",
                "decode_ssm_ms_per_step", "decode_window_attn_ms_per_step",
                "decode_delta_ms_per_step", "state_mb_per_slot",
                "ring_attn_roofline"} & names
    for other in ("glm-5.decode-deep", "smallthinker-21b-a3b.decode-deep",
                  "starcoder2-3b.decode-saturated"):
        assert not set(NEW_READERS) & {
            m["name"] for m in run.find_cell(other).per_layer}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended in PR 53's turn, behind everything that was there
    assert bench["configs"][8]["name"] == "kimi-k2.7-code"
    assert bench["workloads"][8]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][53:55]] == list(NEW_READERS)
    for entry in (bench["configs"][8], bench["workloads"][8]):
        assert len(entry["why"]) <= 200
    assert bench["configs"][8]["reduced"] == cell.conf["reduced"]
    # every reader the cell names is a file that is there
    for m in cell.per_layer:
        assert hasattr(run.layer_reader(m["name"]), "read")


def test_the_configurations_work_arithmetic():
    """The sizes the issue reckons with, from the configuration's own file."""
    conf = work.load_conf(CONF_PATH)
    w = work.load_module(os.path.join(conf["_dir"], conf["work"]))
    assert w.n_routed(conf) == 7
    assert w.attention_params(conf) == 101_122_048 == BIG.attn_params
    assert w.expert_params(conf) == w.shared_params(conf) == 44_040_192
    assert w.dense_params(conf) == 396_361_728
    assert w.router_params(conf) == 2_752_512
    assert not hasattr(w, "index_params")
    assert w.row_bytes(conf, "int8") == 512 + 2 * 64 + 2 * 4 == 648
    assert w.row_bytes(conf, "bfloat16") == 576 * 2
    assert work.kv_bytes_per_token(conf, "int8") == 8 * 648
    assert work.attn_flops_per_pair(conf) == 8 * 64 * (576 + 512) * 2
    assert w.latent_bytes_per_live_position(conf, "int8") == 8 * 648
    assert w.latent_flops_per_live_position(conf) == 8 * 139_264
    # near the ridge: 215 operations a byte, the chip's 240.5
    assert 214 < w.latent_flops_per_live_position(
        conf) / w.latent_bytes_per_live_position(conf, "int8") < 216
    # 64 tokens of 8 picks over 384 experts touch 74% of the 12 held
    assert w.distinct_experts(conf, 64) == pytest.approx(
        12 * (1 - (1 - 8 / 384) ** 64))
    assert 0.73 < w.distinct_experts(conf, 64) / 12 < 0.75
    assert w.experts_bytes_step(conf, 1e9, "bfloat16") == pytest.approx(
        7 * 12 * 44_040_192 * 2)
    assert work.weight_bytes_step(conf, 1e9, "bfloat16") == pytest.approx(
        2 * (BIG.n_params - 20480 * 7168), rel=1e-9)  # the embedding: a lookup
    # the issue's step: 3.36 GB of fixed weights, 5.5 GB of experts at 64
    assert 3.35e9 < 2 * w.fixed_params(conf) < 3.37e9
    assert 5.4e9 < w.experts_bytes_step(conf, 64, "bfloat16") < 5.6e9
    # a token keeps 8 of 384 and one in thirty-two of those is held here
    assert work.matmul_flops_per_token(conf) == pytest.approx(
        2 * (w.fixed_params(conf) + 7 * 0.25 * w.expert_params(conf)))
