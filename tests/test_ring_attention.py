"""Ring attention / sequence-parallel long context on the 8-device CPU mesh.

The sequence axis is new TPU-native capability (SURVEY.md §5: the reference
has no long-context support at all) — these tests pin its semantics to the
dense single-device decoder bit-approximately."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.ops.attention import attend_hf, causal_mask
from ollama_operator_tpu.parallel import MeshPlan, make_mesh, shard_params
from ollama_operator_tpu.parallel import long_context as lc
from ollama_operator_tpu.parallel.ring_attention import (
    ring_attention, sp_cache_write, sp_decode_attention)
from ollama_operator_tpu.runtime.engine import Engine, EngineConfig, SlotOptions

F32 = jnp.float32


def tiny():
    return cfglib.PRESETS["tiny"]


def _ring_dense_pair(sp, T=32, window=0, seed=0):
    """Run ring_attention on an sp-way mesh and dense attend_hf; return both."""
    mesh = make_mesh(MeshPlan(dp=1, sp=sp, tp=8 // sp))
    B, H, KvH, hd = 2, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd), F32)
    k = jax.random.normal(ks[1], (B, KvH, T, hd), F32)
    v = jax.random.normal(ks[2], (B, KvH, T, hd), F32)
    scale = 1.0 / math.sqrt(hd)

    mask = causal_mask(T, T, 0, sliding_window=window)
    mask = jnp.broadcast_to(mask, (B, 1, T, T))
    ref = attend_hf(q, k, v, mask, scale)

    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, scale, "sp",
                                       sliding_window=window),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, None, "sp"), P(None, None, "sp")),
        out_specs=P(None, "sp"),
        axis_names={"sp"}, check_vma=False))
    out = fn(q, k, v)
    return np.asarray(ref), np.asarray(out)


def test_ring_attention_matches_dense_causal():
    for sp in (2, 4, 8):
        ref, out = _ring_dense_pair(sp, seed=sp)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ring_attention_sliding_window():
    ref, out = _ring_dense_pair(4, T=32, window=9, seed=3)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_sp_decode_attention_matches_dense():
    mesh = make_mesh(MeshPlan(dp=1, sp=4, tp=2))
    B, T, H, KvH, hd, S = 3, 1, 4, 2, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd), F32)
    kc = jax.random.normal(ks[1], (B, KvH, S, hd), F32)
    vc = jax.random.normal(ks[2], (B, KvH, S, hd), F32)
    lengths = jnp.array([5, 17, 32], jnp.int32)
    q_pos = (lengths - 1)[:, None]
    scale = 1.0 / math.sqrt(hd)

    k_pos = jnp.arange(S)[None, None, :]
    mask = jnp.where(k_pos <= q_pos[:, :, None], 0.0, -1e30)[:, None]
    ref = attend_hf(q, kc, vc, mask, scale)

    fn = jax.jit(jax.shard_map(
        lambda q, kc, vc, qp: sp_decode_attention(q, kc, vc, qp, scale, "sp"),
        mesh=mesh,
        in_specs=(P(), P(None, None, "sp"), P(None, None, "sp"), P()),
        out_specs=P(),
        axis_names={"sp"}, check_vma=False))
    out = fn(q, kc, vc, q_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_sp_cache_write_places_tokens_on_owner():
    # T=2 writes straddling a shard boundary (chunk size 4: positions 3|4
    # and 12|13 land on different owners) — exercises the mode="drop"
    # scatter contract for multi-token chunked continuation.
    mesh = make_mesh(MeshPlan(dp=1, sp=4, tp=2))
    B, KvH, S, hd, T = 2, 2, 16, 8, 2
    kc = jnp.zeros((B, KvH, S, hd), F32)
    vc = jnp.zeros((B, KvH, S, hd), F32)
    vals = jnp.array([[[[2.0]], [[2.5]]], [[[3.0]], [[3.5]]]])  # [B,T,1,1]
    k_new = jnp.ones((B, KvH, T, hd), F32) * vals.transpose(0, 2, 1, 3)
    pos = jnp.array([[3, 4], [12, 13]], jnp.int32)

    fn = jax.jit(jax.shard_map(
        lambda kc, vc, kn, vn, p: sp_cache_write(kc, vc, kn, vn, p, "sp"),
        mesh=mesh,
        in_specs=(P(None, None, "sp"), P(None, None, "sp"), P(), P(), P()),
        out_specs=(P(None, None, "sp"), P(None, None, "sp")),
        axis_names={"sp"}, check_vma=False))
    kc2, _ = fn(kc, vc, k_new, k_new, pos)
    got = np.asarray(kc2)
    assert np.all(got[0, :, 3] == 2.0) and np.all(got[0, :, 4] == 2.5)
    assert np.all(got[1, :, 12] == 3.0) and np.all(got[1, :, 13] == 3.5)
    mask = np.ones((B, S), bool)
    mask[0, 3] = mask[0, 4] = mask[1, 12] = mask[1, 13] = False
    assert np.all(got.transpose(0, 2, 1, 3)[mask] == 0.0)


def test_sp_prefill_matches_reference():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    ref, ref_k, ref_v = decoder.prefill_chunk(params, cfg, tokens)

    mesh = make_mesh(MeshPlan(dp=1, sp=4, tp=2))
    sharded = shard_params(params, mesh, cfg)
    out, ks, vs = jax.jit(
        lambda p, t: lc.prefill_chunk_sp(p, cfg, t, mesh))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(ks), np.asarray(ref_k), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(vs), np.asarray(ref_v), rtol=2e-4,
                               atol=2e-4)


def test_sp_forward_with_cache_matches_reference():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    B, S = 2, 32
    shape = (cfg.n_layers, B, cfg.n_kv_heads, S, cfg.head_dim)
    k_cache = jax.random.normal(jax.random.PRNGKey(2), shape, F32)
    v_cache = jax.random.normal(jax.random.PRNGKey(3), shape, F32)
    lengths = jnp.array([9, 21], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, 1), 0,
                                cfg.vocab_size)
    ref, ref_k, ref_v = decoder.forward_with_cache(
        params, cfg, tokens, k_cache, v_cache, lengths)

    mesh = make_mesh(MeshPlan(dp=1, sp=4, tp=2))
    sharded = shard_params(params, mesh, cfg)
    cache_sh = NamedSharding(mesh, P(None, None, None, "sp", None))
    kc = jax.device_put(k_cache, cache_sh)
    vc = jax.device_put(v_cache, cache_sh)
    out, ks, vs = jax.jit(
        lambda p, t, kc, vc, l: lc.forward_with_cache_sp(
            p, cfg, t, kc, vc, l, mesh))(sharded, tokens, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(ks), np.asarray(ref_k), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(vs), np.asarray(ref_v), rtol=2e-4,
                               atol=2e-4)


def test_engine_sp_greedy_matches_single_device():
    from tests.test_engine import GREEDY, greedy_reference

    cfg = dataclasses.replace(tiny(), kernels="xla")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    ref = greedy_reference(params, cfg, np.array([5, 9, 2, 11, 7], np.int32),
                           6)

    mesh = make_mesh(MeshPlan(dp=1, sp=4, tp=2))
    eng = Engine(cfg, params, mesh=mesh,
                 ecfg=EngineConfig(max_slots=4, max_seq_len=128,
                                   cache_dtype=F32, min_prefill_bucket=16))
    assert eng.sp_size == 4
    got = [eng.admit(0, np.array([5, 9, 2, 11, 7], np.int32), GREEDY)]
    for _ in range(5):
        got.append(int(eng.decode()[0]))
    assert got == ref


def test_engine_sp_int8_matches_single_device_int8():
    """int8 KV × sp (round-1 weak #4 exclusion): the sp collectives
    quantize fresh K/V into sharded {"q","s"} chunks and fold the scales
    into scores/probs — greedy tokens must match the single-device int8
    engine exactly (identical quantization on both sides)."""
    from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                    SlotOptions)
    cfg = dataclasses.replace(tiny(), kernels="xla")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    prompt = np.array([3, 1, 4, 1, 5, 9, 2, 6, 10, 11, 12, 13], np.int32)
    opts = SlotOptions(temperature=0.0)

    def run(mesh):
        eng = Engine(cfg, params, mesh=mesh,
                     ecfg=EngineConfig(max_slots=2, max_seq_len=64,
                                       cache_dtype=jnp.int8,
                                       min_prefill_bucket=16))
        seq = [eng.admit(0, prompt, opts)]
        for _ in range(6):
            seq.append(int(eng.decode()[0]))
        return seq

    assert run(make_mesh(MeshPlan(sp=2, tp=2))) == run(None)


def test_engine_sp_multimodal_embeds_matches_single_device():
    """Multimodal admissions on sp meshes (round-1 weak #4): embeds shard
    over sp along the sequence axis through prefill_chunk_sp."""
    from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                    SlotOptions)
    cfg = dataclasses.replace(tiny(), kernels="xla")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    prompt = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    emb = np.asarray(decoder._embed(cfg, params,
                                    jnp.asarray(prompt)[None]))[0]
    opts = SlotOptions(temperature=0.0)

    def run(mesh):
        eng = Engine(cfg, params, mesh=mesh,
                     ecfg=EngineConfig(max_slots=2, max_seq_len=64,
                                       cache_dtype=F32,
                                       min_prefill_bucket=16))
        seq = [eng.admit(0, prompt, opts, embeds=emb)]
        for _ in range(3):
            seq.append(int(eng.decode()[0]))
        return seq

    assert run(make_mesh(MeshPlan(sp=2, tp=2))) == run(None)
