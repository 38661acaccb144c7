"""Pipeline parallelism: the GPipe-schedule forwards must agree exactly
with the dense single-device decoder, across pp widths, microbatch counts,
combined pp×tp meshes, and MoE blocks (pp×ep)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.parallel import MeshPlan, make_mesh
from ollama_operator_tpu.parallel import pipeline as PL
from ollama_operator_tpu.parallel.sharding import shard_params

F32 = jnp.float32


def tiny(name="tiny", **kw):
    base = cfglib.PRESETS[name]
    return cfglib.ModelConfig(**{**base.__dict__, **kw}).validate()


def make_cache(cfg, B, S, dtype=F32):
    shape = (cfg.n_layers, B, cfg.n_kv_heads, S, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def ref_state(cfg, params, tokens, split, S):
    """Dense prefill of tokens[:, :split] into an S-slot cache."""
    logits, ks, vs = decoder.prefill_chunk(params, cfg, tokens[:, :split])
    k_cache, v_cache = make_cache(cfg, tokens.shape[0], S)
    k_cache = k_cache.at[:, :, :, :split].set(ks)
    v_cache = v_cache.at[:, :, :, :split].set(vs)
    return logits, k_cache, v_cache


@pytest.mark.parametrize("pp,mb", [(2, 2), (4, 4), (2, 4)])
def test_pp_prefill_matches_dense(pp, mb):
    cfg = tiny(n_layers=4)
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    B, T = 4, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    ref, ref_k, ref_v = decoder.prefill_chunk(params, cfg, tokens)

    mesh = make_mesh(MeshPlan(pp=pp))
    logits, ks, vs = PL.prefill_chunk_pp(params, cfg, tokens, mesh,
                                         n_microbatches=mb)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ks), np.asarray(ref_k),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(vs), np.asarray(ref_v),
                               rtol=1e-5, atol=1e-5)


def test_pp_decode_matches_dense():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    B, T, split, S = 4, 12, 8, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    ref_logits, _, _ = decoder.prefill_chunk(params, cfg, tokens)
    _, k_cache, v_cache = ref_state(cfg, params, tokens, split, S)
    lengths = jnp.full((B,), split, jnp.int32)

    mesh = make_mesh(MeshPlan(pp=2))
    for i in range(split, T):
        logits, k_cache, v_cache = PL.forward_with_cache_pp(
            params, cfg, tokens[:, i:i + 1], k_cache, v_cache, lengths, mesh)
        lengths = lengths + 1
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(ref_logits[:, i]),
                                   rtol=3e-4, atol=3e-4)


def test_pp_tp_mesh_matches_dense():
    """pp manual + tp GSPMD-auto in the same program (Megatron sharding on
    each stage's weights stays live inside the manual region)."""
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    B, T = 4, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    ref, _, _ = decoder.prefill_chunk(params, cfg, tokens)

    mesh = make_mesh(MeshPlan(pp=2, tp=4))
    with jax.set_mesh(mesh):
        sharded = shard_params(params, mesh, cfg)
        fn = jax.jit(lambda p, t: PL.prefill_chunk_pp(p, cfg, t, mesh))
        logits, _, _ = fn(sharded, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_pp_moe_ep_mesh_matches_dense():
    """MoE blocks inside pipeline stages, experts ep-sharded: pp manual ×
    ep/tp auto — the full 5-axis story in one program."""
    cfg = tiny("tiny-moe", moe_impl="einsum")
    params = decoder.init_params(cfg, jax.random.PRNGKey(2), dtype=F32)
    B, T = 4, 8
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0,
                                cfg.vocab_size)
    ref, _, _ = decoder.prefill_chunk(params, cfg, tokens)

    mesh = make_mesh(MeshPlan(pp=2, ep=2, tp=2))
    with jax.set_mesh(mesh):
        sharded = shard_params(params, mesh, cfg)
        fn = jax.jit(lambda p, t: PL.prefill_chunk_pp(p, cfg, t, mesh))
        logits, _, _ = fn(sharded, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_split_merge_stages_roundtrip():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    st = PL.split_stages(params["layers"], 2)
    back = PL.merge_stages(st)
    for k in params["layers"]:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params["layers"][k]))
