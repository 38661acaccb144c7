"""SPMD sharding tests on the 8-device virtual CPU mesh.

Verifies tp/dp-sharded execution is numerically identical to single-device
execution — the stand-in for multi-chip TPU slices (SURVEY.md §4
implication (b))."""

import jax
import jax.numpy as jnp
import numpy as np

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.parallel import (MeshPlan, make_mesh,
                                           shard_params)
from ollama_operator_tpu.parallel.sharding import (
    kv_cache_pspec, params_sharding_tree)
from jax.sharding import NamedSharding, PartitionSpec as P


def tiny():
    return cfglib.PRESETS["tiny"]


def test_mesh_construction():
    mesh = make_mesh(MeshPlan(dp=2, sp=1, tp=4))
    assert mesh.shape == {"dp": 2, "pp": 1, "sp": 1, "ep": 1, "tp": 4}


def test_tp_sharded_prefill_matches_single_device():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    ref, ref_k, _ = decoder.prefill_chunk(params, cfg, tokens)

    mesh = make_mesh(MeshPlan(dp=1, sp=1, tp=4))
    with jax.set_mesh(mesh):
        sharded = shard_params(params, mesh)
        fn = jax.jit(lambda p, t: decoder.prefill_chunk(p, cfg, t))
        out, ks, _ = fn(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(ks), np.asarray(ref_k), rtol=2e-4,
                               atol=2e-4)


def test_dp_tp_sharded_decode_matches_single_device():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, S = 4, 16
    shape = (cfg.n_layers, B, cfg.n_kv_heads, S, cfg.head_dim)
    k_cache = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    v_cache = jax.random.normal(jax.random.PRNGKey(3), shape, jnp.float32)
    lengths = jnp.array([3, 5, 0, 7], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, 1), 0,
                                cfg.vocab_size)

    ref, ref_k, ref_v = decoder.forward_with_cache(params, cfg, tokens,
                                                   k_cache, v_cache, lengths)

    mesh = make_mesh(MeshPlan(dp=2, sp=1, tp=2))
    with jax.set_mesh(mesh):
        p_sh = shard_params(params, mesh)
        cache_sh = NamedSharding(mesh, kv_cache_pspec())
        kc = jax.device_put(k_cache, cache_sh)
        vc = jax.device_put(v_cache, cache_sh)
        fn = jax.jit(lambda p, t, k, v, l: decoder.forward_with_cache(
            p, cfg, t, k, v, l))
        out, k2, v2 = fn(p_sh, tokens, kc, vc, lengths)

    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(k2), np.asarray(ref_k), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(ref_v), rtol=2e-4,
                               atol=2e-4)


def test_params_sharding_tree_covers_all_leaves():
    cfg = cfglib.ModelConfig(**{**tiny().__dict__, "attn_bias": True,
                                "out_bias": True, "qk_norm": True,
                                "norm_type": "layernorm"}).validate()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(MeshPlan(dp=1, sp=1, tp=8))
    tree = params_sharding_tree(params, mesh)
    flat_p, _ = jax.tree_util.tree_flatten(params)
    flat_s, _ = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, NamedSharding))
    assert len(flat_p) == len(flat_s)
