"""Weight-only int8 quantization: groupwise quantize/dequant round-trip,
XLA grouped matmul vs reference, pallas fused kernel (interpret) parity,
quantized decoder forward accuracy, TP-sharded quantized params, and the
engine running fully quantized end-to-end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.ops import quant as Q
from ollama_operator_tpu.ops.pallas.quant import qmm_pallas
from ollama_operator_tpu.parallel import (MeshPlan, make_mesh,
                                           shard_params)
from ollama_operator_tpu.runtime.engine import Engine, EngineConfig, SlotOptions

rng = np.random.default_rng(5)


def tiny(**kw):
    base = cfglib.PRESETS["tiny"]
    return cfglib.ModelConfig(**{**base.__dict__, **kw}).validate()


def test_quantize_dequantize_roundtrip():
    w = rng.standard_normal((64, 48)).astype(np.float32)
    qw = Q.quantize_groupwise(w, group=32)
    assert qw["q"].dtype == np.int8
    assert qw["q"].shape == (64, 48)
    assert qw["s"].shape == (2, 48)
    back = np.asarray(Q.dequantize_groupwise(qw))
    # int8 groupwise: max error is half a step = amax/254 per group
    err = np.abs(back - w)
    step = np.abs(w).reshape(2, 32, 48).max(1, keepdims=True) / 127.0
    assert (err.reshape(2, 32, 48) <= 0.51 * step + 1e-7).all()


def test_quantize_already_int8_grid_is_lossless():
    """Weights that already sit on a symmetric int8 g=32 grid (i.e. what a
    GGUF q8_0 tensor dequantizes to) must survive requantization exactly."""
    q = rng.integers(-126, 127, (64, 16)).astype(np.int8)
    # q8_0 scale is amax/127, so every group's max quant hits ±127
    q.reshape(2, 32, 16)[:, 0, :] = 127
    s = (rng.random((2, 16)).astype(np.float32) + 0.5) / 127.0
    w = np.asarray(Q.dequantize_groupwise({"q": q, "s": s}))
    qw = Q.quantize_groupwise(w, group=32)
    back = np.asarray(Q.dequantize_groupwise(qw))
    np.testing.assert_allclose(back, w, rtol=1e-6, atol=1e-7)


def test_qmm_matches_dequant_matmul():
    x = jnp.asarray(rng.standard_normal((3, 5, 64)), jnp.float32)
    w = rng.standard_normal((64, 48)).astype(np.float32)
    qw = jax.tree_util.tree_map(jnp.asarray, Q.quantize_groupwise(w, 32))
    want = np.asarray(x) @ np.asarray(Q.dequantize_groupwise(qw))
    got = Q.qmm(x, qw)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,K,O", [(1, 64, 128), (8, 256, 256), (5, 128, 384)])
def test_qmm_pallas_interpret_matches_xla(B, K, O):
    x = jnp.asarray(rng.standard_normal((B, K)), jnp.float32)
    w = rng.standard_normal((K, O)).astype(np.float32)
    qw = jax.tree_util.tree_map(jnp.asarray, Q.quantize_groupwise(w, 32))
    ref = Q.qmm(x, qw, out_dtype=jnp.float32)
    got = qmm_pallas(x, qw["q"], qw["s"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_qmm_pallas_fallback_odd_shapes():
    """Shapes that don't tile must silently use the XLA path."""
    x = jnp.asarray(rng.standard_normal((2, 48)), jnp.float32)
    w = rng.standard_normal((48, 40)).astype(np.float32)
    qw = jax.tree_util.tree_map(jnp.asarray, Q.quantize_groupwise(w, 16))
    ref = Q.qmm(x, qw, out_dtype=jnp.float32)
    got = qmm_pallas(x, qw["q"], qw["s"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_quantized_decoder_close_to_dense():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = Q.quantize_params(
        jax.tree_util.tree_map(np.asarray, params))
    qparams = jax.tree_util.tree_map(jnp.asarray, qparams)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                cfg.vocab_size)
    ref, _, _ = decoder.prefill_chunk(params, cfg, tokens)
    got, _, _ = decoder.prefill_chunk(qparams, cfg, tokens)
    # weight-only int8: logits drift slightly but ranking must agree
    ref_n, got_n = np.asarray(ref), np.asarray(got)
    assert np.abs(ref_n - got_n).max() < 0.15 * np.abs(ref_n).max() + 0.05
    agree = (ref_n.argmax(-1) == got_n.argmax(-1)).mean()
    assert agree > 0.9


def test_quantized_params_tp_sharded_matches_single_device():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = jax.tree_util.tree_map(
        jnp.asarray, Q.quantize_params(jax.tree_util.tree_map(
            np.asarray, params)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    ref, _, _ = decoder.prefill_chunk(qparams, cfg, tokens)

    mesh = make_mesh(MeshPlan(tp=4))
    with jax.set_mesh(mesh):
        sharded = shard_params(qparams, mesh, cfg)
        fn = jax.jit(lambda p, t: decoder.prefill_chunk(p, cfg, t))
        out, _, _ = fn(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_engine_int8_params_decode():
    """Engine end-to-end with quantized weights: greedy tokens match the
    dequantized-dense engine (same numeric path, g=32 exact grid)."""
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    qparams_np = Q.quantize_params(jax.tree_util.tree_map(np.asarray, params))
    dq = {}
    for k, v in qparams_np.items():
        if k == "layers":
            dq[k] = {lk: (Q.dequantize_groupwise(lv) if Q.is_quantized(lv)
                          else jnp.asarray(lv)) for lk, lv in v.items()}
        else:
            dq[k] = (Q.dequantize_groupwise(v) if Q.is_quantized(v)
                     else jnp.asarray(v))
    qparams = jax.tree_util.tree_map(jnp.asarray, qparams_np)

    ecfg = EngineConfig(max_slots=2, max_seq_len=64, min_prefill_bucket=8,
                        cache_dtype=jnp.float32)
    opts = SlotOptions(temperature=0.0)
    prompt = np.asarray(rng.integers(1, cfg.vocab_size, 11), np.int32)

    eng_q = Engine(cfg, qparams, ecfg=ecfg)
    tq = [eng_q.admit(0, prompt, opts)]
    for _ in range(5):
        tq.append(int(eng_q.decode()[0]))

    eng_d = Engine(cfg, dq, ecfg=ecfg)
    td = [eng_d.admit(0, prompt, opts)]
    for _ in range(5):
        td.append(int(eng_d.decode()[0]))

    assert tq == td


def test_quantized_bytes_halved():
    cfg = tiny()
    params = jax.tree_util.tree_map(
        np.asarray, decoder.init_params(cfg, jax.random.PRNGKey(0)))
    dense = Q.quantized_bytes(params)
    qp = Q.quantize_params(params)
    quant = Q.quantized_bytes(qp)
    assert quant < 0.75 * dense


# ---------------------------------------------------------------------------
# int4 (W4A16, packed nibbles — ops/quant.py int4 section)
# ---------------------------------------------------------------------------

def test_int4_pack_unpack_roundtrip():
    q = rng.integers(-7, 8, (96, 24)).astype(np.int8)
    packed = Q.pack_int4(q)
    assert packed.dtype == np.uint8
    assert packed.shape == (48, 24)
    np.testing.assert_array_equal(Q.unpack_int4(packed), q)


def test_int4_quantize_dequantize_error_bound():
    w = rng.standard_normal((64, 48)).astype(np.float32)
    qw = Q.quantize_groupwise_int4(w)
    assert qw["q4"].shape == (32, 48)
    assert qw["s"].shape == (2, 48)
    back = np.asarray(Q.dequantize_groupwise(qw))
    err = np.abs(back - w)
    step = np.abs(w).reshape(2, 32, 48).max(1, keepdims=True) / 7.0
    assert (err.reshape(2, 32, 48) <= 0.51 * step + 1e-7).all()


def test_int4_grid_is_lossless():
    """Weights already on the symmetric int4 g=32 grid requantize exactly
    (what a GGUF q4_0 tensor dequantizes to, modulo its lone -8 code)."""
    q = rng.integers(-7, 8, (64, 16)).astype(np.int8)
    q.reshape(2, 32, 16)[:, 0, :] = 7      # every group attains ±7
    s = (rng.random((2, 16)).astype(np.float32) + 0.5) / 7.0
    w = np.asarray(Q.dequantize_groupwise({"q4": Q.pack_int4(q), "s": s}))
    qw = Q.quantize_groupwise_int4(w)
    back = np.asarray(Q.dequantize_groupwise(qw))
    np.testing.assert_allclose(back, w, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("lead", [(3,), (2, 5), (24,)])
def test_qmm4_matches_dequant_matmul(lead):
    """Covers both the decode grouped form (N<=16) and the prefill
    dequant-transient form (N>16)."""
    x = jnp.asarray(rng.standard_normal((*lead, 64)), jnp.float32)
    w = rng.standard_normal((64, 48)).astype(np.float32)
    qw = jax.tree_util.tree_map(jnp.asarray, Q.quantize_groupwise_int4(w))
    want = np.asarray(x) @ np.asarray(Q.dequantize_groupwise(qw))
    got = Q.qmm4(x, qw)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,K,O", [(1, 64, 128), (8, 256, 256), (5, 128, 384)])
def test_qmm4_pallas_interpret_matches_xla(B, K, O):
    from ollama_operator_tpu.ops.pallas.quant import qmm4_pallas
    x = jnp.asarray(rng.standard_normal((B, K)), jnp.float32)
    w = rng.standard_normal((K, O)).astype(np.float32)
    qw = jax.tree_util.tree_map(jnp.asarray, Q.quantize_groupwise_int4(w))
    ref = Q.qmm4(x, qw, out_dtype=jnp.float32)
    got = qmm4_pallas(x, qw["q4"], qw["s"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_qmm4_pallas_fallback_odd_shapes():
    from ollama_operator_tpu.ops.pallas.quant import qmm4_pallas
    x = jnp.asarray(rng.standard_normal((2, 96)), jnp.float32)
    w = rng.standard_normal((96, 40)).astype(np.float32)
    qw = jax.tree_util.tree_map(jnp.asarray, Q.quantize_groupwise_int4(w))
    ref = Q.qmm4(x, qw, out_dtype=jnp.float32)
    got = qmm4_pallas(x, qw["q4"], qw["s"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_int4_decoder_close_to_dense():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = Q.quantize_params(
        jax.tree_util.tree_map(np.asarray, params), bits=4)
    qparams = jax.tree_util.tree_map(jnp.asarray, qparams)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                cfg.vocab_size)
    ref, _, _ = decoder.prefill_chunk(params, cfg, tokens)
    got, _, _ = decoder.prefill_chunk(qparams, cfg, tokens)
    # int4 drifts more than int8; ranking must still broadly agree
    ref_n, got_n = np.asarray(ref), np.asarray(got)
    assert np.abs(ref_n - got_n).max() < 0.4 * np.abs(ref_n).max() + 0.1
    agree = (ref_n.argmax(-1) == got_n.argmax(-1)).mean()
    assert agree > 0.75


def test_int4_params_tp_sharded_matches_single_device():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = jax.tree_util.tree_map(
        jnp.asarray, Q.quantize_params(jax.tree_util.tree_map(
            np.asarray, params), bits=4))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    ref, _, _ = decoder.prefill_chunk(qparams, cfg, tokens)

    mesh = make_mesh(MeshPlan(tp=4))
    with jax.set_mesh(mesh):
        sharded = shard_params(qparams, mesh, cfg)
        fn = jax.jit(lambda p, t: decoder.prefill_chunk(p, cfg, t))
        out, _, _ = fn(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_engine_int4_params_decode():
    """Engine end-to-end with int4 weights: greedy tokens match the
    dequantized-dense engine (same numeric path, exact grid)."""
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    qparams_np = Q.quantize_params(
        jax.tree_util.tree_map(np.asarray, params), bits=4)
    dq = {}
    for k, v in qparams_np.items():
        if k == "layers":
            dq[k] = {lk: (Q.dequantize_groupwise(lv) if Q.is_quantized(lv)
                          else jnp.asarray(lv)) for lk, lv in v.items()}
        else:
            dq[k] = (Q.dequantize_groupwise(v) if Q.is_quantized(v)
                     else jnp.asarray(v))
    qparams = jax.tree_util.tree_map(jnp.asarray, qparams_np)

    ecfg = EngineConfig(max_slots=2, max_seq_len=64, min_prefill_bucket=8,
                        cache_dtype=jnp.float32)
    opts = SlotOptions(temperature=0.0)
    prompt = np.asarray(rng.integers(1, cfg.vocab_size, 11), np.int32)

    eng_q = Engine(cfg, qparams, ecfg=ecfg)
    tq = [eng_q.admit(0, prompt, opts)]
    for _ in range(5):
        tq.append(int(eng_q.decode()[0]))

    eng_d = Engine(cfg, dq, ecfg=ecfg)
    td = [eng_d.admit(0, prompt, opts)]
    for _ in range(5):
        td.append(int(eng_d.decode()[0]))

    assert tq == td


def test_int4_bytes_quartered():
    """Per quantized leaf: the packed int4 code array is exactly half the
    int8 one (the tiny preset's dense embeddings would wash this out of a
    whole-tree ratio)."""
    cfg = tiny()
    params = jax.tree_util.tree_map(
        np.asarray, decoder.init_params(cfg, jax.random.PRNGKey(0)))
    q8 = Q.quantize_params(dict(params))["layers"]["wq"]
    params2 = jax.tree_util.tree_map(
        np.asarray, decoder.init_params(cfg, jax.random.PRNGKey(0)))
    q4 = Q.quantize_params(params2, bits=4)["layers"]["wq"]
    assert q4["q4"].nbytes * 2 == q8["q"].nbytes
    assert q4["s"].nbytes == q8["s"].nbytes


def test_int4_mm_kernels_interpret_matches_xla():
    """cfg.mm_kernels routes just the quantized matmuls through the
    kernel (decoder._mm); interpret-mode output must match the XLA path."""
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = jax.tree_util.tree_map(
        jnp.asarray, Q.quantize_params(jax.tree_util.tree_map(
            np.asarray, params), bits=4))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                cfg.vocab_size)
    ref, _, _ = decoder.prefill_chunk(qparams, cfg, tokens)
    import dataclasses
    cfg_k = dataclasses.replace(cfg, mm_kernels="interpret")
    got, _, _ = decoder.prefill_chunk(qparams, cfg_k, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
