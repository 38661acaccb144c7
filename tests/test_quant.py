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


# ---------------------------------------------------------------------------
# the int8 matmul's routes by row count (ops/quant.matmul)
# ---------------------------------------------------------------------------

from ollama_operator_tpu.ops.attention import record_kernels  # noqa: E402

_RK, _RO = 256, 384


def _route_case(N, dtype=jnp.float32, K=_RK, O=_RO, group=32):
    r = np.random.default_rng([N, K, O])
    x = jnp.asarray(r.standard_normal((N, K)), dtype)
    w = r.standard_normal((K, O)).astype(np.float32)
    qw = jax.tree_util.tree_map(jnp.asarray, Q.quantize_groupwise(w, group))
    want = np.asarray(x, np.float32) @ np.asarray(
        Q.dequantize_groupwise(qw), np.float32)
    return x, qw, want


# regime 1 up to 16 rows, regime 2 above, at every row count the warm plan
# compiles programs for (one block of rows up to 512, equal blocks above)
@pytest.mark.parametrize("N,kernel", [
    (1, "xla_int8"), (16, "xla_int8"), (17, "qmm_pallas"),
    (32, "qmm_pallas"), (64, "qmm_pallas"), (256, "qmm_pallas"),
    (1024, "qmm_pallas")])
def test_int8_matmul_routes_by_rows(N, kernel):
    x, qw, want = _route_case(N)
    with record_kernels() as picked:
        got = Q.matmul(x, qw, jnp.float32, kernels="interpret")
    assert picked == [("matmul", kernel, False)]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("N", [1, 16, 17, 32, 64, 256, 1024])
def test_int8_matmul_xla_stays_xla(N):
    """Regime 3: an explicit (or resolved) "xla" is XLA at every N, and
    is a route, not a fallback."""
    x, qw, want = _route_case(N)
    with record_kernels() as picked:
        got = Q.matmul(x, qw, jnp.float32, kernels="xla")
    assert picked == [("matmul", "xla_int8", False)]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("N", [17, 64])
def test_int8_matmul_bf16_operands_match_dense_form(N):
    """The fused kernel's arithmetic is the dense XLA form's: f32 scales,
    bf16 operands, f32 accumulation."""
    x, qw, _ = _route_case(N, jnp.bfloat16)
    ref = Q.qmm_dense(x, qw, jnp.float32)
    got = Q.matmul(x, qw, jnp.float32, kernels="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_int8_matmul_untileable_shape_is_the_only_fallback():
    x, qw, want = _route_case(24, K=48, O=40, group=16)
    with record_kernels() as picked:
        got = Q.matmul(x, qw, jnp.float32, kernels="interpret")
    assert picked == [("matmul", "xla_int8", True)]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("N,bm,nm", [
    (1, 16, 1), (17, 32, 1), (64, 64, 1), (192, 192, 1), (512, 512, 1),
    (768, 384, 2), (1024, 512, 2), (4096, 512, 8), (16384, 512, 32)])
def test_fused_kernel_row_blocks(N, bm, nm):
    from ollama_operator_tpu.ops.pallas.quant import _row_blocks
    assert _row_blocks(N) == (bm, nm)


def _resolved(monkeypatch, backend, mesh, **kw):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.delenv("OLLAMA_TPU_KERNELS", raising=False)
    return Q.resolve_mm_kernels(tiny(**kw), mesh).mm_kernels


@pytest.mark.parametrize("backend,mesh_shape,kw,want", [
    ("tpu", None, {}, "pallas"),
    ("tpu", (1,), {}, "pallas"),
    ("tpu", (2,), {}, "xla"),               # pallas_call is opaque to GSPMD
    ("cpu", None, {}, "xla"),
    ("tpu", None, {"kernels": "xla"}, "xla"),          # the escape hatch
    ("tpu", None, {"mm_kernels": "xla"}, "xla"),       # explicit stands
    ("cpu", None, {"mm_kernels": "pallas"}, "pallas"),
    ("tpu", (2,), {"mm_kernels": "interpret"}, "interpret")])
def test_resolve_mm_kernels(monkeypatch, backend, mesh_shape, kw, want):
    mesh = None
    if mesh_shape is not None:
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:mesh_shape[0]]), ("tp",))
    assert _resolved(monkeypatch, backend, mesh, **kw) == want


def test_int4_mm_kernels_still_imports_and_returns_a_config(monkeypatch):
    """The benchmark's child imports this name (benchmark/server_child.py)."""
    from ollama_operator_tpu.ops.quant import int4_mm_kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    out = int4_mm_kernels(tiny(), None)
    assert isinstance(out, cfglib.ModelConfig)
    assert out.mm_kernels == "pallas"
    assert int4_mm_kernels(out, None) is out


@pytest.mark.parametrize("mesh_devices", [0, 2])
def test_engine_resolves_mm_kernels_auto(mesh_devices):
    """The engine's constructor is where "auto" is decided: on this CPU
    backend, and on a mesh of two devices, the programs stay on XLA and no
    program reports a fallback."""
    cfg = tiny(max_seq_len=64)
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    qparams = Q.quantize_params(
        jax.tree_util.tree_map(np.asarray, params))
    mesh = None
    if mesh_devices:
        mesh = make_mesh(MeshPlan(tp=mesh_devices))
    qparams = jax.tree_util.tree_map(jnp.asarray, qparams)
    eng = Engine(cfg, qparams, mesh=mesh,
                 ecfg=EngineConfig(max_slots=2, max_seq_len=64,
                                   cache_dtype=jnp.float32,
                                   min_prefill_bucket=32))
    assert eng.cfg.mm_kernels == "xla"
    eng.admit(0, np.arange(3, 23, dtype=np.int32))     # 32 rows: N > 16
    picks = {p for ps in eng.program_kernels.values() for p in ps}
    assert "matmul=xla_int8" in picks
    assert not any("pallas" in p for p in picks if p.startswith("matmul"))


def test_engine_explicit_interpret_serves_fused_kernel_above_16_rows():
    """An engine whose matmuls are routed to the kernel: the admit program
    (32 rows) takes the fused kernel, the decode program (2 rows) the
    grouped XLA form, neither as a fallback."""
    from ollama_operator_tpu.runtime.trace import FLIGHT
    cfg = tiny(max_seq_len=64, mm_kernels="interpret", dim=128, ffn_dim=256)
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    qparams = jax.tree_util.tree_map(jnp.asarray, Q.quantize_params(
        jax.tree_util.tree_map(np.asarray, params)))
    before = len([e for e in FLIGHT.snapshot()
                  if e["kind"] == "kernel_fallback"])
    eng = Engine(cfg, qparams, mesh=None,
                 ecfg=EngineConfig(max_slots=2, max_seq_len=64,
                                   cache_dtype=jnp.float32,
                                   min_prefill_bucket=32))
    eng.admit(0, np.arange(3, 23, dtype=np.int32))
    eng.decode_n(2)
    by_kind = eng.kernels_by_kind()
    admit = [k for k in by_kind if k.startswith("admit")]
    assert admit and all("matmul=qmm_pallas" in by_kind[k] for k in admit)
    assert "matmul=xla_int8" in by_kind["decode"]
    assert "matmul=qmm_pallas" not in by_kind["decode"]
    after = len([e for e in FLIGHT.snapshot()
                 if e["kind"] == "kernel_fallback"])
    assert after == before


@pytest.mark.parametrize("bits", [8, 4])
def test_fused_matmuls_read_their_layer_of_the_stack_in_place(bits):
    """Under a kernel mode the decoder's layer scan leaves the quantized
    stacks unsliced and hands each step its index (decoder._scan_layers);
    prefill (18 rows) and a cached step must match the XLA path, which
    slices."""
    import dataclasses
    cfg = tiny(dim=128, ffn_dim=256)
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = jax.tree_util.tree_map(
        jnp.asarray, Q.quantize_params(jax.tree_util.tree_map(
            np.asarray, params), bits=bits))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                cfg.vocab_size)
    cfg_k = dataclasses.replace(cfg, mm_kernels="interpret")
    ref, ks, vs = decoder.prefill_chunk(qparams, cfg, tokens)
    with record_kernels() as picked:
        got, _, _ = decoder.prefill_chunk(qparams, cfg_k, tokens)
    kernel = "qmm_pallas" if bits == 8 else "qmm4_pallas"
    assert ("matmul", kernel, False) in picked
    assert not any(fb for _s, _k, fb in picked)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    S = 16
    pad = ((0, 0), (0, 0), (0, 0), (0, S - ks.shape[3]), (0, 0))
    kc, vc = jnp.pad(ks, pad), jnp.pad(vs, pad)
    step = tokens[:, :1]
    lengths = jnp.full((2,), 9, jnp.int32)
    want = decoder.forward_with_cache(qparams, cfg, step, kc, vc, lengths)[0]
    have = decoder.forward_with_cache(qparams, cfg_k, step, kc, vc,
                                      lengths)[0]
    np.testing.assert_allclose(np.asarray(have), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
