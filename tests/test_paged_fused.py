"""The paged-attention pallas kernel (interpret mode on CPU) against the
gather+einsum path with everything else equal, bit-for-bit at the token
level — greedy and seeded, cold and with a radix stitch, across attention
tail buckets — plus the int4 nibble-packed KV pool riding the same A/B
(both arms share one codec, so gather+einsum stays a parity oracle for
the lossy dtype). The reference arm is reached by substituting the one
function that chooses the route (``decoder._paged_kernel_usable``): no
variable selects it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.models.config import PRESETS
from ollama_operator_tpu.ops import quant_cache as QC
from ollama_operator_tpu.runtime.engine import Engine, EngineConfig, SlotOptions
from ollama_operator_tpu.runtime.scheduler import Scheduler

BASE = PRESETS["tiny"]
INTERP = dataclasses.replace(BASE, kernels="interpret")
GREEDY = SlotOptions(temperature=0.0)
SEEDED = SlotOptions(temperature=0.9, top_k=40, seed=13)
PAGED = EngineConfig(max_slots=4, max_seq_len=64, cache_dtype=jnp.float32,
                     min_prefill_bucket=16, paged=True, page_size=8)

PREFIX = np.arange(1, 25, dtype=np.int32)          # 24 tokens = 3 pages
SHORT = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(BASE, jax.random.key(0), jnp.float32)


def _never_the_kernel(cfg, mesh, T, k_pool):
    """``_paged_kernel_usable`` answering no, and recording the route as
    the real one does."""
    from ollama_operator_tpu.ops.attention import note_kernel
    note_kernel("paged_decode" if T == 1 else "paged_extend",
                "gather_einsum")
    return False


def _arm(params, monkeypatch, fused, cache_dtype, warm, kinds=None):
    """One serving arm. Probes land in different attention tail buckets
    (8-token prompt → 16 bucket, 24-token radix prefix → 32 bucket) and
    the 8-token budgets walk generation across a bucket boundary."""
    with monkeypatch.context() as mp:
        if not fused:
            mp.setattr(decoder, "_paged_kernel_usable", _never_the_kernel)
        return _serve(params, cache_dtype, warm, kinds)


def _serve(params, cache_dtype, warm, kinds):
    ecfg = dataclasses.replace(PAGED, cache_dtype=cache_dtype)
    eng = Engine(INTERP, params, ecfg=ecfg)
    sched = Scheduler(eng)
    try:
        outs = []
        if warm:
            donor = np.concatenate([PREFIX, np.array([60, 61], np.int32)])
            outs.append(list(sched.submit(donor, max_tokens=4,
                                          opts=GREEDY).tokens()))
        probes = [
            (np.concatenate([PREFIX, np.array([70], np.int32)]), GREEDY),
            (np.concatenate([PREFIX, np.array([70], np.int32)]), SEEDED),
            (SHORT, GREEDY),
            (SHORT, SEEDED),
        ]
        reqs = [sched.submit(p, max_tokens=8, opts=o) for p, o in probes]
        outs += [list(r.tokens()) for r in reqs]
        for r in reqs:
            assert r.error is None
        if warm:
            assert any(r.stats.n_reused >= 16 for r in reqs)
        if kinds is not None:
            kinds.update(eng.kernels_by_kind())
        return outs
    finally:
        sched.shutdown()


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "radix-hit"])
@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8, "int4"],
                         ids=["f32", "int8", "int4"])
def test_fused_streams_match_reference(params, monkeypatch, cache_dtype,
                                       warm):
    on = _arm(params, monkeypatch, True, cache_dtype, warm)
    off = _arm(params, monkeypatch, False, cache_dtype, warm)
    assert on == off, (cache_dtype, warm)


def test_fused_knob_routes_the_kernel(params, monkeypatch):
    """The substitution actually flips the route (guards a refactor that
    would compare the kernel's path against itself): each arm's engine
    says which attention its decode programs traced."""
    on, off = {}, {}
    _arm(params, monkeypatch, True, jnp.float32, False, kinds=on)
    _arm(params, monkeypatch, False, jnp.float32, False, kinds=off)
    assert "paged_decode=paged_v3" in on["decode"]
    assert "paged_decode=gather_einsum" not in on["decode"]
    assert "paged_decode=gather_einsum" in off["decode"]
    assert "paged_decode=paged_v3" not in off["decode"]


# --- int4 KV pool ------------------------------------------------------------

def test_quantize_kv4_roundtrip_bound():
    """Dequantised int4 codes land within half a step (scale/2) of the
    source, and the codes stay in the nibble-safe [-7, 7] band."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 4, 16, 8)), jnp.float32)
    q, s = QC.quantize_kv4(x)
    assert int(jnp.max(jnp.abs(q))) <= 7
    back = q.astype(jnp.float32) * s[..., None]
    err = np.abs(np.asarray(back - x))
    bound = np.asarray(s)[..., None] * 0.51 + 1e-7
    assert (err <= bound).all()


def test_pack_unpack_kv4_exact():
    rng = np.random.default_rng(6)
    codes = jnp.asarray(rng.integers(-7, 8, (3, 2, 10, 4)), jnp.int8)
    packed = QC.pack_kv4(codes)
    assert packed.shape == (3, 2, 5, 4)
    np.testing.assert_array_equal(np.asarray(QC.unpack_kv4(packed)),
                                  np.asarray(codes))


def test_attend_hf_q4_close_to_dense():
    from ollama_operator_tpu.ops import attention as A
    rng = np.random.default_rng(7)
    B, T, S, H, KvH, hd = 2, 1, 32, 8, 2, 16
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((B, KvH, S, hd)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((B, KvH, S, hd)), jnp.float32) * 0.3
    mask = jnp.broadcast_to(A.causal_mask(T, S, 20), (B, 1, T, S))
    ref = A.attend_hf(q, k, v, mask, hd ** -0.5)
    kq, ks = QC.quantize_kv4(k)
    vq, vs = QC.quantize_kv4(v)
    got = QC.attend_hf_q4(q, {"q4": QC.pack_kv4(kq), "s": ks},
                          {"q4": QC.pack_kv4(vq), "s": vs},
                          mask, hd ** -0.5)
    # 4-bit KV: looser than int8 but the attention output stays close
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0.25, atol=0.1)


def test_int4_requires_paged(params):
    with pytest.raises(ValueError):
        Engine(BASE, params, ecfg=EngineConfig(
            max_slots=2, max_seq_len=64, cache_dtype="int4",
            min_prefill_bucket=16))


def test_int4_engine_end_to_end(params):
    """int4 paged engine decodes through bucket crossings; the pool's
    code arrays are half-width (two positions per byte)."""
    ecfg = dataclasses.replace(PAGED, cache_dtype="int4")
    eng = Engine(BASE, params, ecfg=ecfg)
    t0 = eng.admit(0, SHORT, GREEDY)
    toks = [t0]
    for _ in range(4):
        toks.extend(int(x) for x in eng.decode_n(4)[:, 0])
    assert len(toks) == 17 and all(0 <= t < BASE.vocab_size for t in toks)
    k_pool = eng.k_cache[0] if isinstance(eng.k_cache, list) else eng.k_cache
    assert QC.pool_bits(k_pool) == 4
    # greedy first token agrees with the f32 engine (prefill is unquantized)
    eng2 = Engine(BASE, params, ecfg=PAGED)
    assert t0 == eng2.admit(0, SHORT, GREEDY)
