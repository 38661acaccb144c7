"""Fused dequant-matmul Pallas kernels (weight-only int8/int4).

Decode matmuls are HBM-bound: the win is streaming quantized weight
tiles (half / a quarter of bf16's bytes) into VMEM and dequantizing
in-register right before the MXU dot — the bf16 weight tensor never
exists in HBM. The XLA grouped-einsum paths (ops/quant.qmm / qmm4) are
the portable fallbacks; these kernels are the single-chip fast path,
dispatched through the same kernels switch as the flash-attention
kernels (ops/attention.py).

Grid (oi, ki), ki innermost: each step loads a (bk, bo) int8 tile (or
(bk/2, bo) packed-nibble tile) plus its (bk/g, bo) scales, dequantizes
to one tile in VMEM, and accumulates x_tile @ w_tile into an f32
scratch that persists across ki. The int4 unpack exploits the
group-local packing (ops/quant.pack_int4): low/high nibble planes are
whole half-groups, so rebuilding weight rows is one sublane-granular
concat per tile, and each packed byte is read from HBM exactly once —
the traffic halving the XLA int4 path can't get.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import note_kernel
from ..quant import GROUP, qmm, qmm4

_BLOCKS = (512, 256, 128, 64, 32)


def _pick(n: int, cap: int, multiple: int = 1):
    for b in _BLOCKS:
        if b <= cap and n % b == 0 and b % multiple == 0:
            return b
    return None


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int, g: int, cdt):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[...]                                   # [B, bk] bf16
    qb = q_ref[...]                                   # [bk, bo] int8
    sb = s_ref[...]                                   # [bk/g, bo] f32
    bk, bo = qb.shape
    # dequant in f32 (exact: int8 code x f32 scale), then drop to the
    # compute dtype for the MXU dot — bf16 operands run at full MXU rate
    # where the first kernel generation's f32 dot measured a fraction of
    # it (on-chip: int4 527.8 tok/s vs int8-XLA 569.2 despite 38% fewer
    # bytes). f32 activations (CPU tests) keep f32 for bit-stable parity.
    w = qb.astype(jnp.float32).reshape(bk // g, g, bo) * sb[:, None, :]
    w = w.reshape(bk, bo)
    acc_ref[...] += jax.lax.dot_general(
        xb.astype(cdt), w.astype(cdt), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def flush():
        o_ref[...] = acc_ref[...]


def qmm_pallas(x: jax.Array, q: jax.Array, s: jax.Array,
               interpret: bool = False) -> jax.Array:
    """x [B, K] @ dequant(q [K, O], s [K/g, O]) → [B, O] f32.

    Falls back to the XLA grouped path when the shapes don't tile cleanly
    (odd dims, tiny K/O) — callers never need to care.
    """
    B, K = x.shape
    K2, O = q.shape
    G = s.shape[0]
    g = K // G
    bk = _pick(K, 512, multiple=g) if g in (16, 32, 64, 128) else None
    bo = _pick(O, 512)
    lanes_ok = interpret or (O % 128 == 0 and bo is not None and
                             bo % 128 == 0)
    if bk is None or bo is None or not lanes_ok:
        note_kernel("matmul", "xla_int8", fell_back=True)
        return qmm(x, {"q": q, "s": s}, out_dtype=jnp.float32)
    note_kernel("matmul", "qmm_pallas")

    Bp = max(8, B)
    if Bp != B:
        x = jnp.pad(x, ((0, Bp - B), (0, 0)))
    nk = K // bk
    cdt = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, g=g, cdt=cdt),
        name="qmm_pallas",
        grid=(O // bo, nk),
        in_specs=[
            pl.BlockSpec((Bp, bk), lambda oi, ki: (0, ki)),
            pl.BlockSpec((bk, bo), lambda oi, ki: (ki, oi)),
            pl.BlockSpec((bk // g, bo), lambda oi, ki: (ki, oi)),
        ],
        out_specs=pl.BlockSpec((Bp, bo), lambda oi, ki: (0, oi)),
        out_shape=jax.ShapeDtypeStruct((Bp, O), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Bp, bo), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, q, s.astype(jnp.float32))
    return out[:B]


def _kernel4(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int, g: int, cdt):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[...]                                   # [B, bk] bf16
    qb = q_ref[...]                                   # [bk/2, bo] uint8
    sb = s_ref[...]                                   # [bk/g, bo] f32
    bkp, bo = qb.shape
    h = g // 2
    bi = qb.astype(jnp.int32).reshape(bkp // h, h, bo)
    lo = (bi & 0xF) - 8                               # rows [0, g/2) of
    hi = (bi >> 4) - 8                                # each group; [g/2, g)
    w = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)
    w = (w * sb[:, None, :]).reshape(2 * bkp, bo)
    # bf16 dot for the MXU (see _kernel); f32 x keeps f32 parity
    acc_ref[...] += jax.lax.dot_general(
        xb.astype(cdt), w.astype(cdt), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def flush():
        o_ref[...] = acc_ref[...]


def qmm4_pallas(x: jax.Array, q4: jax.Array, s: jax.Array,
                interpret: bool = False) -> jax.Array:
    """x [B, K] @ dequant(q4 [K/2, O] packed, s [K/g, O]) → [B, O] f32.

    Falls back to the XLA grouped path when the shapes don't tile cleanly
    (odd dims, tiny K/O) — callers never need to care.
    """
    B, K = x.shape
    Kp, O = q4.shape
    assert 2 * Kp == K, (Kp, K)
    G = s.shape[0]
    g = K // G
    # bk % 2g keeps the packed tile's sublane count a multiple of g —
    # no partial groups, and the uint8 tile stays (32, 128)-tileable
    bk = _pick(K, 512, multiple=2 * g) if g in (16, 32, 64, 128) else None
    bo = _pick(O, 512)
    lanes_ok = interpret or (O % 128 == 0 and bo is not None and
                             bo % 128 == 0)
    if bk is None or bo is None or not lanes_ok:
        note_kernel("matmul", "xla_int4", fell_back=True)
        return qmm4(x, {"q4": q4, "s": s}, out_dtype=jnp.float32)
    note_kernel("matmul", "qmm4_pallas")

    Bp = max(8, B)
    if Bp != B:
        x = jnp.pad(x, ((0, Bp - B), (0, 0)))
    nk = K // bk
    cdt = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32

    out = pl.pallas_call(
        functools.partial(_kernel4, nk=nk, g=g, cdt=cdt),
        name="qmm4_pallas",
        grid=(O // bo, nk),
        in_specs=[
            pl.BlockSpec((Bp, bk), lambda oi, ki: (0, ki)),
            pl.BlockSpec((bk // 2, bo), lambda oi, ki: (ki, oi)),
            pl.BlockSpec((bk // g, bo), lambda oi, ki: (ki, oi)),
        ],
        out_specs=pl.BlockSpec((Bp, bo), lambda oi, ki: (0, oi)),
        out_shape=jax.ShapeDtypeStruct((Bp, O), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Bp, bo), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, q4, s.astype(jnp.float32))
    return out[:B]
