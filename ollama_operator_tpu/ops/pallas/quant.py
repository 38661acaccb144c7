"""Fused dequant-matmul Pallas kernels (weight-only int8/int4).

A weight-only matmul is bound by the weight's bytes until the row count
reaches the hundreds: the win is streaming quantized weight tiles (half /
a quarter of bf16's bytes) HBM -> VMEM and dequantizing there, right
before the MXU dot, so a dequantized weight never exists in HBM. These
kernels are the single-chip path of ``ops/quant.matmul`` for int4 at every
row count and for int8 above ``ops/quant.GROUPED_MAX_ROWS`` rows (batched
decode, admits, extends, verifies); the XLA forms (ops/quant.qmm / qmm4)
serve GSPMD meshes, other backends and shapes that do not tile.

Grid (mi, oi, ki), ki innermost: each step loads a (bk, bo) int8 tile (or
(bk/2, bo) packed-nibble tile) plus its (bk/g, bo) f32 scales, dequantizes
it chunk by chunk (f32 code x f32 scale, cast to the dot's operand type),
and accumulates x_tile @ w_tile into an f32 scratch that persists across
ki. mi walks blocks of at most ``_MAX_ROWS`` rows of x, so any row count
compiles inside the VMEM limit; the weight is read, and dequantized, once
per row block. The int4 unpack exploits the group-local packing
(ops/quant.pack_int4): low/high nibble planes are whole half-groups, so
rebuilding weight rows is one sublane-granular concat per chunk, and each
packed byte is read from HBM exactly once per row block.

The weight may be a stack of layers and an index (``layer=``): the index
is a prefetched scalar in the weight's index maps, so the kernel reads its
layer where it lies. A pallas_call cannot fuse a slice of its operand as an
XLA consumer does, and the decoder's layer scan would otherwise copy every
layer's weights once more than the kernel reads them
(models/decoder.py _scan_layers).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import note_kernel
from ..quant import layer_of, qmm, qmm4

# rows of x per block: one block up to here, equal blocks above. At 512
# rows a weight tile's dot outlasts its dequantization several times over,
# so re-doing the dequantization per row block is noise.
_MAX_ROWS = 512
# K rows dequantized at a time inside a tile: keeps the f32 intermediate
# a few hundred KB whatever the tile, and its scale rows (chunk / g) a
# whole f32 sublane tile for g = 32
_CHUNK = 256


def _pick(n: int, cap: int, step: int):
    """Largest multiple of ``step`` that divides n and is at most cap."""
    for b in range(min(cap, n) // step * step, 0, -step):
        if n % b == 0:
            return b
    return None


def _row_blocks(B: int):
    """(block rows, blocks): equal blocks of at most _MAX_ROWS rows, each a
    multiple of 16 (a bf16 sublane tile)."""
    nm = -(-B // _MAX_ROWS)
    bm = -(-B // (16 * nm)) * 16
    return bm, nm


def _dequant8(qb, sb, g: int):
    ck, bo = qb.shape                                 # int8 [ck, bo]
    w = qb.astype(jnp.float32).reshape(ck // g, g, bo) * sb[:, None, :]
    return w.reshape(ck, bo)


def _dequant4(qb, sb, g: int):
    ckp, bo = qb.shape                                # uint8 [ck/2, bo]
    h = g // 2
    bi = qb.astype(jnp.int32).reshape(ckp // h, h, bo)
    lo = (bi & 0xF) - 8                               # rows [0, g/2) of
    hi = (bi >> 4) - 8                                # each group; [g/2, g)
    w = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)
    return (w * sb[:, None, :]).reshape(2 * ckp, bo)


def _kernel(_layer_ref, x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int,
            g: int, cdt, dequant, pack: int, ck: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk = x_ref.shape[1]
    part = None
    for c in range(0, bk, ck):
        # dequant in f32 (exact: code x f32 scale), then drop to the
        # compute dtype for the MXU dot — bf16 operands run at full MXU
        # rate where the first kernel generation's f32 dot measured a
        # fraction of it. f32 activations (CPU tests) keep f32 for
        # bit-stable parity.
        w = dequant(q_ref[c // pack:(c + ck) // pack, :],
                    s_ref[c // g:(c + ck) // g, :], g)
        d = jax.lax.dot_general(
            x_ref[:, c:c + ck].astype(cdt), w.astype(cdt),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        part = d if part is None else part + d
    acc_ref[...] += part

    @pl.when(ki == nk - 1)
    def flush():
        o_ref[...] = acc_ref[...]


def _fused(name: str, dequant, pack: int, x, q, s, g: int, bk: int, bo: int,
           interpret: bool, layer):
    """q and s are one weight or, with ``layer`` (an int32 scalar), stacks
    [L, ...] of which the kernel reads that layer where it lies: the index
    is a prefetched scalar in the weight's index maps, so no slice of the
    stack is ever copied."""
    B, K = x.shape
    O = q.shape[-1]
    if layer is None:
        q, s, layer = q[None], s[None], 0
    bm, nm = _row_blocks(B)
    if bm * nm != B:
        x = jnp.pad(x, ((0, bm * nm - B), (0, 0)))
    nk = K // bk
    ck = _CHUNK if bk % _CHUNK == 0 else bk
    cdt = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32

    def wmap(mi, oi, ki, l):
        return l[0], ki, oi

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, g=g, cdt=cdt, dequant=dequant,
                          pack=pack, ck=ck),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nm, O // bo, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda mi, oi, ki, l: (mi, ki)),
                pl.BlockSpec((None, bk // pack, bo), wmap),
                pl.BlockSpec((None, bk // g, bo), wmap),
            ],
            out_specs=pl.BlockSpec((bm, bo), lambda mi, oi, ki, l: (mi, oi)),
            scratch_shapes=[pltpu.VMEM((bm, bo), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bm * nm, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, q, s.astype(jnp.float32))
    return out[:B]


def _tiles(B: int, K: int, O: int, G: int, bits: int, interpret: bool):
    """(bk, bo) of the weight tile the fused kernel runs x [B, K] @ w [K, O]
    (G scale groups) with, or None where the shape does not tile: odd dims,
    tiny K/O, an output that is no multiple of the 128 lanes.

    As large as the default 16 MiB of VMEM takes beside the row block (x,
    accumulator, output: they grow with the rows, so the tile shrinks),
    because a grid step costs ~0.35 us whatever it moves: w_up at 64 rows
    read 118 us in (512, 512) tiles and 98 in (1024, 2048)
    (hack/qmm_microbench.py --tiles). Any divisor serves, not only powers
    of two: phi-2's K = 2560 tiles by 512, its 7680 outputs by 1920. On the
    chip bk is a multiple of _CHUNK (whole f32 sublane tiles of scale rows)
    and bo of the 128 lanes; the interpreter also takes multiples of the
    group and of 32."""
    g = K // G
    if g not in (16, 32, 64, 128):
        return None
    bm = _row_blocks(B)[0]
    cap_k = 1024 if bm <= 256 else 512
    cap_o = 2048 if bm <= 128 else 1024
    bk = _pick(K, cap_k, _CHUNK)
    bo = _pick(O, cap_o, 128)
    if interpret:
        # int4: bk % 2g keeps the packed tile's sublane count a multiple
        # of g — no partial groups
        bk = bk or _pick(K, cap_k, g if bits == 8 else 2 * g)
        bo = bo or _pick(O, cap_o, 32)
    if bk is None or bo is None:
        return None
    return bk, bo


def qmm_pallas(x: jax.Array, q: jax.Array, s: jax.Array,
               interpret: bool = False, layer=None) -> jax.Array:
    """x [B, K] @ dequant(q [K, O], s [K/g, O]) → [B, O] f32.

    With ``layer`` (an int32 scalar, traced or not) q and s are stacks
    [L, K, O] / [L, K/g, O] and the kernel reads that layer where it lies.
    Falls back to the XLA path when the shapes don't tile cleanly (odd
    dims, tiny K/O) — callers never need to care.
    """
    K, O = q.shape[-2:]
    G = s.shape[-2]
    tiles = _tiles(x.shape[0], K, O, G, 8, interpret)
    if tiles is None:
        note_kernel("matmul", "xla_int8", fell_back=True)
        return qmm(x, {"q": layer_of(q, layer), "s": layer_of(s, layer)},
                   out_dtype=jnp.float32)
    note_kernel("matmul", "qmm_pallas")
    return _fused("qmm_pallas", _dequant8, 1, x, q, s, K // G, *tiles,
                  interpret, layer)


def qmm4_pallas(x: jax.Array, q4: jax.Array, s: jax.Array,
                interpret: bool = False, layer=None) -> jax.Array:
    """x [B, K] @ dequant(q4 [K/2, O] packed, s [K/g, O]) → [B, O] f32.

    ``layer`` as in ``qmm_pallas``. Falls back to the XLA path when the
    shapes don't tile cleanly (odd dims, tiny K/O) — callers never need to
    care.
    """
    K = x.shape[1]
    Kp, O = q4.shape[-2:]
    assert 2 * Kp == K, (Kp, K)
    G = s.shape[-2]
    tiles = _tiles(x.shape[0], K, O, G, 4, interpret)
    if tiles is None:
        note_kernel("matmul", "xla_int4", fell_back=True)
        return qmm4(x, {"q4": layer_of(q4, layer), "s": layer_of(s, layer)},
                    out_dtype=jnp.float32)
    note_kernel("matmul", "qmm4_pallas")
    return _fused("qmm4_pallas", _dequant4, 2, x, q4, s, K // G, *tiles,
                  interpret, layer)
