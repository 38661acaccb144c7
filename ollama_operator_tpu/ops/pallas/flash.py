"""Flash-attention Pallas kernels: causal GQA prefill + cached decode.

Both kernels keep the classic flash structure — stream K/V blocks through
VMEM, fp32 online softmax (running max ``m``, normaliser ``l``, accumulator
``acc`` in VMEM scratch that persists across the innermost grid dimension) —
with two TPU-specific tricks:

- **Causal / length DMA elision.** The K/V block index map clamps the block
  index to the last block the current query can see; Pallas elides the DMA
  when consecutive grid steps map to the same block, so fully-masked tail
  blocks cost neither bandwidth nor compute (the ``@pl.when`` guard skips
  the math).
- **Scalar-prefetched lengths (decode).** Slot lengths ride in SMEM via
  ``PrefetchScalarGridSpec`` so the clamp above can depend on the per-slot
  length — a slot at position 100 in a 4096-slot cache reads 1 block, not 16.

Layout: K/V are **head-first** ([B, KvH, S, hd] — the KV-cache layout the
whole serving stack uses) so every block is a (seq, head_dim) tile, the
natural (sublane, lane) orientation for the MXU. GQA never repeats K/V:
prefill points each query head's K/V spec at ``head // group``; decode lays
q out as [B, KvH, G, hd].

The reference delegates these ops to llama.cpp's C++/CUDA kernels inside
the `ollama/ollama` image (/root/reference/pkg/model/pod.go:11).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF, softcap_scores

_BLOCKS = (512, 256, 128, 64, 32, 16, 8)


def _pick_block(n: int, cap: int) -> Optional[int]:
    for b in _BLOCKS:
        if b <= cap and n % b == 0:
            return b
    return None


def _lane_ok(hd: int, interpret: bool) -> bool:
    # Mosaic pads the trailing (lane) dim to 128 internally, so any
    # 16-multiple head dim compiles and runs correctly on TPU (verified on
    # v5e for 64/80/96 — phi's hd=80 included); the padding costs some
    # VMEM but the length-clamped DMA elision is a far bigger win than the
    # XLA path's full-cache reads. Truly odd dims still fall back.
    return interpret or hd % 16 == 0


def prefill_tileable(T: int, H: int, KvH: int, hd: int, interpret: bool,
                     block_q: int = 256, block_k: int = 512) -> bool:
    """True iff flash_prefill will NOT bail for these (possibly
    device-local) shapes — checked BEFORE entering a shard_map region,
    where a mid-trace None-fallback is no longer possible."""
    return (KvH > 0 and H % KvH == 0 and _lane_ok(hd, interpret)
            and _pick_block(T, block_q) is not None
            and _pick_block(T, block_k) is not None)


def decode_tileable(S: int, H: int, KvH: int, hd: int, interpret: bool,
                    block_k: int = 512) -> bool:
    """True iff decode_attention will NOT bail (see prefill_tileable)."""
    return (KvH > 0 and H % KvH == 0 and _lane_ok(hd, interpret)
            and _pick_block(S, block_k) is not None)


# ---------------------------------------------------------------------------
# prefill: causal self-attention over a fresh chunk (positions [0, T))
# ---------------------------------------------------------------------------

def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                    scale: float, softcap: float, window: int,
                    bq: int, bk: int, nk: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    k_start = ki * bk
    needed = k_start <= (qi + 1) * bq - 1  # block overlaps the causal tri
    if window:
        # any (q, k) pair in range: k_end > min_q_pos - window
        needed = jnp.logical_and(needed, k_start + bk - 1 > qi * bq - window)

    @pl.when(needed)
    def _step():
        q = q_ref[0, 0, :, :]                                # [bq, hd]
        k = k_ref[0, 0, :, :]                                # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        s = softcap_scores(s, softcap)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = k_pos <= q_pos
        if window:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:]                                     # [bq, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # rows with no valid key yet keep m == NEG_INF; exp would turn the
        # masked NEG_INF scores into 1s, so gate p on a live running max.
        p = jnp.where(m_cur > NEG_INF / 2, jnp.exp(s - m_cur), 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0, :, :]                                 # [bk, hd]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_cur

    @pl.when(ki == nk - 1)
    def _done():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


def flash_prefill(q, k, v, scale: float, softcap: float = 0.0,
                  sliding_window: int = 0, *, block_q: int = 256,
                  block_k: int = 512, interpret: bool = False):
    """Causal GQA self-attention for a fresh chunk.

    q [B, T, H, hd], k/v head-first [B, KvH, T, hd] → [B, T, H, hd]
    (q.dtype). Query i attends keys j <= i (positions are chunk-local,
    offset 0), optionally within ``sliding_window``. Returns None when the
    shapes don't tile (caller falls back to the XLA path).
    """
    B, T, H, hd = q.shape
    KvH = k.shape[1]
    if not prefill_tileable(T, H, KvH, hd, interpret, block_q, block_k):
        return None
    bq = _pick_block(T, block_q)
    bk = _pick_block(T, block_k)
    G = H // KvH
    nq, nk = T // bq, T // bk
    q_hf = q.transpose(0, 2, 1, 3)                            # [B, H, T, hd]

    def kv_index(b, h, qi, ki):
        # clamp to the last causally-needed block → tail DMAs are elided
        last = ((qi + 1) * bq - 1) // bk
        return (b, h // G, jnp.minimum(ki, last), 0)

    kernel = functools.partial(
        _prefill_kernel, scale=scale, softcap=softcap,
        window=sliding_window, bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        name="flash_prefill",
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q_hf, k, v)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# decode: one new query per slot against the slot's KV cache rows
# ---------------------------------------------------------------------------

def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, softcap: float, window: int,
                   bk: int, nk: int):
    b, ki = pl.program_id(0), pl.program_id(2)
    qp = qpos_ref[b]                       # query's absolute position

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    k_start = ki * bk
    needed = k_start <= qp                 # keys j <= qp are visible
    if window:
        needed = jnp.logical_and(needed, k_start + bk - 1 > qp - window)

    @pl.when(needed)
    def _step():
        q = q_ref[0, 0, :, :]                                # [Gp, hd]
        kb = k_ref[0, 0, :, :]                                # [bk, hd]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [Gp, bk]
        s = softcap_scores(s, softcap)
        Gp = s.shape[0]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (Gp, bk), 1)
        ok = k_pos <= qp
        if window:
            ok = jnp.logical_and(ok, k_pos > qp - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(m_cur > NEG_INF / 2, jnp.exp(s - m_cur), 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        vb = v_ref[0, 0, :, :]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_cur

    @pl.when(ki == nk - 1)
    def _done():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, q_pos, scale: float,
                     softcap: float = 0.0, sliding_window: int = 0, *,
                     block_k: int = 512, interpret: bool = False):
    """Single-token GQA attention against the head-first slot KV cache.

    q [B, 1, H, hd]; k_cache/v_cache [B, KvH, S, hd]; q_pos [B] int32 —
    the query's absolute position (keys at j <= q_pos are attended; blocks
    beyond are neither read nor computed). → [B, 1, H, hd] (q.dtype).
    Returns None when the shapes don't tile.
    """
    B, T, H, hd = q.shape
    KvH, S = k_cache.shape[1], k_cache.shape[2]
    if T != 1 or not decode_tileable(S, H, KvH, hd, interpret, block_k):
        return None
    bk = _pick_block(S, block_k)
    G = H // KvH
    Gp = max(8, -(-G // 8) * 8)            # pad group to a sublane multiple
    nk = S // bk

    qg = q.reshape(B, KvH, G, hd)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    def kv_index(b, h, ki, qpos_ref):
        last = qpos_ref[b] // bk           # last visible block for this slot
        return (b, h, jnp.minimum(ki, last), 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, softcap=softcap,
        window=sliding_window, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KvH, nk),
            in_specs=[
                pl.BlockSpec((1, 1, Gp, hd),
                             lambda b, h, ki, qpos_ref: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, hd), kv_index),
                pl.BlockSpec((1, 1, bk, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, Gp, hd),
                                   lambda b, h, ki, qpos_ref: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Gp, hd), jnp.float32),
                pltpu.VMEM((Gp, 1), jnp.float32),
                pltpu.VMEM((Gp, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KvH, Gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), qg, k_cache, v_cache)
    return out[:, :, :G, :].reshape(B, 1, H, hd)

