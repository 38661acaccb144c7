"""Ring attention + sequence-parallel decode collectives.

Long-context capability the reference lacks entirely (SURVEY.md §5
"Long-context / sequence parallelism: ABSENT" — the reference inherits
whatever context llama.cpp defaults to inside the delegated `ollama/ollama`
image, /root/reference/pkg/model/pod.go:11). Here the sequence axis is
sharded over the mesh's ``sp`` axis so prompts / KV caches larger than one
chip's HBM span the slice over ICI.

Two primitives, both designed to run INSIDE a ``jax.shard_map`` region that
is manual over ``sp`` (and only ``sp`` — tp/dp stay GSPMD-auto, so the
Megatron TP sharding of the closed-over weights keeps working around these
calls; see parallel/long_context.py for the wrappers):

- ``ring_attention``: causal flash attention for sequence-sharded prefill.
  Each device holds one contiguous chunk of Q and of K/V; K/V chunks rotate
  around the ring via ``lax.ppermute`` while an fp32 online-softmax carry
  (running max ``m``, normaliser ``l``, accumulator ``acc``) stays put with
  Q. Blocks that the causal structure (or a sliding window) makes fully
  invisible are skipped with ``lax.cond`` — compute AND the softmax update
  are elided, only the ring DMA still moves.

- ``sp_decode_attention``: decode against a sequence-sharded KV cache. Each
  device computes a flash partial (m, l, acc) over its local cache chunk,
  then one ``pmax`` + two ``psum`` combine the partials exactly — the
  per-step collective traffic is O(B·H·hd), independent of context length.

Chunking convention: contiguous ("chunked") sharding — device i owns
absolute positions [i·C, (i+1)·C). The causal skip makes the compute
triangular rather than balanced; a zig-zag layout would balance it but
complicates the KV-cache write path, so round 1 keeps the simple layout.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import NEG_INF, softcap_scores

_FP32 = jnp.float32


def _accum(carry, q, k, v, mask, scale: float, softcap: float,
           k_s=None, v_s=None):
    """One online-softmax accumulation step.

    carry: (m [B,KvH,G,T], l [B,KvH,G,T], acc [B,KvH,G,T,hd]) fp32
    q [B,T,H,hd]; k/v head-first [B,KvH,S,hd]; mask [B,T,S] additive fp32.
    ``k_s``/``v_s`` [B,KvH,S] — per-position dequant scales for int8
    chunks (ops/quant_cache.py convention: the key scale factors out of
    the q·k dot onto the scores; the value scale folds into the
    probabilities — dequantized tensors never materialise).
    """
    m, l, acc = carry
    B, T, H, hd = q.shape
    KvH = k.shape[1]
    G = H // KvH
    qg = q.reshape(B, T, KvH, G, hd)
    kc = k.astype(q.dtype) if k_s is not None else k
    s = jnp.einsum("btkgh,bksh->bkgts", qg, kc, preferred_element_type=_FP32)
    if k_s is not None:
        s = s * k_s[:, :, None, None, :]
    s = softcap_scores(s * scale, softcap)
    s = s + mask[:, None, None, :, :]
    m_new = jnp.maximum(m, s.max(axis=-1))
    # m_new can still be NEG_INF when nothing is visible yet; keep exp args
    # finite so p/alpha are exactly 0/1 rather than NaN.
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l = l * alpha + p.sum(axis=-1)
    if v_s is not None:
        p = p * v_s[:, :, None, None, :]
        vc = v.astype(q.dtype)
    else:
        vc = v
    acc = acc * alpha[..., None] + jnp.einsum(
        "bkgts,bksh->bkgth", p.astype(vc.dtype), vc,
        preferred_element_type=_FP32)
    return m_new, l, acc


def _finish(carry, B, T, H, hd):
    m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    # [B,KvH,G,T,hd] -> [B,T,H,hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd)


def ring_attention(q, k, v, scale: float, axis_name: str = "sp",
                   softcap: float = 0.0, sliding_window: int = 0):
    """Causal ring flash attention over sequence-sharded chunks.

    Per-device shapes (inside shard_map, manual over ``axis_name``):
      q      [B, Tc, H, hd]   — this device's query chunk
      k, v   [B, KvH, Tc, hd] — this device's key/value chunk (head-first)
    Device i owns absolute positions [i·Tc, (i+1)·Tc). Returns [B,Tc,H,hd]
    in q.dtype — bitwise semantics of dense causal attention over the full
    sequence.
    """
    sp = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tc, H, hd = q.shape
    KvH = k.shape[1]
    G = H // KvH

    q_pos = my * Tc + jnp.arange(Tc, dtype=jnp.int32)          # [Tc]
    carry = (jnp.full((B, KvH, G, Tc), NEG_INF, _FP32),
             jnp.zeros((B, KvH, G, Tc), _FP32),
             jnp.zeros((B, KvH, G, Tc, hd), _FP32))
    # the accumulated carry is device-varying (per-chunk); mark the literal
    # init as such so both lax.cond branches type-check under check_vma
    carry = jax.tree.map(
        lambda a: lax.pcast(a, (axis_name,), to="varying"), carry)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    for step in range(sp):
        src = (my - step) % sp            # origin of the chunk we now hold
        k_pos = src * Tc + jnp.arange(Tc, dtype=jnp.int32)     # [Tc]
        ok = k_pos[None, :] <= q_pos[:, None]
        if sliding_window:
            ok = ok & (k_pos[None, :] > q_pos[:, None] - sliding_window)
        mask = jnp.where(ok, 0.0, NEG_INF).astype(_FP32)
        mask = jnp.broadcast_to(mask[None], (B, Tc, Tc))

        # Block-level visibility: any (q, k) pair unmasked? Causal: the
        # earliest key must not exceed the latest query; window: the latest
        # key must be inside the window of the earliest query.
        visible = (src * Tc) <= (my * Tc + Tc - 1)
        if sliding_window:
            visible = visible & ((src * Tc + Tc - 1) >
                                 (my * Tc - sliding_window))
        carry = lax.cond(
            visible,
            lambda c, kk, vv, mm: _accum(c, q, kk, vv, mm, scale, softcap),
            lambda c, kk, vv, mm: c,
            carry, k, v, mask)

        if step < sp - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)

    return _finish(carry, B, Tc, H, hd).astype(q.dtype)


def sp_decode_attention(q, k_chunk, v_chunk, q_pos, scale: float,
                        axis_name: str = "sp", softcap: float = 0.0,
                        sliding_window: int = 0):
    """Attention of fresh tokens against a sequence-sharded KV cache.

    Per-device shapes (inside shard_map, manual over ``axis_name``):
      q                [B, T, H, hd]    — replicated across sp (T=1 decode,
                                          T>1 chunked continuation)
      k_chunk, v_chunk [B, KvH, Sc, hd] — local cache chunk; device i holds
                                          absolute slots [i·Sc, (i+1)·Sc)
      q_pos            [B, T] int32     — absolute positions of the queries
    Returns [B, T, H, hd] replicated across sp (psum-combined partials).
    """
    my = lax.axis_index(axis_name)
    B, T, H, hd = q.shape
    quant = isinstance(k_chunk, dict)
    k_s = k_chunk["s"] if quant else None
    v_s = v_chunk["s"] if quant else None
    if quant:
        k_chunk, v_chunk = k_chunk["q"], v_chunk["q"]
    KvH, Sc = k_chunk.shape[1], k_chunk.shape[2]
    G = H // KvH

    k_pos = my * Sc + jnp.arange(Sc, dtype=jnp.int32)          # [Sc]
    ok = k_pos[None, None, :] <= q_pos[:, :, None]             # [B,T,Sc]
    if sliding_window:
        ok = ok & (k_pos[None, None, :] > q_pos[:, :, None] - sliding_window)
    mask = jnp.where(ok, 0.0, NEG_INF).astype(_FP32)

    # local flash partial = one _accum step from an empty carry
    zero = (jnp.full((B, KvH, G, T), NEG_INF, _FP32),
            jnp.zeros((B, KvH, G, T), _FP32),
            jnp.zeros((B, KvH, G, T, hd), _FP32))
    m_loc, l_loc, acc_loc = _accum(zero, q, k_chunk, v_chunk, mask, scale,
                                   softcap, k_s=k_s, v_s=v_s)

    m_g = lax.pmax(m_loc, axis_name)
    corr = jnp.exp(m_loc - m_g)                                # 0 when local
    l_g = lax.psum(l_loc * corr, axis_name)                    # chunk empty
    acc_g = lax.psum(acc_loc * corr[..., None], axis_name)

    out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd).astype(q.dtype)


def sp_cache_write(k_cache, v_cache, k_new, v_new, write_pos,
                   axis_name: str = "sp"):
    """Write fresh K/V into a sequence-sharded cache chunk.

    k_cache/v_cache [B, KvH, Sc, hd] — local chunk (device i owns absolute
    slots [i·Sc, (i+1)·Sc)), or int8 dicts {"q": entries, "s": [B,KvH,Sc]
    scales} — fresh K/V is then quantized before the scatter; k_new/v_new
    [B, KvH, T, hd] replicated across sp; write_pos [B, T] absolute slots.
    Positions outside the local chunk are dropped (they land on the owning
    device instead).
    """
    my = lax.axis_index(axis_name)
    quant = isinstance(k_cache, dict)
    Sc = (k_cache["q"] if quant else k_cache).shape[2]
    B, KvH = k_new.shape[0], k_new.shape[1]
    local = write_pos - my * Sc                                # [B,T]
    # mode="drop" discards scatters whose local index is outside [0, Sc) —
    # they belong to another shard — but negative indices would wrap
    # (numpy semantics) before the bounds check, so send them out of bounds
    # explicitly. (No clip-then-select: clipping would alias a dropped write
    # onto the chunk-boundary slot, and duplicate scatter indices have
    # undefined update order.)
    local = jnp.where(local < 0, Sc, local)
    bidx = jnp.arange(B)[:, None, None]
    hidx = jnp.arange(KvH)[None, :, None]
    pidx = local[:, None, :]
    if quant:
        from ..ops.quant_cache import quantize_kv
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        k_cache = {"q": k_cache["q"].at[bidx, hidx, pidx].set(
                       kq, mode="drop"),
                   "s": k_cache["s"].at[bidx, hidx, pidx].set(
                       ks, mode="drop")}
        v_cache = {"q": v_cache["q"].at[bidx, hidx, pidx].set(
                       vq, mode="drop"),
                   "s": v_cache["s"].at[bidx, hidx, pidx].set(
                       vs, mode="drop")}
        return k_cache, v_cache
    k_cache = k_cache.at[bidx, hidx, pidx].set(
        k_new.astype(k_cache.dtype), mode="drop")
    v_cache = v_cache.at[bidx, hidx, pidx].set(
        v_new.astype(v_cache.dtype), mode="drop")
    return k_cache, v_cache
