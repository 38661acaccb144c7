"""Sequence-parallel decoder forwards (long-context serving).

`shard_map` wrappers around the decoder's building blocks that shard the
sequence axis over the mesh's ``sp`` axis: prefill runs ring attention
(K/V chunks rotating over ICI, parallel/ring_attention.py) and decode runs
against a sequence-sharded KV cache with an exact flash-partial combine.
The wrappers are manual over ``sp`` ONLY — dp/tp stay GSPMD-auto, so the
closed-over params keep their Megatron TP sharding (parallel/sharding.py)
and XLA still inserts the tp all-reduces inside the manual region.

This is a new capability over the reference, whose context length is
whatever llama.cpp defaults to inside the delegated image (SURVEY.md §5):
here a Model CR's ``contextLength`` can exceed one chip's HBM and the cache
spans the slice.

Semantics match models/decoder.py exactly (tests/test_ring_attention.py
asserts logits and caches agree with the dense single-device path).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.decoder import (Params, _attn_scale, _block_cached,
                              _block_chunk, _embed, _unembed)
from ..ops.rope import rope_angles_cfg
from .ring_attention import (ring_attention, sp_cache_write,
                             sp_decode_attention)

SP_AXIS = "sp"


def prefill_chunk_sp(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     mesh: Mesh, inputs_embeds: jax.Array = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel twin of ``decoder.prefill_chunk``.

    tokens [B, T] with T divisible by mesh sp; returns (logits [B,T,V] fp32,
    k [L,B,KvH,T,hd], v [...]) — logits and K/V sharded over ``sp`` along
    their sequence axis. ``inputs_embeds`` [B, T, D] (multimodal prompts)
    replaces the embedding lookup; it shards over sp along T like tokens.
    """
    sp = mesh.shape[SP_AXIS]
    B, T = tokens.shape
    assert T % sp == 0, f"prefill length {T} must divide sp={sp}"
    if cfg.altern_sliding:
        raise NotImplementedError(
            "per-layer alternating windows / dual rope (gemma2, gemma3) are not implemented "
            "on the sequence-parallel path")
    scale = _attn_scale(cfg)

    def inner(tokens, inputs_embeds):
        my = lax.axis_index(SP_AXIS)
        Bc, Tc = tokens.shape
        positions = my * Tc + jnp.arange(Tc, dtype=jnp.int32)
        positions = jnp.broadcast_to(positions[None], (Bc, Tc))
        cos, sin = rope_angles_cfg(positions, cfg)
        if inputs_embeds is not None:
            x = inputs_embeds.astype(params["tok_emb"].dtype)
        else:
            x = _embed(cfg, params, tokens)

        def attn_fn(q, k, v):
            return ring_attention(q, k, v, scale, SP_AXIS, cfg.attn_softcap,
                                  cfg.sliding_window)

        def body(x, lp):
            return _block_chunk(cfg, lp, x, cos, sin, None, scale,
                                attn_fn=attn_fn)

        x, (ks, vs) = lax.scan(body, x, params["layers"])
        logits = _unembed(cfg, params, x)
        return logits, ks, vs

    seq_spec = P(None, None, None, SP_AXIS, None)   # [L,B,KvH,T@sp,hd]
    emb_spec = None if inputs_embeds is None else P(None, SP_AXIS, None)
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, SP_AXIS), emb_spec),
        out_specs=(P(None, SP_AXIS, None), seq_spec, seq_spec),
        axis_names={SP_AXIS}, check_vma=False)(tokens, inputs_embeds)


def forward_with_cache_sp(params: Params, cfg: ModelConfig,
                          tokens: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, lengths: jax.Array,
                          mesh: Mesh
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel twin of ``decoder.forward_with_cache``.

    k_cache/v_cache [L,B,KvH,S,hd] sharded over ``sp`` along S — dense, or
    int8 dicts {"q", "s": [L,B,KvH,S]} (the sp collectives quantize fresh
    K/V and dequantize via scales folded into scores/probs, closing
    round-1's int8×sp exclusion). The fresh tokens' compute is replicated
    across sp (decode is memory-bound; sp exists for HBM capacity) — only
    the cache reads/writes are sharded.
    Returns (logits [B,T,V] replicated, k_cache, v_cache).
    """
    if cfg.altern_sliding:
        raise NotImplementedError(
            "per-layer alternating windows / dual rope (gemma2, gemma3) are not implemented "
            "on the sequence-parallel path")
    scale = _attn_scale(cfg)
    quant = isinstance(k_cache, dict)

    def inner(tokens, k_cache, v_cache, lengths):
        B, T = tokens.shape
        positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        cos, sin = rope_angles_cfg(positions, cfg)
        x = _embed(cfg, params, tokens)

        def attn_fn(q, kc, vc, pos):
            return sp_decode_attention(q, kc, vc, pos, scale, SP_AXIS,
                                       cfg.attn_softcap, cfg.sliding_window)

        def write_fn(kc, vc, k, v, pos):
            return sp_cache_write(kc, vc, k, v, pos, SP_AXIS)

        def body(x, layer_in):
            lp, kc, vc = layer_in
            x, kc, vc = _block_cached(cfg, lp, x, cos, sin, kc, vc,
                                      positions, None, scale,
                                      attn_fn=attn_fn, write_fn=write_fn)
            return x, (kc, vc)

        x, (k_cache, v_cache) = lax.scan(
            body, x, (params["layers"], k_cache, v_cache))
        logits = _unembed(cfg, params, x)
        return logits, k_cache, v_cache

    cache_spec = P(None, None, None, SP_AXIS, None)
    if quant:
        cache_spec = {"q": cache_spec,
                      "s": P(None, None, None, SP_AXIS)}
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, None), cache_spec, cache_spec, P(None)),
        out_specs=(P(None, None, None), cache_spec, cache_spec),
        axis_names={SP_AXIS}, check_vma=False)(
        tokens, k_cache, v_cache, lengths)
