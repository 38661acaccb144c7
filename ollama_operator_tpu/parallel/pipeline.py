"""Pipeline-parallel decoder forwards (layer stages over the ``pp`` axis).

The reference has no pipeline concept at all — its only scale-out is
independent replicas behind a Service (/root/reference/pkg/model/model.go:72,
SURVEY.md §2.3). This module is new TPU-native capability: it lets a model
whose weights exceed one host's HBM span hosts along the *layer* axis, where
the only inter-stage traffic is one [b, T, D] activation ppermute per
microbatch per tick — point-to-point, tolerant of DCN between hosts (unlike
tp's per-layer all-reduces, which need ICI).

Design (GPipe-style schedule, SPMD formulation):
- Layer-stacked params [L, ...] are reshaped to [pp, L/pp, ...] and passed
  into a ``jax.shard_map`` manual over ``pp`` ONLY — each device holds its
  stage's layers. Non-layer params (embeddings, norms, lm_head) are closed
  over and keep their GSPMD sharding (Megatron tp stays live inside the
  manual region, same trick as long_context.py).
- The KV cache [L, B, KvH, S, hd] is likewise stage-sharded on L.
- The batch is cut into M microbatches of b = B/M rows. A static loop of
  M + pp - 1 ticks runs: at tick t, stage s processes microbatch m = t - s
  (a masked no-op outside [0, M)), then ppermutes its activation to stage
  s+1. Stage 0 ingests (embeds) microbatch t; the last stage accumulates
  final hidden states, psum-broadcast after the loop so the unembed runs
  replicated (or tp-sharded) outside the manual region.

All control flow is static — the schedule compiles to one XLA program with
a fori_loop, no host round-trips between ticks.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import dataclasses

from ..models.config import ModelConfig
from ..models.decoder import _attn_scale, Params, _block_cached, _embed, _unembed
from ..ops.rope import rope_angles_cfg
from .sharding import resolve_moe_impl

PP_AXIS = "pp"


def split_stages(layer_params, pp: int):
    """Reshape every stacked layer leaf [L, ...] → [pp, L/pp, ...]."""
    def r(a):
        L = a.shape[0]
        assert L % pp == 0, f"pp={pp} must divide n_layers {L}"
        return a.reshape(pp, L // pp, *a.shape[1:])
    return jax.tree_util.tree_map(r, layer_params)


def merge_stages(layer_params):
    """Inverse of split_stages: [pp, L/pp, ...] → [L, ...]."""
    def r(a):
        return a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])
    return jax.tree_util.tree_map(r, layer_params)


def forward_with_cache_pp(params: Params, cfg: ModelConfig,
                          tokens: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, lengths: jax.Array,
                          mesh: Mesh,
                          n_microbatches: Optional[int] = None
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pipeline-parallel twin of ``decoder.forward_with_cache``.

    tokens [B, T]; k_cache/v_cache [L, B, KvH, S, hd] sharded over ``pp``
    along L; lengths [B]. Returns (logits [B, T, V] fp32 replicated over pp,
    k_cache, v_cache updated).
    """
    pp = mesh.shape[PP_AXIS]
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, moe_impl=resolve_moe_impl(cfg, mesh))
    B, T = tokens.shape
    L = cfg.n_layers
    M = n_microbatches or pp
    assert B % M == 0, f"microbatches {M} must divide batch {B}"
    assert M >= pp, f"need at least pp={pp} microbatches, got {M}"
    b = B // M
    Lpp = L // pp
    if cfg.altern_sliding:
        raise NotImplementedError(
            "per-layer alternating windows / dual rope (gemma2, gemma3) are not implemented "
            "on the pipeline path")
    scale = _attn_scale(cfg)
    KvH, hd = cfg.n_kv_heads, cfg.head_dim
    S = k_cache.shape[3]

    stages = split_stages(params["layers"], pp)
    kc5 = k_cache.reshape(pp, Lpp, B, KvH, S, hd)
    vc5 = v_cache.reshape(pp, Lpp, B, KvH, S, hd)

    def inner(stage_lp, kc, vc, tokens, lengths):
        # the mapped pp axis arrives as a leading size-1 dim — drop it
        stage_lp = jax.tree_util.tree_map(lambda a: a[0], stage_lp)
        kc, vc = kc[0], vc[0]
        # per-device: stage_lp [Lpp, ...], kc/vc [Lpp, B, KvH, S, hd]
        s = lax.axis_index(PP_AXIS)
        positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        k_pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]

        def run_stage(x_mb, kc_mb, vc_mb, pos_mb):
            cos, sin = rope_angles_cfg(pos_mb, cfg)
            ok = k_pos <= pos_mb[:, :, None]
            if cfg.sliding_window:
                ok = ok & (k_pos > pos_mb[:, :, None] - cfg.sliding_window)
            mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[:, None]

            def body(x, layer_in):
                lp, kcl, vcl = layer_in
                x, kcl, vcl = _block_cached(cfg, lp, x, cos, sin, kcl, vcl,
                                            pos_mb, mask, scale)
                return x, (kcl, vcl)

            x, (kc_mb, vc_mb) = lax.scan(body, x_mb, (stage_lp, kc_mb, vc_mb))
            return x, kc_mb, vc_mb

        D = cfg.dim
        dtype = params["tok_emb"].dtype
        # embed the whole batch once, outside the tick loop — a per-tick
        # embed would re-gather the (possibly vocab-sharded) table on every
        # stage every tick only to be consumed on stage 0
        x_all = _embed(cfg, params, tokens)

        def tick(t, carry):
            act, kc, vc, out = carry
            # stage 0 ingests microbatch t (garbage once t >= M; masked off)
            in_off = jnp.clip(t, 0, M - 1) * b
            x0 = lax.dynamic_slice_in_dim(x_all, in_off, b, axis=0)
            x_in = jnp.where(s == 0, x0, act)
            # this stage works on microbatch m = t - s
            m = t - s
            valid = (m >= 0) & (m < M)
            boff = jnp.clip(m, 0, M - 1) * b
            pos_mb = lax.dynamic_slice_in_dim(positions, boff, b, axis=0)
            kc_mb = lax.dynamic_slice(kc, (0, boff, 0, 0, 0),
                                      (Lpp, b, KvH, S, hd))
            vc_mb = lax.dynamic_slice(vc, (0, boff, 0, 0, 0),
                                      (Lpp, b, KvH, S, hd))
            x_out, kc_new, vc_new = run_stage(x_in, kc_mb, vc_mb, pos_mb)
            # masked cache writeback (writes original values when invalid)
            kc_sel = jnp.where(valid, kc_new, kc_mb)
            vc_sel = jnp.where(valid, vc_new, vc_mb)
            kc = lax.dynamic_update_slice(kc, kc_sel, (0, boff, 0, 0, 0))
            vc = lax.dynamic_update_slice(vc, vc_sel, (0, boff, 0, 0, 0))
            # last stage banks the final hidden states for microbatch m
            is_out = valid & (s == pp - 1)
            mo = jnp.clip(m, 0, M - 1)
            out = out.at[mo].set(
                jnp.where(is_out, x_out.astype(out.dtype), out[mo]))
            # hand activation to the next stage (ring; stage 0's incoming
            # slot is overwritten by fresh ingest next tick)
            act = lax.ppermute(x_out, PP_AXIS,
                               [(i, (i + 1) % pp) for i in range(pp)])
            return act, kc, vc, out

        act0 = lax.pcast(jnp.zeros((b, T, D), dtype), (PP_AXIS,),
                         to="varying")
        out0 = lax.pcast(jnp.zeros((M, b, T, D), jnp.float32), (PP_AXIS,),
                         to="varying")
        act, kc, vc, out = lax.fori_loop(0, M + pp - 1, tick,
                                         (act0, kc, vc, out0))
        # replicate the last stage's bank to every device
        out = lax.psum(jnp.where(s == pp - 1, out, 0), PP_AXIS)
        return out, kc[None], vc[None]

    cache_spec = P(PP_AXIS, None, None, None, None, None)
    # check_vma stays on: JAX 0.9's eager shard_map refuses a region that
    # is manual over part of the mesh with the check off
    out, kc5, vc5 = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(
            lambda _: P(PP_AXIS), stages), cache_spec, cache_spec,
            P(None, None), P(None)),
        out_specs=(P(None, None, None, None), cache_spec, cache_spec),
        axis_names={PP_AXIS})(stages, kc5, vc5, tokens, lengths)

    hidden = out.reshape(B, T, cfg.dim).astype(params["tok_emb"].dtype)
    logits = _unembed(cfg, params, hidden)
    return (logits, kc5.reshape(L, B, KvH, S, hd),
            vc5.reshape(L, B, KvH, S, hd))


def prefill_chunk_pp(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     mesh: Mesh, n_microbatches: Optional[int] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pipeline-parallel prefill: fresh chunk at positions [0, T).

    Same contract as ``decoder.prefill_chunk`` (logits [B,T,V] fp32,
    k/v [L,B,KvH,T,hd]) — implemented as a cached forward into an empty
    T-slot cache, which is exactly equivalent.
    """
    B, T = tokens.shape
    shape = (cfg.n_layers, B, cfg.n_kv_heads, T, cfg.head_dim)
    dtype = params["tok_emb"].dtype
    zeros = jnp.zeros(shape, dtype)
    lengths = jnp.zeros((B,), jnp.int32)
    return forward_with_cache_pp(params, cfg, tokens, zeros, zeros, lengths,
                                 mesh, n_microbatches)
