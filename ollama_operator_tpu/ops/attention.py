"""Attention cores (pure-JAX reference paths).

These are the semantics-defining implementations; ``ops/pallas`` provides
TPU-tuned kernels that must match them bit-approximately. GQA is expressed as
a grouped einsum (no materialised head repeat) so XLA keeps the MXU matmuls
large and avoids an HBM-resident K/V copy.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


def softcap_scores(scores, cap: float):
    """Gemma2-style tanh soft-capping (no-op when cap <= 0)."""
    if cap and cap > 0.0:
        return cap * jnp.tanh(scores / cap)
    return scores


_softcap = softcap_scores


def attend(q, k, v, mask, scale: float, softcap: float = 0.0):
    """Grouped-query attention.

    q    [B, T, H, hd]
    k, v [B, S, KvH, hd]
    mask [B, 1, T, S] additive (0 or NEG_INF), broadcastable
    →    [B, T, H, hd]
    """
    B, T, H, hd = q.shape
    KvH = k.shape[2]
    G = H // KvH
    qg = q.reshape(B, T, KvH, G, hd)
    # scores [B, KvH, G, T, S]
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * scale
    scores = _softcap(scores, softcap)
    scores = scores + mask[:, :, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs.astype(v.dtype), v)
    return out.reshape(B, T, H, hd)


def attend_hf(q, k, v, mask, scale: float, softcap: float = 0.0):
    """Grouped-query attention with **head-first** K/V — the serving
    layout: the KV cache keeps (seq, head_dim) as its trailing dims so the
    pallas kernels tile it directly and XLA reads it without relayout.

    q    [B, T, H, hd]
    k, v [B, KvH, S, hd]
    mask [B, 1, T, S] additive, broadcastable
    →    [B, T, H, hd]
    """
    B, T, H, hd = q.shape
    KvH = k.shape[1]
    G = H // KvH
    qg = q.reshape(B, T, KvH, G, hd)
    scores = jnp.einsum("btkgh,bksh->bkgts", qg, k,
                        preferred_element_type=jnp.float32)
    scores = _softcap(scores * scale, softcap)
    scores = scores + mask[:, :, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bksh->btkgh", probs.astype(v.dtype), v)
    return out.reshape(B, T, H, hd)


def causal_mask(T: int, S: int, offset, dtype=jnp.float32,
                sliding_window: int = 0):
    """Additive [1, 1, T, S] mask. Query i sits at absolute position
    offset + i; key j at absolute position j. Supports a sliding window
    (mistral) when ``sliding_window > 0``."""
    q_pos = offset + jnp.arange(T)[:, None]
    k_pos = jnp.arange(S)[None, :]
    ok = k_pos <= q_pos
    if sliding_window:
        ok = ok & (k_pos > q_pos - sliding_window)
    return jnp.where(ok, 0.0, NEG_INF).astype(dtype)[None, None]


# ---------------------------------------------------------------------------
# kernel dispatch (ModelConfig.kernels: auto | pallas | xla | interpret)
# ---------------------------------------------------------------------------

KERNEL_MODES = ("auto", "pallas", "xla", "interpret")

# Which kernel each dispatcher picked while a program was traced. A
# dispatcher that wanted a pallas kernel and took the next path because the
# shapes do not tile says so here (fell_back=True); the engine reports it
# once per program (runtime/engine.py), so a kernel that quietly gave way to
# einsum on the chip is seen at start-up, not in a profile weeks later.
_KERNEL_SINK: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_sink", default=None)


@contextlib.contextmanager
def record_kernels() -> Iterator[List[Tuple[str, str, bool]]]:
    """Collect (site, kernel, fell_back) for every dispatch traced inside
    the block, each once. Nothing is collected outside such a block."""
    sink: List[Tuple[str, str, bool]] = []
    token = _KERNEL_SINK.set(sink)
    try:
        yield sink
    finally:
        _KERNEL_SINK.reset(token)


def note_kernel(site: str, kernel: str, fell_back: bool = False) -> None:
    sink = _KERNEL_SINK.get()
    if sink is not None and (site, kernel, fell_back) not in sink:
        sink.append((site, kernel, fell_back))


def _mesh_attn_axes(mesh, B: int, H: int, KvH: int):
    """(batch_axis, head_axis) for a dp/tp-manual ``shard_map`` around the
    attention kernels, or None when this mesh can't shard them evenly.

    pallas_call is opaque to GSPMD — on a real mesh the kernels must run
    inside a manual region where each device sees only its local heads /
    batch rows (attention needs no cross-device traffic along dp or tp:
    heads and batch entries are independent). sp/pp paths wrap attention
    themselves (parallel/long_context.py, parallel/pipeline.py) and ep
    meshes stay on the einsum path (MoE attention operands would be
    GSPMD-auto along ep inside the manual region — untested; einsum is
    correct there)."""
    if mesh is None or mesh.size == 1:
        return None
    shape = dict(mesh.shape)
    if (shape.get("sp", 1) > 1 or shape.get("pp", 1) > 1
            or shape.get("ep", 1) > 1):
        return None
    dp, tp = shape.get("dp", 1), shape.get("tp", 1)
    if dp * tp != mesh.size:
        return None
    if B % dp or H % tp or KvH % tp:
        return None
    return ("dp" if dp > 1 else None), ("tp" if tp > 1 else None)


def _sharded_kernel_call(mesh, q, KvH: int, tileable, inner, args,
                         with_pos: bool):
    """Run a pallas attention kernel inside a dp/tp-manual shard_map.

    ``tileable(H_local, KvH_local)`` re-checks the kernel's bail conditions
    at per-device shapes BEFORE entering the manual region (a mid-trace
    None-fallback is impossible inside shard_map). Returns the sharded
    result, or None when the mesh can't shard or the kernel wouldn't tile —
    callers then fall back to the einsum path (GSPMD-auto). ``args`` are
    (q, k, v[, pos]) with k/v head-first; ``with_pos`` appends the [B]
    q_pos spec."""
    from jax.sharding import PartitionSpec as P
    B, _, H, _ = q.shape
    axes = _mesh_attn_axes(mesh, B, H, KvH)
    if axes is None:
        return None
    tp = mesh.shape.get("tp", 1)
    if not tileable(H // tp, KvH // tp):
        return None
    b_ax, h_ax = axes
    qspec = P(b_ax, None, h_ax, None)
    kvspec = P(b_ax, h_ax, None, None)
    in_specs = (qspec, kvspec, kvspec) + ((P(b_ax),) if with_pos else ())
    # manual over EVERY mesh axis (_mesh_attn_axes admits only meshes whose
    # other axes are size 1): Mosaic refuses a kernel inside a region that
    # leaves any axis to the partitioner
    return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=qspec, check_vma=False)(*args)


def _kernels_override() -> str:
    """``OLLAMA_TPU_KERNELS``, checked: the ONE read of the environment
    under ``ops/`` and ``models/`` (tools/invariant_lint holds that). A
    read at trace time is no part of a jit cache key, so nothing else here
    may depend on one."""
    env = os.environ.get("OLLAMA_TPU_KERNELS", "")
    if env and env not in KERNEL_MODES:
        raise ValueError(
            f"OLLAMA_TPU_KERNELS={env!r}; expected one of {KERNEL_MODES}")
    return env


def resolve_kernels(kernels: str) -> str:
    """Trace-time kernel choice. ``auto`` → pallas on TPU backends, XLA
    elsewhere. The OLLAMA_TPU_KERNELS env var overrides only the ``auto``
    choice — an explicit config always wins. (On >1-device meshes the
    dispatchers below run the kernels inside a dp/tp-manual shard_map;
    there is no multi-device XLA fallback anymore.)"""
    env = _kernels_override()
    if kernels == "auto" and env:
        kernels = env
    if kernels == "auto":
        kernels = "pallas" if jax.default_backend() == "tpu" else "xla"
    return kernels


def chunk_attention(cfg, q, k, v, mask, scale: float, mesh=None):
    """Prefill attention over a fresh chunk (chunk-local causal semantics,
    the mask callers build via ``causal_mask(T, T, 0)``). K/V are
    head-first [B, KvH, T, hd]. Routes to the pallas flash kernel when
    enabled and tileable, else the einsum path. On a >1-device ``mesh``
    the kernel runs inside a dp/tp-manual shard_map (each device computes
    its local heads/batch rows; no collectives — attention is independent
    along both axes), so GSPMD never sees the opaque pallas_call."""
    mode = resolve_kernels(cfg.kernels)
    if mode in ("pallas", "interpret"):
        from .pallas import flash_prefill, prefill_tileable
        interp = mode == "interpret"
        T, hd = q.shape[1], q.shape[3]

        def inner(q, k, v):
            return flash_prefill(q, k, v, scale, cfg.attn_softcap,
                                 cfg.sliding_window, interpret=interp)

        if mesh is not None and mesh.size > 1:
            out = _sharded_kernel_call(
                mesh, q, k.shape[1],
                lambda h, kvh: prefill_tileable(T, h, kvh, hd, interp),
                inner, (q, k, v), with_pos=False)
            # None → mesh not shardable/tileable → einsum (GSPMD-auto)
        else:
            out = inner(q, k, v)
        if out is not None:
            note_kernel("prefill", "flash_prefill")
            return out
        note_kernel("prefill", "einsum", fell_back=True)
    else:
        note_kernel("prefill", "einsum")
    return attend_hf(q, k, v, mask, scale, cfg.attn_softcap)


def cached_attention(cfg, q, k_cache, v_cache, mask, q_pos, scale: float,
                     attn_len=None, mesh=None):
    """Attention against the head-first slot KV cache [B, KvH, S, hd].
    ``q_pos`` [B, T] are the new tokens' absolute positions (the T=1 decode
    step routes to the pallas kernel, which skips unread cache blocks; T>1
    continuations use the masked einsum path). ``attn_len`` statically
    bounds the attended prefix: the einsum path slices the cache view (the
    lazy slice fuses into its reads). The decode path (forward_with_cache)
    hands this an A-sized window sliced from the full cache carry, so the
    pallas kernel's operand is that window — materialized once per layer
    either way; the kernel's q_pos block clamp still elides unread blocks'
    DMAs within it. On a >1-device ``mesh`` the kernel runs inside a
    dp/tp-manual shard_map (see chunk_attention)."""
    mode = resolve_kernels(cfg.kernels)
    # MHA (G == 1) maps badly onto this kernel's (B, KvH, nk) grid: B×KvH
    # programs of 8 rows, 7 of them padding. So a pallas that "auto" chose
    # by backend leaves MHA to the einsum; an explicit pallas (config or
    # OLLAMA_TPU_KERNELS) still forces the kernel. Unmeasured since the
    # cells came: the ledger's one MHA model (phi-2) is paged in every
    # line, and no cell serves MHA from the dense cache.
    explicit_pallas = (cfg.kernels == "pallas"
                       or _kernels_override() == "pallas")
    is_mha = q.shape[2] == k_cache.shape[1]
    if (mode in ("pallas", "interpret") and q.shape[1] == 1
            and (not is_mha or explicit_pallas or mode == "interpret")):
        from .pallas import decode_attention, decode_tileable
        interp = mode == "interpret"
        hd, S = q.shape[3], k_cache.shape[2]

        def inner(q, k_cache, v_cache, pos):
            return decode_attention(
                q, k_cache, v_cache, pos, scale, cfg.attn_softcap,
                cfg.sliding_window, interpret=interp)

        def tileable(h, kvh):
            return decode_tileable(S, h, kvh, hd, interp)

        if mesh is not None and mesh.size > 1:
            out = _sharded_kernel_call(
                mesh, q, k_cache.shape[1], tileable,
                inner, (q, k_cache, v_cache, q_pos[:, 0]), with_pos=True)
            # None → mesh not shardable/tileable → einsum (GSPMD-auto)
        else:
            out = inner(q, k_cache, v_cache, q_pos[:, 0])
        if out is not None:
            note_kernel("decode", "decode_attention")
            return out
        note_kernel("decode", "einsum", fell_back=True)
    else:
        # xla mode, a T>1 continuation, or MHA by design (see above)
        note_kernel("decode", "einsum")
    if attn_len is not None and attn_len < k_cache.shape[2]:
        k_cache = k_cache[:, :, :attn_len, :]
        v_cache = v_cache[:, :, :attn_len, :]
    return attend_hf(q, k_cache, v_cache, mask, scale, cfg.attn_softcap)
