"""Weight-only int8/int4 quantization for serving (W8A16 / W4A16).

The reference serves GGUF-quantized weights through llama.cpp's CPU/GPU
dequant kernels inside the delegated ollama image (SURVEY.md §2.2). The
TPU-native equivalent keeps weights **quantized in HBM** and dequantizes on
the fly inside the matmul — decode is HBM-bandwidth-bound, so halving the
weight bytes roughly doubles decode throughput and is what lets llama2:70b
fit comfortably across a v5e-16 (BASELINE.md north star).

Representation: a quantized linear is a dict leaf in the params pytree —

    int8: {"q":  int8  [..., K,   O], "s": f32 [..., K/g, O]}
    int4: {"q4": uint8 [..., K/2, O], "s": f32 [..., K/g, O]}

symmetric, group-wise along the contracted (input) axis with group size
``g`` = 32, llama.cpp's q8_0/q4_0 block size — so transcoding q8_0 weights
onto the int8 grid adds (almost) no error beyond the original quantization,
and q4-family weights land on the int4 grid with only the clip of q4_0's
lone -8 code (we keep the symmetric [-7, 7] range).

int4 packing is **group-local**: each group of 32 rows packs into 16 bytes
where byte j holds row j in its low nibble and row j+16 in its high nibble
(both biased by +8 into [1, 15]). Group-local packing means any K-tile
that is a multiple of the group unpacks with a sublane-granular concat —
no cross-tile shuffles — which is what the pallas kernel wants.

Matmul paths (``matmul`` routes between them by row count, weight type
and the ``kernels`` it is given):
- ``qmm`` / ``qmm4``: pure-XLA grouped partial einsums — correct on any
  backend and under GSPMD (the convert fuses into the dot's operand
  stream). The int4 decode form runs two half-group dots over the same
  packed bytes, so its HBM traffic matches int8's — the *capacity* win
  (~0.63 B/weight with the f32 group scales; 70B int4 ≈ 43 GB) is
  unconditional, the *bandwidth* win needs the kernel below.
- ``ops/pallas/quant.py``: fused dequant-matmul kernels (int8 and int4);
  no dequantized weight reaches HBM at any row count, and the int4 kernel
  reads each packed byte once, i.e. half int8's weight traffic.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .attention import note_kernel

GROUP = 32

# matmul leaves worth quantizing (the big projections). tok_emb stays dense
# (it is a gather, not a matmul); MoE expert stacks stay dense this round.
QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
QUANT_TOP_KEYS = ("lm_head",)


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and ("q" in w or "q4" in w) and "s" in w


def is_int4(w: Any) -> bool:
    return isinstance(w, dict) and "q4" in w


def quantize_groupwise(w, group: int = GROUP) -> Dict[str, Any]:
    """Symmetric int8 per ``group`` along the second-to-last (input) axis.

    w [..., K, O] float → {"q" int8 [..., K, O], "s" f32 [..., K/g, O]}.
    jax arrays quantize on-device (jitted — milliseconds even for 70B
    leaves); numpy stays on host for the memory-bounded transcode path.
    """
    if isinstance(w, jax.Array):
        return _quantize_jax(w, group)
    w = np.asarray(w)
    *lead, K, O = w.shape
    assert K % group == 0, f"group {group} must divide in-dim {K}"
    if lead:
        # stacked [L, ...] leaves quantize one slice at a time — the f32
        # temporaries below are per-slice, so peak host RAM stays one
        # layer, not 3x the whole (potentially 70B-scale) leaf
        q = np.empty(w.shape, np.int8)
        s = np.empty((*lead, K // group, O), np.float32)
        flat_w = w.reshape(-1, K, O)
        flat_q = q.reshape(-1, K, O)
        flat_s = s.reshape(-1, K // group, O)
        for i in range(flat_w.shape[0]):
            sl = quantize_groupwise(flat_w[i], group)
            flat_q[i], flat_s[i] = sl["q"], sl["s"]
        return {"q": q, "s": s}
    w = np.asarray(w, np.float32)
    wr = w.reshape(K // group, group, O)
    amax = np.abs(wr).max(axis=-2, keepdims=True)          # [K/g, 1, O]
    s = (amax / 127.0).astype(np.float32)
    q = np.rint(np.where(s > 0, wr / np.maximum(s, 1e-30), 0.0))
    q = np.clip(q, -127, 127).astype(np.int8)
    return {"q": q.reshape(K, O), "s": s[:, 0, :]}


@partial(jax.jit, donate_argnums=(0,))
def _quantize_jax_impl(w, group: int = GROUP):
    *lead, K, O = w.shape
    wr = w.astype(jnp.float32).reshape(*lead, K // group, group, O)
    amax = jnp.max(jnp.abs(wr), axis=-2, keepdims=True)
    s = amax / 127.0
    q = jnp.round(jnp.where(s > 0, wr / jnp.maximum(s, 1e-30), 0.0))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return {"q": q.reshape(*lead, K, O), "s": s[..., 0, :]}


def _quantize_jax(w: jax.Array, group: int = GROUP) -> Dict[str, Any]:
    assert w.shape[-2] % group == 0
    assert group == GROUP, "jit path is specialised to the default group"
    return _quantize_jax_impl(w)


def pack_int4(q, bias: int = 8):
    """Pack int codes in [-7, 7] ([..., K, O]) into group-local nibbles
    ([..., K/2, O] uint8): within each 32-row group, byte j = row j
    (low nibble) | row j+16 (high nibble), both biased by +8."""
    xp = jnp if isinstance(q, jax.Array) else np
    *lead, K, O = q.shape
    assert K % GROUP == 0
    qr = (q.reshape(*lead, K // GROUP, GROUP, O) + bias).astype(xp.uint8)
    lo, hi = qr[..., :GROUP // 2, :], qr[..., GROUP // 2:, :]
    return (lo | (hi << 4)).reshape(*lead, K // 2, O)


def unpack_int4(q4, bias: int = 8):
    """Inverse of pack_int4: [..., K/2, O] uint8 → int8 [..., K, O]."""
    xp = jnp if isinstance(q4, jax.Array) else np
    *lead, Kp, O = q4.shape
    h = GROUP // 2
    assert Kp % h == 0
    b = q4.reshape(*lead, Kp // h, h, O)
    lo = (b & 0xF).astype(xp.int8) - bias
    hi = (b >> 4).astype(xp.int8) - bias
    return xp.concatenate([lo, hi], axis=-2).reshape(*lead, 2 * Kp, O)


def quantize_groupwise_int4(w, group: int = GROUP) -> Dict[str, Any]:
    """Symmetric int4 per ``group`` along the input axis, nibble-packed.

    w [..., K, O] float → {"q4" uint8 [..., K/2, O], "s" f32 [..., K/g, O]}.
    Codes clip to [-7, 7]: q4_0's asymmetric -8 code costs one extra
    grid point of error on transcode, and symmetry keeps dequant a pure
    multiply (no zero-point correction term in the matmuls).
    """
    assert group == GROUP, "int4 packing is specialised to the group size"
    if isinstance(w, jax.Array):
        return _quantize_jax_int4(w)
    w = np.asarray(w)
    *lead, K, O = w.shape
    assert K % group == 0, f"group {group} must divide in-dim {K}"
    if lead:
        q4 = np.empty((*lead, K // 2, O), np.uint8)
        s = np.empty((*lead, K // group, O), np.float32)
        flat_w = w.reshape(-1, K, O)
        flat_q = q4.reshape(-1, K // 2, O)
        flat_s = s.reshape(-1, K // group, O)
        for i in range(flat_w.shape[0]):
            sl = quantize_groupwise_int4(flat_w[i], group)
            flat_q[i], flat_s[i] = sl["q4"], sl["s"]
        return {"q4": q4, "s": s}
    w = np.asarray(w, np.float32)
    wr = w.reshape(K // group, group, O)
    amax = np.abs(wr).max(axis=-2, keepdims=True)
    s = (amax / 7.0).astype(np.float32)
    q = np.rint(np.where(s > 0, wr / np.maximum(s, 1e-30), 0.0))
    q = np.clip(q, -7, 7).astype(np.int8).reshape(K, O)
    return {"q4": pack_int4(q), "s": s[:, 0, :]}


@partial(jax.jit, donate_argnums=(0,))
def _quantize_jax_int4_impl(w):
    *lead, K, O = w.shape
    g = GROUP
    wr = w.astype(jnp.float32).reshape(*lead, K // g, g, O)
    amax = jnp.max(jnp.abs(wr), axis=-2, keepdims=True)
    s = amax / 7.0
    q = jnp.round(jnp.where(s > 0, wr / jnp.maximum(s, 1e-30), 0.0))
    q = jnp.clip(q, -7, 7).astype(jnp.int8).reshape(*lead, K, O)
    return {"q4": pack_int4(q), "s": s[..., 0, :]}


def _quantize_jax_int4(w: jax.Array) -> Dict[str, Any]:
    assert w.shape[-2] % GROUP == 0
    return _quantize_jax_int4_impl(w)


def dequantize_groupwise(qw: Dict[str, Any]) -> jnp.ndarray:
    """Reference inverse of quantize_groupwise[_int4] (f32)."""
    if is_int4(qw):
        q = unpack_int4(jnp.asarray(qw["q4"]))
    else:
        q = jnp.asarray(qw["q"])
    s = jnp.asarray(qw["s"])
    *lead, K, O = q.shape
    G = s.shape[-2]
    qr = q.reshape(*lead, G, K // G, O).astype(jnp.float32)
    return (qr * s[..., :, None, :]).reshape(*lead, K, O)


def qmm4(x: jax.Array, qw: Dict[str, Any],
         out_dtype: Optional[Any] = None) -> jax.Array:
    """x [..., K] @ dequant(int4 qw) — XLA formulation (portable/GSPMD).

    Same N-split as qmm. The decode form dots the two nibble planes
    separately against the matching half-group activation slices —
    group-local packing makes those static slices, no gather — so the
    packed bytes are each read twice (int8-equivalent traffic); the
    pallas kernel is the half-traffic path.
    """
    q4, s = qw["q4"], qw["s"]
    Kp, O = q4.shape
    K = 2 * Kp
    G = s.shape[0]
    g = K // G
    h = g // 2
    if _rows(x) > GROUPED_MAX_ROWS:
        # dequantize in f32, cast the product once — the decode form and
        # the pallas kernel apply f32 scales post-dot, so prefill must not
        # see scale values rounded through bf16's 8-bit mantissa
        w = (unpack_int4(q4).reshape(G, g, O).astype(jnp.float32)
             * s[:, None, :]).reshape(K, O).astype(x.dtype)
        y = jnp.einsum("...k,ko->...o", x, w,
                       preferred_element_type=jnp.float32)
        return y.astype(out_dtype or x.dtype)
    xr = x.reshape(*x.shape[:-1], G, g)
    b = q4.reshape(G, h, O)
    lo = ((b & 0xF).astype(jnp.int8) - 8).astype(x.dtype)
    hi = ((b >> 4).astype(jnp.int8) - 8).astype(x.dtype)
    partial = (jnp.einsum("...Gg,Ggo->...Go", xr[..., :h], lo,
                          preferred_element_type=jnp.float32)
               + jnp.einsum("...Gg,Ggo->...Go", xr[..., h:], hi,
                            preferred_element_type=jnp.float32))
    y = jnp.einsum("...Go,Go->...o", partial, s)
    return y.astype(out_dtype or x.dtype)


def _rows(x) -> int:
    return int(np.prod(x.shape[:-1], dtype=np.int64))


def layer_of(stack: jax.Array, layer) -> jax.Array:
    """Layer ``layer`` of a stacked leaf (``layer`` None: it is no stack).
    XLA fuses this slice into its consumer's read, as it does a scan's."""
    if layer is None:
        return stack
    return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def qmm_grouped(x: jax.Array, qw: Dict[str, Any],
                out_dtype: Optional[Any] = None) -> jax.Array:
    """The few-rows XLA form: grouped partial, the scale multiply kept
    outside the inner dot so the int8→bf16 convert fuses into the dot's
    read stream and the weight is read once at 1 byte/element:

        y[.., o] = Σ_G s[G, o] · Σ_{k∈G} x[.., k] · q[k, o]

    Its [N, K/g, O] f32 partial is N × the weight's bytes × 4/g: tiny at a
    handful of rows, 302 MB for one 3072x12288 matrix at 64."""
    q, s = qw["q"], qw["s"]
    K, O = q.shape
    G = s.shape[0]
    g = K // G
    xr = x.reshape(*x.shape[:-1], G, g)
    qr = q.reshape(G, g, O)
    partial = jnp.einsum("...Gg,Ggo->...Go", xr, qr.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    y = jnp.einsum("...Go,Go->...o", partial, s)
    return y.astype(out_dtype or x.dtype)


def qmm_dense(x: jax.Array, qw: Dict[str, Any],
              out_dtype: Optional[Any] = None) -> jax.Array:
    """The many-rows XLA form: dequantize the weight to one [K, O]
    transient (f32 scales: the grouped form and the pallas kernel apply
    them in f32, so this one must not round them through bf16) and run a
    single dense dot. The transient goes through HBM: ~12 bytes moved per
    weight element where the codes are one. That is noise once the dot is
    MXU-bound (a thousand rows and more) and is the whole cost below it,
    which is where batched decode (32-64 rows) ran until the fused kernel
    took those row counts on a single-device TPU (``matmul``)."""
    q, s = qw["q"], qw["s"]
    K, O = q.shape
    G = s.shape[0]
    w = (q.reshape(G, K // G, O).astype(jnp.float32)
         * s[:, None, :]).reshape(K, O).astype(x.dtype)
    y = jnp.einsum("...k,ko->...o", x, w,
                   preferred_element_type=jnp.float32)
    return y.astype(out_dtype or x.dtype)


# the grouped form's f32 partial grows with the rows; above this many the
# XLA path dequantizes the whole weight instead (and the fused kernel, where
# it can run, takes over: hack/qmm_microbench.py has the table)
GROUPED_MAX_ROWS = 16


def qmm(x: jax.Array, qw: Dict[str, Any],
        out_dtype: Optional[Any] = None) -> jax.Array:
    """x [..., K] @ dequant(qw [K, O]) with group-wise scales, in XLA:
    ``qmm_grouped`` up to GROUPED_MAX_ROWS rows (N = prod(lead), static),
    ``qmm_dense`` above."""
    form = qmm_grouped if _rows(x) <= GROUPED_MAX_ROWS else qmm_dense
    return form(x, qw, out_dtype)


def matmul(x: jax.Array, w: Any, out_dtype: Optional[Any] = None,
           kernels: str = "xla") -> jax.Array:
    """Unified linear: dense jnp array or quantized dict weight.

    The quantized matmul is one algorithm (dequantize, dot, accumulate)
    that wants the dequantized tile in a different place at different row
    counts N, so the form is chosen from what the inputs show:

    1. int8, N <= GROUPED_MAX_ROWS: the grouped XLA form, which reads the
       weight once already (measured best at one stream).
    2. ``kernels`` "pallas" (or "interpret") otherwise: the fused kernel
       (ops/pallas/quant.py), int8 tiles dequantized in VMEM. int4 takes
       it at every N: only the kernel reads packed bytes once.
    3. ``kernels`` "xla" (a GSPMD mesh, where pallas_call is opaque; CPU;
       an explicit choice): the XLA forms at every N.

    1 and 3 are routes, recorded as the kernel they are; only a shape that
    was meant for the fused kernel and does not tile is a fallback.
    """
    if not is_quantized(w):
        y = x @ w
        return y.astype(out_dtype) if out_dtype is not None else y
    int4 = is_int4(w)
    codes = "q4" if int4 else "q"
    # a stack of layers and the index of this one (models/decoder.py
    # _scan_layers): the fused kernel reads its layer where it lies
    layer = w.get("layer")
    if kernels in ("pallas", "interpret") and (
            int4 or _rows(x) > GROUPED_MAX_ROWS):
        from .pallas.quant import qmm4_pallas, qmm_pallas
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        y = (qmm4_pallas if int4 else qmm_pallas)(
            x2, w[codes], w["s"], interpret=(kernels == "interpret"),
            layer=layer)
        return y.reshape(*lead, -1).astype(out_dtype or x.dtype)
    note_kernel("matmul", "xla_int4" if int4 else "xla_int8")
    w = {k: layer_of(w[k], layer) for k in (codes, "s")}
    return (qmm4 if int4 else qmm)(x, w, out_dtype)


def quantize_params(params: Dict[str, Any], group: int = GROUP,
                    keys_layer=QUANT_LAYER_KEYS, keys_top=QUANT_TOP_KEYS,
                    bits: int = 8) -> Dict[str, Any]:
    """Convert the big matmul leaves of a decoder param tree to int8
    (``bits=8``) or packed int4 (``bits=4``).

    Works on numpy (host) or jax (on-device) arrays; stacked [L, ...]
    layer leaves quantize along their input axis, which is second-to-last
    either way.

    On-device (jax) sources are DONATED leaf by leaf — each bf16 leaf's
    HBM is released as its quantized replacement materialises, so peak
    memory is the bf16 tree + one leaf, never bf16 + quantized trees
    together (a 7B bf16 tree alone is 13.4 GB of a v5e chip's 16).
    """
    assert bits in (8, 4), bits
    quant = quantize_groupwise if bits == 8 else quantize_groupwise_int4
    out: Dict[str, Any] = {}
    for k in list(params.keys()):
        v = params[k]
        if k == "layers":
            lo = {}
            for lk in list(v.keys()):
                if lk in keys_layer:
                    lo[lk] = quant(v.pop(lk), group)
                else:
                    lo[lk] = v[lk]
            out[k] = lo
        elif k in keys_top:
            out[k] = quant(params.pop(k), group)
        else:
            out[k] = v
    return out


def resolve_mm_kernels(cfg, mesh) -> Any:
    """Resolve ``cfg.mm_kernels == "auto"`` for the place the model runs:
    "pallas" on a single-device TPU (``matmul`` then routes by row count
    and weight type), "xla" wherever the fused kernel cannot run — a mesh
    of more than one device (pallas_call is opaque to GSPMD), another
    backend — or is switched off (``kernels=xla`` in the config or
    OLLAMA_TPU_KERNELS: the escape hatch if a kernel miscompiles). An
    explicit ``mm_kernels`` always stands. The ONE place "auto" is decided,
    for int8 and int4 alike: the engine's constructor calls it, so every
    way of building an engine (the server's loader, bench.py, the
    benchmark's child and its probe) serves the same matmul path. Returns
    the cfg, possibly replaced."""
    from .attention import resolve_kernels
    if cfg.mm_kernels != "auto":
        return cfg
    fused = (jax.default_backend() == "tpu"
             and (mesh is None or mesh.size == 1)
             and resolve_kernels(cfg.kernels) != "xla")
    return dataclasses.replace(cfg, mm_kernels="pallas" if fused else "xla")


# the name the loaders and the benchmark's child import (when only int4
# resolved to the kernel)
int4_mm_kernels = resolve_mm_kernels


def quantized_bytes(params: Dict[str, Any]) -> int:
    """HBM footprint of a (possibly partly quantized) param tree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total
