"""Rotary position embeddings, with context-extension scaling.

One convention everywhere: **half-split (NeoX/HF) layout** — the head dim is
split into two halves rotated against each other. GGUF llama-family
checkpoints store weights for the *interleaved* convention; the transcoder
(gguf/transcode.py) permutes wq/wk rows at load time so this single
implementation is correct for every arch. phi-2 style partial rotary is
supported via ``rotary_dim < head_dim``.

Scaling: the reference serves long-context models through llama.cpp inside
the delegated ``ollama/ollama`` image (/root/reference/pkg/model/pod.go:11),
which honors the GGUF ``rope.scaling.*`` metadata (linear and YaRN) and the
per-frequency ``rope_freqs.weight`` factor tensor that llama3.1-family
conversions bake in. This module is the TPU-native equivalent: every scheme
reduces to a **static per-frequency rescale of inv_freq** (plus a scalar
cos/sin magnitude for YaRN's attention factor), computed in numpy at trace
time — zero per-step cost inside jit, and exactly one rope implementation
regardless of scheme.

Parity targets: transformers' ROPE_INIT_FUNCTIONS (linear / yarn / llama3),
which match llama.cpp's runtime math — verified in tests/test_rope_scaling.py.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=64)
def scaled_inv_freq(rotary_dim: int, theta: float, *,
                    scaling_type: str = "none", factor: float = 1.0,
                    orig_ctx: int = 0, low_freq_factor: float = 1.0,
                    high_freq_factor: float = 4.0, attn_factor: float = 0.0,
                    beta_fast: float = 32.0, beta_slow: float = 1.0,
                    freq_factors: Optional[Tuple[float, ...]] = None,
                    yarn_mscale: float = 0.0, yarn_mscale_all_dim: float = 0.0,
                    ) -> Tuple[Tuple[float, ...], float]:
    """The per-frequency rotation rates after context-extension scaling.

    Returns ``(inv_freq, mscale)`` — ``inv_freq`` a length rotary_dim//2
    tuple of f32 rates, ``mscale`` the scalar the YaRN scheme multiplies
    cos/sin by (1.0 for everything else). All inputs are static config
    fields, so the result is a trace-time constant (lru-cached: the decode
    loop re-traces per bucket).

    Schemes (factor > 1 extends context ``factor``-fold past ``orig_ctx``):

    - ``none``  — plain RoPE. A ``factor != 1`` is honored as linear for
      back-compat with the old bare-scalar config field.
    - ``linear`` — positions divided by ``factor`` (all frequencies).
    - ``yarn``  — NTK-by-parts: frequencies whose wavelength fits the
      original window are untouched, long wavelengths interpolate by
      ``factor``, with a linear ramp between the ``beta_fast``/``beta_slow``
      correction dims; cos/sin scale by ``attn_factor`` (default
      ``0.1·ln(factor)+1``). Where the config states ``yarn_mscale`` /
      ``yarn_mscale_all_dim`` (the DeepSeek-V3 convention) cos/sin scale by
      ``yarn_magnitude`` of the one over that of the other, and the softmax
      scale carries the rest (``yarn_softmax_factor``).
    - ``llama3`` — low/high-frequency interpolation: wavelengths beyond
      ``orig_ctx/low_freq_factor`` divide by ``factor``, those inside
      ``orig_ctx/high_freq_factor`` are untouched, smooth blend between.
    - ``freq_factors`` (from a GGUF ``rope_freqs.weight`` tensor) divide
      inv_freq directly — llama3.1-family conversions pre-bake their
      scheme into this tensor, so when present it *is* the scaling and the
      metadata scheme is not applied on top (llama.cpp behavior).
    """
    half = rotary_dim // 2
    inv_freq = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    mscale = 1.0

    if freq_factors is not None:
        ff = np.asarray(freq_factors, dtype=np.float64)
        assert ff.shape == (half,), (
            f"rope_freq_factors has {ff.shape[0]} entries; rotary_dim "
            f"{rotary_dim} needs {half}")
        inv_freq = inv_freq / ff
        if attn_factor > 0:
            # phi3-family longrope: the factor tensor rescales frequencies
            # AND cos/sin scale by the magnitude factor (transformers
            # Phi3LongRoPE; plain llama3.1 rope_freqs carry no attn_factor
            # so their mscale stays 1)
            mscale = attn_factor
    elif scaling_type == "linear" or (scaling_type == "none"
                                      and factor != 1.0):
        inv_freq = inv_freq / factor
    elif scaling_type == "llama3":
        assert orig_ctx > 0, "llama3 rope scaling needs rope_orig_ctx"
        low_wavelen = orig_ctx / low_freq_factor
        high_wavelen = orig_ctx / high_freq_factor
        wavelen = 2.0 * math.pi / inv_freq
        scaled = np.where(wavelen > low_wavelen, inv_freq / factor, inv_freq)
        smooth = ((orig_ctx / wavelen - low_freq_factor)
                  / (high_freq_factor - low_freq_factor))
        blended = (1.0 - smooth) * scaled / factor + smooth * scaled
        medium = (wavelen >= high_wavelen) & (wavelen <= low_wavelen)
        inv_freq = np.where(medium, blended, scaled)
    elif scaling_type == "yarn":
        assert orig_ctx > 0, "yarn rope scaling needs rope_orig_ctx"

        def correction_dim(n_rot: float) -> float:
            return (rotary_dim
                    * math.log(orig_ctx / (n_rot * 2.0 * math.pi))
                    / (2.0 * math.log(theta)))

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), rotary_dim - 1)
        if low == high:
            high = low + 0.001  # avoid a 0-width ramp
        ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        extrap = 1.0 - ramp          # 1 at high-freq dims: keep original
        inv_freq = (inv_freq / factor) * (1.0 - extrap) + inv_freq * extrap
        if yarn_mscale or yarn_mscale_all_dim:
            mscale = (yarn_magnitude(factor, yarn_mscale)
                      / yarn_magnitude(factor, yarn_mscale_all_dim))
        else:
            mscale = attn_factor if attn_factor > 0 else yarn_magnitude(
                factor)
    elif scaling_type != "none":
        raise ValueError(f"unknown rope scaling type {scaling_type!r}")

    return tuple(np.asarray(inv_freq, np.float32).tolist()), float(mscale)


def yarn_magnitude(factor: float, mscale: float = 1.0) -> float:
    """YaRN's m(s) = 0.1 s ln(factor) + 1 (1 where nothing is extended)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_softmax_factor(cfg) -> float:
    """What the softmax scale is multiplied by under the DeepSeek-V3
    convention of YaRN, m(mscale_all_dim)^2 (the queries' and the keys'
    share, which that convention takes out of cos/sin); 1.0 for every other
    scheme, the Llama convention of YaRN among them."""
    if cfg.rope_scaling_type != "yarn" or not cfg.rope_yarn_mscale_all_dim:
        return 1.0
    return yarn_magnitude(cfg.rope_scaling, cfg.rope_yarn_mscale_all_dim) ** 2


def rope_angles(positions, rotary_dim: int, theta: float,
                scaling: float = 1.0, *, inv_freq=None, mscale: float = 1.0):
    """positions [..] int32 → (cos, sin) [.., rotary_dim//2] float32.

    The legacy form (``scaling`` = bare linear factor) stays for callers
    without a full config; cfg-aware paths use :func:`rope_angles_cfg`.
    """
    if inv_freq is None:
        half = rotary_dim // 2
        inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                    / half))
        pos = positions.astype(jnp.float32) / scaling
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
        pos = positions.astype(jnp.float32)
    angles = pos[..., None] * inv_freq  # [.., half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    return cos, sin


def rope_angles_cfg(positions, cfg, rotary_dim: Optional[int] = None):
    """cfg-driven rope_angles: applies the model's full scaling scheme
    (ModelConfig.rope_scaling_type & friends, gguf/transcode.py) over
    ``rotary_dim`` channels (cfg.rotary_dim where not given: latent
    attention rotates its qk_rope_dim)."""
    rotary_dim = rotary_dim or cfg.rotary_dim
    inv_freq, mscale = scaled_inv_freq(
        rotary_dim, cfg.rope_theta,
        scaling_type=cfg.rope_scaling_type, factor=cfg.rope_scaling,
        orig_ctx=cfg.rope_orig_ctx,
        low_freq_factor=cfg.rope_low_freq_factor,
        high_freq_factor=cfg.rope_high_freq_factor,
        attn_factor=cfg.rope_attn_factor,
        beta_fast=cfg.rope_yarn_beta_fast,
        beta_slow=cfg.rope_yarn_beta_slow,
        freq_factors=cfg.rope_freq_factors,
        yarn_mscale=cfg.rope_yarn_mscale,
        yarn_mscale_all_dim=cfg.rope_yarn_mscale_all_dim)
    return rope_angles(positions, rotary_dim, cfg.rope_theta,
                       inv_freq=inv_freq, mscale=mscale)


def apply_rope(x, cos, sin, rotary_dim: int):
    """x [B, T, H, head_dim]; cos/sin [B, T, rotary_dim//2].

    Rotates the first ``rotary_dim`` channels (half-split), passes the rest
    through unchanged.
    """
    half = rotary_dim // 2
    x_rot = x[..., :rotary_dim].astype(jnp.float32)
    x1 = x_rot[..., :half]
    x2 = x_rot[..., half:]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    out = jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)
    if rotary_dim == x.shape[-1]:
        return out
    return jnp.concatenate([out, x[..., rotary_dim:]], axis=-1)
