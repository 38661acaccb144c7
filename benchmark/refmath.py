"""Plain float32 building blocks of the reference forward passes, and the
benchmark's own copy of weight dequantization. No kernels, no cache, no
batching: one sequence, every position attends to all earlier ones."""

from __future__ import annotations

import jax
import jax.numpy as jnp

GROUP = 32          # rows of the input axis that share one scale


def dequant(w):
    """A served weight leaf as float32: plain arrays pass through; int8
    {"q" [.., K, O], "s" [.., K/32, O]} and nibble-packed int4 {"q4"
    [.., K/2, O], "s"} (within each 32-row group byte j holds row j in its low
    nibble and row j+16 in its high one, both biased by 8) are scaled back."""
    if not isinstance(w, dict):
        return jnp.asarray(w, jnp.float32)
    s = jnp.asarray(w["s"], jnp.float32)
    if "q4" in w:
        b = w["q4"].astype(jnp.int32)
        *lead, kp, o = b.shape
        b = b.reshape(*lead, kp // (GROUP // 2), GROUP // 2, o)
        q = jnp.concatenate([(b & 15) - 8, (b >> 4) - 8], axis=-2)
    else:
        q = w["q"].astype(jnp.int32)
        *lead, k, o = q.shape
        q = q.reshape(*lead, k // GROUP, GROUP, o)
    full = q.astype(jnp.float32) * s[..., :, None, :]
    return full.reshape(*full.shape[:-3], -1, full.shape[-1])


def rotate_half(x, positions, rotary_dim: int, theta: float):
    """Rotary embedding over the first ``rotary_dim`` channels of x
    [T, H, hd], half-split pairing (channel i with i + rotary_dim/2)."""
    half = rotary_dim // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                          / rotary_dim)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot, x[..., rotary_dim:]], -1)


def causal_attention(q, k, v, window: int = 0):
    """q [T, H, hd], k and v [T, KvH, hd]: softmax(q k^T / sqrt(hd)) v with a
    causal mask (and a sliding window where one is set); query heads share
    key/value heads in consecutive groups."""
    t, h, hd = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(hd))
    qi = jnp.arange(t)[:, None]
    ki = jnp.arange(t)[None, :]
    ok = ki <= qi
    if window:
        ok = ok & (ki > qi - window)
    s = jnp.where(ok[None], s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def f32(x):
    return jnp.asarray(x, jnp.float32)
