"""int8/int4 KV cache: quantized storage + attention over it.

Decode attention traffic is the KV cache itself; storing K/V as int8 with
one f32 scale per (position, head) halves that traffic and doubles how
much context fits in HBM — the same lever llama.cpp pulls with its
quantized KV options inside the reference's delegated container.

Layout mirrors the bf16 cache, plus a scale array one axis short:

    q [.., KvH, S, hd] int8      s [.., KvH, S] f32

(latent attention's one row a position, models/decoder.py, has two parts of
two sizes and a scale for each: q [.., 1, S, C + dr], s [.., 2, S],
``quantize_latent``, which can keep the key's rounding in a second code)

The arithmetic stays exact-shaped with the dense path (ops/attention.py
``attend_hf``): scores pick up the key scale AFTER the q·k dot (the scale
is per key position, so it factors out), and the value scale folds into
the probabilities before the p·v dot — dequantized V tensors never
materialise:

    scores[.., t, j] = (q_t · kq_j) * ks_j
    out[.., t]       = Σ_j (p_tj * vs_j) · vq_j

int4 (paged pools only, TPU_KV_DTYPE=int4): same per-(position, head)
scale layout, codes in [-7, 7] (scale = amax/7, ops/quant.py's symmetric
int4 range) stored two POSITIONS per byte along the page axis —

    q4 [.., KvH, ps//2, hd] uint-packed int8      s [.., KvH, ps] f32

position 2j rides the low nibble, 2j+1 the high nibble, both biased +8
(codes land in 1..15; 8 == 0.0 is the empty-pool value is wrong — zeros
decode to -8*scale, but empty pages carry scale 0 so they still read as
exact 0.0). Packing along the position (sublane) axis keeps the pool's
128-lane head dim intact, which is what lets the fused pallas kernel DMA
int4 pages with the same lane alignment as int8 ones.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .attention import NEG_INF, softcap_scores


# what a second code of a latent row's rotated key counts in: the first code's
# rounding lies in [-1/2, 1/2] of its step, 254 of these steps
RESIDUAL_STEPS = 254


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., hd] float → (int8 [..., hd], f32 scale [...])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = amax / 127.0
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(s[..., None], 1e-30))
    return jnp.clip(q, -127, 127).astype(jnp.int8), s


def quantize_latent(row: jax.Array, split: int, residual: int = 0
                    ) -> Tuple[jax.Array, jax.Array]:
    """A latent-attention row [..., C + dr] = [normed latent | rotated key]
    -> (int8 [..., C + dr], f32 scales [..., 2]): the two parts differ in
    size (a norm's output against a raw projection), so each has its own
    scale, the latent's first; ``split`` = C.

    With ``residual`` = dr (``ModelConfig.latent_key_residual``: rows whose
    rounding the softmax's scale amplifies) the key's dr codes are followed
    by as many again that hold what the first rounded away, in 1 /
    ``RESIDUAL_STEPS`` of the first's step: key = scale x (code + code' /
    RESIDUAL_STEPS), so a query [q | q / RESIDUAL_STEPS] scores both in the
    one dot. -> int8 [..., C + 2 dr], then whatever channels the row had
    past them (a cache's padding, handed on)."""
    lq, ls = quantize_kv(row[..., :split])
    if not residual:
        rq, rs = quantize_kv(row[..., split:])
        return jnp.concatenate([lq, rq], axis=-1), jnp.stack([ls, rs], axis=-1)
    key = row[..., split:split + residual].astype(jnp.float32)
    rq, rs = quantize_kv(key)
    left = key / jnp.maximum(rs[..., None], 1e-30) - rq
    fine = jnp.clip(jnp.round(left * RESIDUAL_STEPS), -127, 127)
    codes = jnp.concatenate(
        [lq, rq, fine.astype(jnp.int8),
         row[..., split + 2 * residual:].astype(jnp.int8)], axis=-1)
    return codes, jnp.stack([ls, rs], axis=-1)


def attend_hf_q(q, kc: Dict, vc: Dict, mask, scale: float,
                softcap: float = 0.0, attn_len=None, compute_dtype=None):
    """Grouped-query attention against the quantized head-first cache.

    q [B, T, H, hd]; kc/vc {"q" [B, KvH, S, hd] int8, "s" [B, KvH, S]};
    mask [B, 1, T, A] additive. → [B, T, H, hd] (q.dtype).
    """
    B, T, H, hd = q.shape
    kq, ks = kc["q"], kc["s"]
    vq, vs = vc["q"], vc["s"]
    if attn_len is not None and attn_len < kq.shape[2]:
        kq, ks = kq[:, :, :attn_len], ks[:, :, :attn_len]
        vq, vs = vq[:, :, :attn_len], vs[:, :, :attn_len]
    KvH = kq.shape[1]
    G = H // KvH
    dt = compute_dtype or q.dtype
    qg = q.reshape(B, T, KvH, G, hd)
    scores = jnp.einsum("btkgh,bksh->bkgts", qg, kq.astype(dt),
                        preferred_element_type=jnp.float32)
    scores = scores * ks[:, :, None, None, :]          # key scale, per j
    scores = softcap_scores(scores * scale, softcap)
    scores = scores + mask[:, :, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    pv = (probs * vs[:, :, None, None, :]).astype(dt)  # value scale into p
    out = jnp.einsum("bkgts,bksh->btkgh", pv, vq.astype(dt))
    return out.reshape(B, T, H, hd)


def is_quantized_cache(kc) -> bool:
    return isinstance(kc, dict) and ("q" in kc or "q4" in kc) and "s" in kc


def empty_cache(L: int, B: int, KvH: int, S: int, hd: int) -> Dict:
    return {"q": jnp.zeros((L, B, KvH, S, hd), jnp.int8),
            "s": jnp.zeros((L, B, KvH, S), jnp.float32)}


# --------------------------------------------------------------------------
# int4 pool codecs (per-page KV layout)
# --------------------------------------------------------------------------

INT4_BIAS = 8   # stored nibble = code + 8, codes in [-7, 7]


def pool_codes(pool: Dict) -> jax.Array:
    """The code array of a quantized pool dict ({"q"} int8 or {"q4"}
    nibble-packed)."""
    return pool["q4"] if "q4" in pool else pool["q"]


def pool_bits(pool) -> int:
    """Code width of a pool: 4 for nibble-packed dicts, 8 for int8 dicts,
    and the storage itemsize*8 for plain (unquantized) arrays."""
    if isinstance(pool, dict):
        return 4 if "q4" in pool else 8
    return pool.dtype.itemsize * 8


def quantize_kv4(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., hd] float → (int4 codes [-7, 7] as int8 [..., hd], f32 scale
    [...]). Same shape contract as ``quantize_kv``; packing into nibbles
    is a separate step because the paged scatter needs per-position codes
    (``pack_kv4`` / the read-modify-write nibble scatter in
    models/decoder.py)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = amax / 7.0
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(s[..., None], 1e-30))
    return jnp.clip(q, -7, 7).astype(jnp.int8), s


def pack_kv4(codes: jax.Array, axis: int = -2) -> jax.Array:
    """Pack int4 codes [-7, 7] pairwise along ``axis`` (the position axis;
    must be even-sized): position 2j → low nibble, 2j+1 → high nibble,
    biased +8. Returns int8 with ``axis`` halved."""
    codes = jnp.moveaxis(codes, axis, -1)
    n = codes.shape[-1]
    assert n % 2 == 0, f"pack_kv4: axis size {n} must be even"
    b = (codes + INT4_BIAS).astype(jnp.uint8)
    lo, hi = b[..., 0::2], b[..., 1::2]
    packed = (lo | (hi << 4)).astype(jnp.int8)
    return jnp.moveaxis(packed, -1, axis)


def unpack_kv4(packed: jax.Array, axis: int = -2) -> jax.Array:
    """Inverse of ``pack_kv4``: int8 nibble pairs → int4 codes [-7, 7]
    (int8), ``axis`` doubled."""
    b = jnp.moveaxis(packed, axis, -1).astype(jnp.uint8)
    lo = (b & 0xF).astype(jnp.int8) - INT4_BIAS
    hi = ((b >> 4) & 0xF).astype(jnp.int8) - INT4_BIAS
    out = jnp.stack([lo, hi], axis=-1)            # [..., n//2, 2]
    out = out.reshape(*out.shape[:-2], -1)        # [..., n]
    return jnp.moveaxis(out, -1, axis)


def attend_hf_q4(q, kc: Dict, vc: Dict, mask, scale: float,
                 softcap: float = 0.0, attn_len=None, compute_dtype=None):
    """``attend_hf_q`` over an int4 pool view: unpack the nibble codes
    back to per-position int8 codes, then run the shared scaled-dot path
    (the unpack is a register-level shift/mask — no f32 KV materialises).
    kc/vc {"q4" [B, KvH, S//2, hd], "s" [B, KvH, S]}."""
    kc8 = {"q": unpack_kv4(kc["q4"]), "s": kc["s"]}
    vc8 = {"q": unpack_kv4(vc["q4"]), "s": vc["s"]}
    return attend_hf_q(q, kc8, vc8, mask, scale, softcap, attn_len,
                       compute_dtype)
