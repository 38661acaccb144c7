"""Paged-attention decode kernel (block-table KV cache).

The serving cache is a physical page pool ``[L, P, KvH, ps, hd]`` shared by
all slots; a slot's logical positions ``[0, len)`` live in the pages listed
by its block-table row (``runtime/paged.py`` owns allocation). This kernel
is the decode step against that pool:

- **Block-table indirection via scalar prefetch.** Tables and per-slot
  lengths ride in SMEM (``PrefetchScalarGridSpec``), so the K/V index map
  dereferences ``table[b, block]`` at grid time — pages are DMA'd straight
  out of the pool with no gather copy.
- **Head-blocked grid (B, nblk).** Each step reads a page ACROSS all its
  KV heads (one [KvH, ps, hd] DMA) and runs the per-head flash updates
  unrolled inside the kernel. The first on-chip capture ran the old
  (B, KvH, nblk) grid and measured phi (MHA, KvH=32) at 233 ms/step —
  16384 tiny 8 KB steps/layer, 2.1% of HBM bandwidth; folding heads into
  the block cuts the grid by KvH and makes every DMA page-contiguous.
- **Per-slot DMA elision.** The block index is clamped to the slot's last
  live block; Pallas elides the repeated DMA and ``@pl.when`` skips the
  math — a 100-token slot in a 4096-token-bucket batch reads 1-2 pages,
  not the bucket.
- **Lane-wise int8 dequant.** For the quantized pool the per-position
  scales multiply the score matrix (``s * k_scale[None, :]``) and the
  probability matrix (``p * v_scale[None, :]``) — both lane-aligned
  broadcasts, so dequant adds no relayout and page DMAs stay int8. int4
  pools (``{"q4": ..}``, two positions per byte along the page axis —
  ops/quant_cache.py) DMA at half that width again and unpack in-register
  (``_unpack4``) before the dots, same scale algebra. Scales
  ride as [L, P, KvH, 1, ps]: the unit axis keeps the block's trailing
  dims equal to their array dims (Mosaic's (8,128) rule — the 4D spec
  lowers in interpret mode but is rejected by the real TPU lowering).
- **bf16 score/probability dots.** int8 codes are exact in bf16's 8-bit
  mantissa and the MXU is bf16-native; dotting f32 (the first kernel
  generation) runs at a fraction of MXU rate. f32 activations (CPU
  tests) keep f32 dots for bit-stable parity.

The layer index is a prefetched scalar too: the kernel reads the full
``[L, ...]`` pool and the grid never materialises a per-layer slice.

The reference delegates paged/continuous batching to llama.cpp inside the
`ollama/ollama` image (/root/reference/pkg/model/pod.go:11); this is its
TPU-native equivalent (SURVEY.md §7 hard-part 2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF, note_kernel, softcap_scores
from .flash import _lane_ok


def _unpack4(kb):
    """Nibble-packed page rows [..., ps//2, hd] int8 → int4 codes [-7, 7]
    as int8 [..., ps, hd] (position 2j rides the low nibble —
    ops/quant_cache.pack_kv4). A register-level shift/mask + sublane
    interleave; the page DMA itself stays at int4 width, which is the
    whole bandwidth win."""
    b = kb.astype(jnp.uint8)
    lo = (b & 0xF).astype(jnp.int8) - 8
    hi = ((b >> 4) & 0xF).astype(jnp.int8) - 8
    st = jnp.stack([lo, hi], axis=-2)          # [..., ps//2, 2, hd]
    return st.reshape(*kb.shape[:-2], kb.shape[-2] * 2, kb.shape[-1])


def _pool_arrs(k_pool, v_pool):
    """(quant, quant4, k_arr, v_arr) for a plain / {"q","s"} / {"q4","s"}
    pool pair."""
    quant = isinstance(k_pool, dict)
    quant4 = quant and "q4" in k_pool
    k_arr = (k_pool["q4"] if quant4 else k_pool["q"]) if quant else k_pool
    v_arr = (v_pool["q4"] if quant4 else v_pool["q"]) if quant else v_pool
    return quant, quant4, k_arr, v_arr


def _paged_kernel(lay_ref, len_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, softcap: float, window: int,
                  ps: int, nblk: int, kvh: int, gp: int, cdt,
                  quant: bool, quant4: bool = False,
                  ks_ref=None, vs_ref=None):
    # NB: scale blocks span the full (possibly 128-lane-padded) scale
    # array dim; reads below slice the live [: ps] lanes
    """Grid (B, nblk). Block ki covers the slot's logical positions
    [ki*ps, (ki+1)*ps) across ALL KvH heads; the per-head flash updates
    are unrolled below (static python loop — KvH is a trace-time
    constant). With ``quant`` the k/v refs are int8 pages and ks/vs carry
    the per-position f32 scales; with ``quant4`` the pages are
    nibble-packed ([ps//2, hd] stored rows) and unpack in-register before
    the dots — ``ps`` is always the LOGICAL page size."""
    b, ki = pl.program_id(0), pl.program_id(1)
    qp = len_ref[b]                        # query's absolute position

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    k_start = ki * ps
    needed = k_start <= qp
    if window:
        needed = jnp.logical_and(needed, k_start + ps - 1 > qp - window)

    @pl.when(needed)
    def _step():
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (gp, ps), 1)
        ok = k_pos <= qp
        if window:
            ok = jnp.logical_and(ok, k_pos > qp - window)
        for h in range(kvh):               # unrolled per kv head
            r0 = h * gp
            q = q_ref[0, h, :, :].astype(cdt)                 # [Gp, hd]
            kb = k_ref[0, 0, h, :, :]                         # [ps, hd]
            if quant4:
                kb = _unpack4(kb)          # [ps//2, hd] packed → [ps, hd]
            s = jax.lax.dot_general(
                q, kb.astype(cdt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [Gp, ps]
            if quant:
                # per-position k scale: lane-aligned broadcast
                s = s * ks_ref[0, 0, h, 0, :ps][None, :]
            s = softcap_scores(s, softcap)
            s = jnp.where(ok, s, NEG_INF)

            m_prev = m_ref[r0:r0 + gp, :]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(m_cur > NEG_INF / 2, jnp.exp(s - m_cur), 0.0)
            alpha = jnp.exp(m_prev - m_cur)
            l_ref[r0:r0 + gp, :] = (l_ref[r0:r0 + gp, :] * alpha
                                    + jnp.sum(p, axis=-1, keepdims=True))
            vb = v_ref[0, 0, h, :, :]                         # [ps, hd]
            if quant4:
                vb = _unpack4(vb)
            if quant:
                # fold the per-position v scale into p (lane-aligned)
                p = p * vs_ref[0, 0, h, 0, :ps][None, :]
            acc_ref[r0:r0 + gp, :] = (
                acc_ref[r0:r0 + gp, :] * alpha + jax.lax.dot_general(
                    p.astype(cdt), vb.astype(cdt),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            m_ref[r0:r0 + gp, :] = m_cur

    @pl.when(ki == nblk - 1)
    def _done():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, :, :] = out.astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, layer, tables, lengths,
                           scale: float, softcap: float = 0.0,
                           sliding_window: int = 0, *, nblk: int,
                           interpret: bool = False):
    """Single-token attention against the paged pool.

    q        [B, 1, H, hd]
    k_pool   [L, P, KvH, ps, hd] (bf16/f32), {"q": int8 pool,
             "s": [L, P, KvH, ps] f32 scales}, or {"q4": nibble-packed
             [L, P, KvH, ps//2, hd] int8, "s": same scale layout}
    layer    [] / [1] int32 — which L slice to attend
    tables   [B, NBLK] int32 physical page per logical block
    lengths  [B] int32 — query's absolute position per slot
    nblk     static number of grid blocks (attention bucket // ps;
             must be <= NBLK)
    → [B, 1, H, hd] (q.dtype), or None when the shapes don't tile.

    The live-page async-DMA pipeline (:func:`paged_decode_attention_v3`)
    is the DEFAULT — the round-4 same-window A/B measured it ahead of
    this grid kernel everywhere (GQA short +2%, GQA long-context +17%,
    MHA +30%; BASELINE.md round-4). ``TPU_PAGED_V3=0`` opts back into
    the v2 grid kernel below.
    """
    import os
    if os.environ.get("TPU_PAGED_V4", "0") == "1":
        # experimental compacted flat-grid formulation (A/B against v3
        # before any default change)
        out = paged_decode_attention_v4(
            q, k_pool, v_pool, layer, tables, lengths, scale, softcap,
            sliding_window, nblk=nblk, interpret=interpret)
        if out is not None:
            note_kernel("paged_decode", "paged_v4")
            return out
    want_v3 = os.environ.get("TPU_PAGED_V3", "1") == "1"
    if want_v3:
        out = paged_decode_attention_v3(
            q, k_pool, v_pool, layer, tables, lengths, scale, softcap,
            sliding_window, nblk=nblk, interpret=interpret)
        if out is not None:
            note_kernel("paged_decode", "paged_v3")
            return out
    quant, quant4, k_arr, v_arr = _pool_arrs(k_pool, v_pool)
    B, T, H, hd_q = q.shape
    L, P, KvH, psq, hd = k_arr.shape
    ps = psq * 2 if quant4 else psq            # logical vs stored rows
    NBLK = tables.shape[1]
    if T != 1 or H % KvH or not _lane_ok(hd, interpret) or nblk > NBLK:
        return None
    if ps % 8:
        return None
    G = H // KvH
    Gp = max(8, -(-G // 8) * 8)
    cdt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32

    qg = q.reshape(B, KvH, G, hd_q)
    if Gp != G or hd != hd_q:
        # group rows pad to a sublane multiple; the head dim pads to the
        # pool's 128-lane width (engine pads the POOL; zero q lanes are
        # inert in the score dot and the pad outputs are sliced off below)
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, hd - hd_q)))

    def kv_index(b, ki, lay_ref, len_ref, tbl_ref):
        last = len_ref[b] // ps
        pg = tbl_ref[b, jnp.minimum(ki, last)]
        return (lay_ref[0], pg, 0, 0, 0)

    kernel = functools.partial(
        _paged_kernel, scale=scale, softcap=softcap, window=sliding_window,
        ps=ps, nblk=nblk, kvh=KvH, gp=Gp, cdt=cdt, quant=quant,
        quant4=quant4)
    in_specs = [
        pl.BlockSpec((1, KvH, Gp, hd), lambda b, ki, *pref: (b, 0, 0, 0)),
        pl.BlockSpec((1, 1, KvH, psq, hd), kv_index),
        pl.BlockSpec((1, 1, KvH, psq, hd), kv_index),
    ]
    args = [qg, k_arr, v_arr]
    if quant:
        def kernel(*refs):  # noqa: F811 — rebind scale refs by position
            (lay_ref, len_ref, tbl_ref, q_ref, k_ref, v_ref,
             ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref) = refs
            return _paged_kernel(
                lay_ref, len_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                acc_ref, m_ref, l_ref, scale=scale, softcap=softcap,
                window=sliding_window, ps=ps, nblk=nblk, kvh=KvH, gp=Gp,
                cdt=cdt, quant=True, quant4=quant4,
                ks_ref=ks_ref, vs_ref=vs_ref)
        # scale arrays may be lane-padded past ps (engine pads to the 128
        # tile for the v3 DMA path); the block stays ps wide at block
        # index 0, so only the live lanes are read
        sp = k_pool["s"].shape[-1]
        in_specs += [pl.BlockSpec((1, 1, KvH, 1, sp), kv_index),
                     pl.BlockSpec((1, 1, KvH, 1, sp), kv_index)]
        args += [k_pool["s"].reshape(L, P, KvH, 1, -1),
                 v_pool["s"].reshape(L, P, KvH, 1, -1)]

    out = pl.pallas_call(
        kernel,
        name="paged_v2",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nblk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KvH * Gp, hd),
                                   lambda b, ki, *pref: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KvH * Gp, hd), jnp.float32),
                pltpu.VMEM((KvH * Gp, 1), jnp.float32),
                pltpu.VMEM((KvH * Gp, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KvH * Gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), tables.astype(jnp.int32),
      qg, *args[1:])
    out = out.reshape(B, KvH, Gp, hd)
    # v3 asked for and refused by its tiling rules lands here
    note_kernel("paged_decode", "paged_v2", fell_back=want_v3)
    return out[:, :, :G, :hd_q].reshape(B, 1, H, hd_q)


# ---------------------------------------------------------------------------
# shared pieces of the v3/v4 formulations
# ---------------------------------------------------------------------------

def _flash_page_update(qv, kb, vb, ksc, vsc, m_ref, l_ref, acc_ref, *,
                       k_start, qp, scale: float, softcap: float,
                       window: int, ps: int, kvh: int, gp: int, cdt):
    """KvH-batched online-softmax update for ONE [KvH, ps, hd] page —
    the body both the v3 per-slot walk and the v4 flat grid run per live
    page (one score dot + one p·v dot, batch dim = kv head). ``ksc``/
    ``vsc`` are the per-position dequant scale rows ([KvH, ·, ps]) or
    None for bf16/f32 pools. Mutates m/l/acc scratch in place."""
    s = jax.lax.dot_general(
        qv.astype(cdt), kb.astype(cdt), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale      # [KvH, Gp, ps]
    if ksc is not None:
        s = s * ksc
    s = softcap_scores(s, softcap)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (kvh, gp, ps), 2)
    ok = k_pos <= qp
    if window:
        ok = jnp.logical_and(ok, k_pos > qp - window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(m_cur > NEG_INF / 2, jnp.exp(s - m_cur), 0.0)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if vsc is not None:
        p = p * vsc
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(cdt), vb.astype(cdt), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur


def _prep_paged(q, k_pool, v_pool, tables, nblk: int, interpret: bool):
    """Shared v3/v4 wrapper preamble: shape/tiling guards and the padded
    grouped query. Returns None when the shapes don't tile (the caller
    bails to the next formulation), else
    (quant, quant4, k_arr, v_arr, dims, sp, G, Gp, cdt, qg) with
    dims = (B, H, hd_q, L, P, KvH, ps, hd); ``ps`` is the LOGICAL page
    size (nibble-packed int4 pools store ps//2 physical rows)."""
    quant, quant4, k_arr, v_arr = _pool_arrs(k_pool, v_pool)
    B, T, H, hd_q = q.shape
    L, P, KvH, ps, hd = k_arr.shape
    if quant4:
        ps *= 2
    NBLK = tables.shape[1]
    if T != 1 or H % KvH or not _lane_ok(hd, interpret) or nblk > NBLK:
        return None
    if ps % 8:
        return None
    sp = k_pool["s"].shape[-1] if quant else ps
    G = H // KvH
    Gp = max(8, -(-G // 8) * 8)
    cdt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
    qg = q.reshape(B, KvH, G, hd_q)
    if Gp != G or hd != hd_q:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, hd - hd_q)))
    return (quant, quant4, k_arr, v_arr, (B, H, hd_q, L, P, KvH, ps, hd),
            sp, G, Gp, cdt, qg)


# ---------------------------------------------------------------------------
# v4: compacted flat-grid (grid over the slot-sorted list of LIVE pages)
# ---------------------------------------------------------------------------

def _paged_kernel_v4(nb_ref, slot_ref, page_ref, blk_ref, lay_ref, len_ref,
                     q_ref, k_ref, v_ref, *rest,
                     scale: float, softcap: float, window: int,
                     ps: int, flat_n: int, kvh: int, gp: int, cdt,
                     quant: bool, quant4: bool = False):
    """Grid (flat_n,): step n processes LIVE page n of the slot-sorted
    flat list (slot_ref/page_ref/blk_ref scalars; nb_ref[0] = live total).

    The design swaps v3's per-slot fori_loop (whose per-page flash update
    serializes behind each DMA wait — the measured B=32 floor) for v2's
    implicit cross-step pipeline, but with ZERO dead interior steps: the
    flat list contains only live pages, consecutive steps of one slot
    revisit the same q/out block (no re-DMA), and dead tail steps beyond
    nb_ref[0] freeze the index maps so their DMAs elide. Dots are
    KvH-batched like v3 (one score + one pv dot_general per page, batch
    dim = kv head), not v2's per-head unrolled chain.

    Accumulators live in scratch [KvH, Gp, hd]; a slot boundary
    (slot_ref[n] != slot_ref[n-1]) resets them, and the slot's LAST live
    page (slot changes at n+1, or n is the live total − 1) normalizes
    and stores the output block."""
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
        ks_ref = vs_ref = None
    n = pl.program_id(0)
    n_total = nb_ref[0]
    slot = slot_ref[n]
    qp = len_ref[slot]
    valid = n < n_total

    first = jnp.logical_or(n == 0, slot_ref[jnp.maximum(n - 1, 0)] != slot)

    @pl.when(jnp.logical_and(valid, first))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(valid)
    def _step():
        kb, vb = k_ref[0, 0], v_ref[0, 0]
        if quant4:
            kb, vb = _unpack4(kb), _unpack4(vb)
        _flash_page_update(
            q_ref[0], kb, vb,
            ks_ref[0, 0][:, :, :ps] if quant else None,
            vs_ref[0, 0][:, :, :ps] if quant else None,
            m_ref, l_ref, acc_ref,
            k_start=blk_ref[n] * ps, qp=qp, scale=scale, softcap=softcap,
            window=window, ps=ps, kvh=kvh, gp=gp, cdt=cdt)

        last = jnp.logical_or(
            n + 1 >= n_total,
            slot_ref[jnp.minimum(n + 1, flat_n - 1)] != slot)

        @pl.when(last)
        def _done():
            out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = out.astype(o_ref.dtype)


def paged_decode_attention_v4(q, k_pool, v_pool, layer, tables, lengths,
                              scale: float, softcap: float = 0.0,
                              sliding_window: int = 0, *, nblk: int,
                              interpret: bool = False):
    """Same contract as :func:`paged_decode_attention`; the compacted
    flat-grid formulation. The flat (slot, page, block) list is built in
    XLA from the live lengths (cumsum + searchsorted) and handed to the
    kernel as prefetched scalars; the static grid is the worst case
    B·nblk, with every step past the live total frozen to the last live
    index so its DMAs elide at the revisit check."""
    prep = _prep_paged(q, k_pool, v_pool, tables, nblk, interpret)
    if prep is None:
        return None
    quant, quant4, k_arr, v_arr, dims, sp, G, Gp, cdt, qg = prep
    B, H, hd_q, L, P, KvH, ps, hd = dims
    psq = ps // 2 if quant4 else ps            # stored page rows
    flat_n = B * nblk

    lengths = lengths.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    nlive = jnp.minimum(lengths // ps + 1, nblk)           # [B]
    ends = jnp.cumsum(nlive)                               # [B]
    starts = ends - nlive
    n_total = ends[-1]
    idx = jnp.arange(flat_n, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, idx, side="right"),
                       B - 1).astype(jnp.int32)            # [flat_n]
    blk = jnp.clip(idx - starts[slot], 0, nblk - 1)
    page = tables[slot, blk]
    # freeze dead tail steps to the LAST live index so their q/kv/out
    # block indices repeat and pallas elides the copies
    live = idx < n_total
    last_blk = jnp.clip(nlive[B - 1] - 1, 0, nblk - 1)
    page = jnp.where(live, page, tables[B - 1, last_blk])
    blk = jnp.where(live, blk, last_blk)

    def q_index(n, nb, slot_r, page_r, blk_r, lay_r, len_r):
        return (slot_r[n], 0, 0, 0)

    def kv_index(n, nb, slot_r, page_r, blk_r, lay_r, len_r):
        return (lay_r[0], page_r[n], 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, KvH, Gp, hd), q_index),
        pl.BlockSpec((1, 1, KvH, psq, hd), kv_index),
        pl.BlockSpec((1, 1, KvH, psq, hd), kv_index),
    ]
    args = [qg, k_arr, v_arr]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, KvH, 1, sp), kv_index),
                     pl.BlockSpec((1, 1, KvH, 1, sp), kv_index)]
        args += [k_pool["s"].reshape(L, P, KvH, 1, -1),
                 v_pool["s"].reshape(L, P, KvH, 1, -1)]

    kernel = functools.partial(
        _paged_kernel_v4, scale=scale, softcap=softcap,
        window=sliding_window, ps=ps, flat_n=flat_n, kvh=KvH, gp=Gp,
        cdt=cdt, quant=quant, quant4=quant4)
    out = pl.pallas_call(
        kernel,
        name="paged_v4",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(flat_n,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KvH, Gp, hd), q_index),
            scratch_shapes=[
                pltpu.VMEM((KvH, Gp, hd), jnp.float32),
                pltpu.VMEM((KvH, Gp, 1), jnp.float32),
                pltpu.VMEM((KvH, Gp, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KvH, Gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(n_total, (1,)).astype(jnp.int32), slot, page, blk,
      jnp.reshape(layer, (1,)).astype(jnp.int32), lengths,
      *args)
    return out[:, :, :G, :hd_q].reshape(B, 1, H, hd_q)


# ---------------------------------------------------------------------------
# v3: live-page async-DMA pipeline (grid (B,), dynamic block loop)
# ---------------------------------------------------------------------------

def _paged_kernel_v3(lay_ref, len_ref, tbl_ref, q_ref, k_hbm, v_hbm, *rest,
                     scale: float, softcap: float, window: int,
                     ps: int, sp: int, kvh: int, gp: int, hd: int, cdt,
                     quant: bool, quant4: bool = False, depth: int = 2):
    """One grid step per SLOT; the kernel walks only the slot's LIVE pages
    with a depth-2 manually-pipelined DMA (pltpu.make_async_copy), so

    - dead grid steps vanish: the v2 grid runs ``nblk`` (= the attention
      bucket) steps per slot and relies on clamped-DMA elision, paying a
      grid-step of overhead per dead block — a mixed-length B=32 batch at
      bucket 1024 is ~80% dead steps;
    - the per-page HBM reads overlap the flash update of the previous
      page (double buffer), instead of riding the grid's implicit
      pipeline across (mostly dead) steps;
    - the per-head python-unrolled flash updates collapse into KvH-batched
      ``dot_general``s (batch dim = kv head): one MXU dispatch per page
      for scores and one for p·v, instead of 2·KvH tiny dispatches (the
      r3 MHA diagnosis: 32 unrolled per-head dots × live blocks × layers
      dominate the step).

    Refs (in order): prefetched lay/len/tbl scalars; q [1, KvH, Gp, hd]
    VMEM block; k/v pools ([L, P, KvH, ps, hd], HBM — DMA'd manually);
    with ``quant`` the k/v scale pools ([L, P, KvH, ps] f32, HBM); the
    output block; then scratch: kbuf/vbuf [2, KvH, ps, hd], (ksbuf/vsbuf
    [2, KvH, ps],) acc [KvH, Gp, hd] f32, m/l [KvH, Gp, 1] f32, sem.
    """
    if quant:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf,
         acc_ref, m_ref, l_ref, sem) = rest
    else:
        o_ref, kbuf, vbuf, acc_ref, m_ref, l_ref, sem = rest
        ks_hbm = vs_hbm = ksbuf = vsbuf = None
    b = pl.program_id(0)
    lay = lay_ref[0]
    qp = len_ref[b]                          # query's absolute position
    nlive = qp // ps + 1                     # pages covering [0, qp]
    start = jnp.int32(0)
    if window:
        # first block holding a key inside the window (older positions in
        # that block are masked off below)
        start = jnp.maximum(start, (qp - window + 1) // ps)

    def start_dma(i, slot):
        pg = tbl_ref[b, i]
        pltpu.make_async_copy(k_hbm.at[lay, pg], kbuf.at[slot],
                              sem.at[0, slot]).start()
        pltpu.make_async_copy(v_hbm.at[lay, pg], vbuf.at[slot],
                              sem.at[1, slot]).start()
        if quant:
            pltpu.make_async_copy(ks_hbm.at[lay, pg], ksbuf.at[slot],
                                  sem.at[2, slot]).start()
            pltpu.make_async_copy(vs_hbm.at[lay, pg], vsbuf.at[slot],
                                  sem.at[3, slot]).start()

    def wait_dma(i, slot):
        pg = tbl_ref[b, i]
        pltpu.make_async_copy(k_hbm.at[lay, pg], kbuf.at[slot],
                              sem.at[0, slot]).wait()
        pltpu.make_async_copy(v_hbm.at[lay, pg], vbuf.at[slot],
                              sem.at[1, slot]).wait()
        if quant:
            pltpu.make_async_copy(ks_hbm.at[lay, pg], ksbuf.at[slot],
                                  sem.at[2, slot]).wait()
            pltpu.make_async_copy(vs_hbm.at[lay, pg], vsbuf.at[slot],
                                  sem.at[3, slot]).wait()

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    # prologue: depth−1 pages in flight before the first wait, so per-page
    # DMA latency amortizes depth−1 deep instead of serializing (depth 2 =
    # the classic double buffer)
    for j in range(depth - 1):
        @pl.when(start + j < nlive)
        def _prime(j=j):
            start_dma(start + j, (start + j) % depth)

    qv = q_ref[0]                            # [KvH, Gp, hd]

    def body(i, _):
        slot = i % depth

        @pl.when(i + depth - 1 < nlive)
        def _prefetch():
            start_dma(i + depth - 1, (i + depth - 1) % depth)

        wait_dma(i, slot)
        # scale buffers are 4-D [depth, KvH, 1, sp] (a 3-D buffer's
        # dynamic-slot load lowers as an unsupported gather) and
        # lane-padded to sp >= ps (Mosaic DMA tile rule); the unit axis
        # is the broadcast axis and only the live ps lanes multiply
        kb, vb = kbuf[slot], vbuf[slot]
        if quant4:
            # pages land nibble-packed [KvH, ps//2, hd]; unpack after the
            # (half-width) DMA so HBM traffic stays at int4
            kb, vb = _unpack4(kb), _unpack4(vb)
        _flash_page_update(
            qv, kb, vb,
            ksbuf[slot][:, :, :ps] if quant else None,
            vsbuf[slot][:, :, :ps] if quant else None,
            m_ref, l_ref, acc_ref,
            k_start=i * ps, qp=qp, scale=scale, softcap=softcap,
            window=window, ps=ps, kvh=kvh, gp=gp, cdt=cdt)
        return 0

    jax.lax.fori_loop(start, nlive, body, 0)
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)   # [KvH, Gp, hd] — caller reshapes


def paged_decode_attention_v3(q, k_pool, v_pool, layer, tables, lengths,
                              scale: float, softcap: float = 0.0,
                              sliding_window: int = 0, *, nblk: int,
                              interpret: bool = False):
    """Same contract as :func:`paged_decode_attention`; the live-page
    async-DMA formulation. ``nblk`` only bounds validity (tables must
    cover it) — the walked range is the slot's live count."""
    import os
    prep = _prep_paged(q, k_pool, v_pool, tables, nblk, interpret)
    if prep is None:
        return None
    quant, quant4, k_arr, v_arr, dims, sp, G, Gp, cdt, qg = prep
    B, H, hd_q, L, P, KvH, ps, hd = dims
    psq = ps // 2 if quant4 else ps            # stored page rows
    if quant and not interpret and sp % 128:
        # manual f32 DMAs need a 128-lane minor dim; unpadded scale pools
        # (hand-built tests, older stores) fall back to the v2 grid kernel
        return None
    if quant4 and not interpret and psq % 32:
        # int8 arrays tile (32, 128); half-width int4 pages below that
        # sublane multiple fall back to the v2 grid kernel
        return None
    # DMA pipeline depth: how many page fetches are in flight ahead of
    # the flash update (2 = classic double buffer). Deeper hides more
    # per-page latency at the cost of depth x page VMEM buffers.
    depth = max(2, int(os.environ.get("TPU_PAGED_DEPTH", "2") or "2"))

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    in_specs = [
        pl.BlockSpec((1, KvH, Gp, hd), lambda b, *pref: (b, 0, 0, 0)),
        hbm, hbm,
    ]
    args = [qg, k_arr, v_arr]
    scratch = [
        pltpu.VMEM((depth, KvH, psq, hd), k_arr.dtype),
        pltpu.VMEM((depth, KvH, psq, hd), v_arr.dtype),
    ]
    if quant:
        in_specs += [hbm, hbm]
        args += [k_pool["s"].reshape(L, P, KvH, 1, -1).astype(jnp.float32),
                 v_pool["s"].reshape(L, P, KvH, 1, -1).astype(jnp.float32)]
        scratch += [pltpu.VMEM((depth, KvH, 1, sp), jnp.float32),
                    pltpu.VMEM((depth, KvH, 1, sp), jnp.float32)]
    scratch += [
        pltpu.VMEM((KvH, Gp, hd), jnp.float32),
        pltpu.VMEM((KvH, Gp, 1), jnp.float32),
        pltpu.VMEM((KvH, Gp, 1), jnp.float32),
        pltpu.SemaphoreType.DMA((4 if quant else 2, depth)),
    ]

    kernel = functools.partial(
        _paged_kernel_v3, scale=scale, softcap=softcap,
        window=sliding_window, ps=ps, sp=sp, kvh=KvH, gp=Gp, hd=hd,
        cdt=cdt, quant=quant, quant4=quant4, depth=depth)
    out = pl.pallas_call(
        kernel,
        name="paged_v3",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KvH, Gp, hd),
                                   lambda b, *pref: (b, 0, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KvH, Gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), tables.astype(jnp.int32),
      *args)
    return out[:, :, :G, :hd_q].reshape(B, 1, H, hd_q)
