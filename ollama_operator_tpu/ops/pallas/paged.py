"""Paged-attention decode kernel (block-table KV cache).

The serving cache is a physical page pool ``[L, P, KvH, ps, hd]`` shared by
all slots; a slot's logical positions ``[0, len)`` live in the pages listed
by its block-table row (``runtime/paged.py`` owns allocation). This module
holds the one kernel that decodes against that pool and the one statement
of the shapes it takes (:func:`paged_decode_tileable`). It is the
``paged_v3`` of every ``breakdown.device_ops`` line of both paged cells
(ledger, PRs 25-30); a shape it refuses is served by gather + einsum
(``models/decoder._paged_attend``) and flagged ``kernel_fallback``.

- **One grid step per slot, ONE walk over the batch's live pages.** Tables,
  per-slot lengths and the layer index are prefetched scalars
  (``PrefetchScalarGridSpec``); the pools stay in HBM and the kernel copies
  ``pool[layer, table[b, i]]`` into VMEM itself (``make_async_copy``), up
  to three pages in flight while page ``i`` is scored (``_walk_depth``: as
  many buffers as the page's bytes leave room for). The walk does not
  drain at a slot's end: after slot ``b``'s last page the next copy is
  slot ``b + 1``'s first live page, started in ``b``'s grid step and waited
  for in the next, so buffers, semaphores and the walk's four words of
  state are scratch that outlives a grid step: a batch of short slots
  (starcoder2's cell: 1,920 slot visits a step, two pages each) never
  waits for a first page with nothing in flight. A 100-token slot in a
  4096-token bucket reads two pages, not the bucket, and its dead blocks
  cost no grid step. The full ``[L, ...]`` pool is the operand: no
  per-layer slice is ever materialised.
- **KvH-batched dots.** A page arrives across all its KV heads
  (``[KvH, ps, hd]``, one contiguous copy) and is scored by one
  ``dot_general`` with the head as batch dimension, and one more for p·v:
  two MXU dispatches a page whatever the head count, which is what lets
  MHA (phi-2, KvH = 32) page at all.
- **Lane-wise dequant.** For the quantized pool the per-position scales
  multiply the score matrix (``s * k_scale``) and the probability matrix
  (``p * v_scale``), both lane-aligned broadcasts, so dequant adds no
  relayout and page copies stay int8. int4 pools (``{"q4": ..}``, two
  positions per byte along the page axis, ops/quant_cache.py) copy at half
  that width again and unpack in-register (``_unpack4``) before the dots,
  same scale algebra. Scale pools ride as ``[L, P, KvH, 1, sp]``, ``sp``
  the page size padded to 128 lanes (the engine builds them so).
- **bf16 score/probability dots.** int8 codes are exact in bf16's 8-bit
  mantissa and the MXU is bf16-native. f32 activations (CPU tests) keep
  f32 dots for bit-stable parity.

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


# What the walk keeps in VMEM, read from the shapes alone: as many (k, v,
# scales) page sets as _PAGE_BUFFER_BYTES hold, between the double buffer
# and _MAX_DEPTH. depth - 1 pages are in flight ahead of the flash update,
# ACROSS slots (BASELINE.md r5's "4 neutral" was of a deeper queue inside
# one slot, where a slot of one or two pages has nothing more to fetch).
# PERF.md section 6, PR 48: four buffers are worth 14% of phi-2's visit over
# two (557 KB a set) and 5% of starcoder2's (66 KB); three read slower than
# two there (a remainder by 3 in the page loop), and no small page gets three.
_PAGE_BUFFER_BYTES = 4 << 20
_MAX_DEPTH = 4


def _walk_depth(page_bytes: int) -> int:
    """Buffers for a page set of ``page_bytes`` (k + v + scales, as stored)."""
    return max(2, min(_MAX_DEPTH, _PAGE_BUFFER_BYTES // page_bytes))


def paged_decode_tileable(H: int, k_pool, interpret: bool) -> bool:
    """True iff :func:`paged_decode_attention` will NOT bail for ``H`` query
    heads over this pool (plain, ``{"q","s"}`` or ``{"q4","s"}``). The one
    statement of the kernel's shapes, as ``flash.prefill_tileable`` is of
    its kernel's: ``decoder._paged_kernel_usable`` asks it before the layer
    scan is traced, so a refused shape is routed, and flagged, once. A
    dp/tp-manual region gets the same answer per device: tp divides H and
    KvH alike, dp cuts only the page axis."""
    quant, quant4, k_arr, _ = _pool_arrs(k_pool, k_pool)
    KvH, psq, hd = k_arr.shape[2:]
    ps = psq * 2 if quant4 else psq            # logical vs stored rows
    if KvH <= 0 or H % KvH:                    # whole query groups a kv head
        return False
    if ps % 8 or not _lane_ok(hd, interpret):  # a page's (sublane, lane)
        return False
    if interpret:
        return True
    # Mosaic's rules for the kernel's own copies; the interpreter has none
    if quant and k_pool["s"].shape[-1] % 128:
        return False       # an f32 copy needs a 128-lane minor dim
    if quant4 and psq % 32:
        return False       # int8 tiles (32, 128): packed pages >= 64 tokens
    return True


def _flash_page_update(qv, kb, vb, ksc, vsc, m_ref, l_ref, acc_ref, *,
                       k_start, qp, scale: float, softcap: float,
                       window: int, ps: int, kvh: int, gp: int, cdt):
    """KvH-batched online-softmax update for ONE [KvH, ps, hd] page: one
    score dot and one p·v dot, batch dim = kv head. ``ksc``/
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


def _paged_kernel(lay_ref, len_ref, tbl_ref, q_ref, k_hbm, v_hbm, *rest,
                  scale: float, softcap: float, window: int,
                  ps: int, kvh: int, gp: int, cdt,
                  quant: bool, quant4: bool, depth: int):
    """One grid step per SLOT, one walk over the LIVE pages (those inside
    the window, if any) of the whole batch: the copies run ``depth - 1``
    pages ahead of the :func:`_flash_page_update` that scores them and do
    not stop at a slot's last page: the next page in flight is then slot
    ``b + 1``'s first, started in this grid step and waited for in the next.

    Refs (in order): prefetched lay/len/tbl scalars; q [1, KvH, Gp, hd]
    VMEM block; k/v pools ([L, P, KvH, ps, hd], HBM, copied by hand);
    with ``quant`` the k/v scale pools ([L, P, KvH, 1, sp] f32, HBM); the
    output block; then scratch, all of which outlives a grid step:
    kbuf/vbuf [depth, KvH, ps, hd], (ksbuf/vsbuf [depth, KvH, 1, sp],)
    acc [KvH, Gp, hd] f32, m/l [KvH, Gp, 1] f32, sem, and the walk's state
    in SMEM: slot and page of the next copy to start, that slot's last
    page + 1, and the count of pages scored so far, whose remainder by
    ``depth`` is the buffer in turn.
    """
    if quant:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf,
         acc_ref, m_ref, l_ref, sem, walk) = rest
    else:
        o_ref, kbuf, vbuf, acc_ref, m_ref, l_ref, sem, walk = rest
        ks_hbm = vs_hbm = ksbuf = vsbuf = None
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    lay = lay_ref[0]

    def live(slot):
        """(first, one past the last) live page of ``slot``: the pages
        covering [0, qp], from the first block that holds a key inside the
        window (older positions in that block are masked off below).
        Positions are not negative, so the truncating ``lax.div`` is the
        floor, at a twentieth of ``//``'s scalar operations: the walk
        computes this once a page."""
        qp = len_ref[slot]                   # query's absolute position
        first = jnp.int32(0)
        if window:
            first = jax.lax.div(jnp.maximum(qp - window + 1, 0), ps)
        return first, jax.lax.div(qp, ps) + 1

    def copies(pg, buf):
        yield pltpu.make_async_copy(k_hbm.at[lay, pg], kbuf.at[buf],
                                    sem.at[0, buf])
        yield pltpu.make_async_copy(v_hbm.at[lay, pg], vbuf.at[buf],
                                    sem.at[1, buf])
        if quant:
            yield pltpu.make_async_copy(ks_hbm.at[lay, pg], ksbuf.at[buf],
                                        sem.at[2, buf])
            yield pltpu.make_async_copy(vs_hbm.at[lay, pg], vsbuf.at[buf],
                                        sem.at[3, buf])

    def fetch(fb, fi, fend, buf):
        """Start the copies of slot ``fb``'s page ``fi`` (nothing once the
        batch's last page is on its way) and step to the pair after it:
        every slot has a live page, so a slot's last (``fend - 1``) is
        followed by the next slot's first."""
        @pl.when(fb < nslots)
        def _start():
            for c in copies(tbl_ref[fb, fi], buf):
                c.start()

        nb = fb + 1
        first, nlive = live(jnp.minimum(nb, nslots - 1))   # rows end there
        last = fi + 1 >= fend
        return (jnp.where(last, nb, fb), jnp.where(last, first, fi + 1),
                jnp.where(last, nlive, fend))

    @pl.when(b == 0)
    def _prime():
        # depth - 1 pages in flight before the first wait, once a call
        state = (jnp.int32(0), *live(0))
        for j in range(depth - 1):
            state = fetch(*state, j)
        walk[0], walk[1], walk[2], walk[3] = *state, jnp.int32(0)

    qp = len_ref[b]
    first, nlive = live(b)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    qv = q_ref[0]                            # [KvH, Gp, hd]

    def body(i, carry):
        *ahead, n = carry
        ahead = fetch(*ahead, jax.lax.rem(n + depth - 1, depth))
        buf = jax.lax.rem(n, depth)
        for c in copies(0, buf):             # a wait reads shape and sem
            c.wait()
        # scale buffers are 4-D [depth, KvH, 1, sp] (a 3-D buffer's
        # dynamic-slot load lowers as an unsupported gather) and
        # lane-padded to sp >= ps (Mosaic DMA tile rule); the unit axis
        # is the broadcast axis and only the live ps lanes multiply
        kb, vb = kbuf[buf], vbuf[buf]
        if quant4:
            # pages land nibble-packed [KvH, ps//2, hd]; unpack after the
            # (half-width) DMA so HBM traffic stays at int4
            kb, vb = _unpack4(kb), _unpack4(vb)
        _flash_page_update(
            qv, kb, vb,
            ksbuf[buf][:, :, :ps] if quant else None,
            vsbuf[buf][:, :, :ps] if quant else None,
            m_ref, l_ref, acc_ref,
            k_start=i * ps, qp=qp, scale=scale, softcap=softcap,
            window=window, ps=ps, kvh=kvh, gp=gp, cdt=cdt)
        return (*ahead, n + 1)

    walk[0], walk[1], walk[2], walk[3] = jax.lax.fori_loop(
        first, nlive, body, (walk[0], walk[1], walk[2], walk[3]))
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)   # [KvH, Gp, hd] — caller reshapes


def paged_decode_attention(q, k_pool, v_pool, layer, tables, lengths,
                           scale: float, softcap: float = 0.0,
                           sliding_window: int = 0, *, nblk: int,
                           interpret: bool = False):
    """Single-token attention against the paged pool.

    q        [B, 1, H, hd]
    k_pool   [L, P, KvH, ps, hd] (bf16/f32), {"q": int8 pool,
             "s": [L, P, KvH, sp] f32 scales}, or {"q4": nibble-packed
             [L, P, KvH, ps//2, hd] int8, "s": same scale layout}
    layer    [] / [1] int32 — which L slice to attend
    tables   [B, NBLK] int32 physical page per logical block
    lengths  [B] int32 — query's absolute position per slot
    nblk     static attention bucket in pages; only bounds validity (the
             tables must cover it): the walked range is the slot's live
             count
    → [B, 1, H, hd] (q.dtype), or None when the shapes don't tile
    (:func:`paged_decode_tileable`).
    """
    B, T, H, hd_q = q.shape
    if (T != 1 or nblk > tables.shape[1]
            or not paged_decode_tileable(H, k_pool, interpret)):
        return None
    quant, quant4, k_arr, v_arr = _pool_arrs(k_pool, v_pool)
    L, P, KvH, psq, hd = k_arr.shape
    ps = psq * 2 if quant4 else psq            # logical vs stored rows
    G = H // KvH
    Gp = max(8, -(-G // 8) * 8)
    cdt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
    qg = q.reshape(B, KvH, G, hd_q)
    if Gp != G or hd != hd_q:
        # group rows pad to a sublane multiple; the head dim pads to the
        # pool's 128-lane width (the engine pads the POOL; zero q lanes are
        # inert in the score dot and the pad outputs are sliced off below)
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, hd - hd_q)))

    page_bytes = KvH * psq * hd * (k_arr.dtype.itemsize
                                   + v_arr.dtype.itemsize)
    if quant:
        sp = k_pool["s"].shape[-1]
        page_bytes += 2 * KvH * sp * 4
    depth = _walk_depth(page_bytes)

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
        pltpu.SMEM((4,), jnp.int32),
    ]

    kernel = functools.partial(
        _paged_kernel, scale=scale, softcap=softcap, window=sliding_window,
        ps=ps, kvh=KvH, gp=Gp, cdt=cdt, quant=quant, quant4=quant4,
        depth=depth)
    out = pl.pallas_call(
        kernel,
        name="paged_v3",       # the ledger's name for it since PR 25
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KvH, Gp, hd),
                                   lambda b, *pref: (b, 0, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KvH, Gp, hd), q.dtype),
        # sequential: copies, semaphores and the walk's state cross steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), tables.astype(jnp.int32),
      *args)
    note_kernel("paged_decode", "paged_v3")
    return out[:, :, :G, :hd_q].reshape(B, 1, H, hd_q)
