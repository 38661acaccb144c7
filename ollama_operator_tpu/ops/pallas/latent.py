"""Latent attention's one-position step, absorbed, over each slot's own rows.

``latent_decode`` is the T == 1 form of ``models/decoder._latent_absorbed``:
for every slot it reads the layer's cached rows ``[latent | rotated key |
padding]`` where they lie and returns the absorbed output ``o_lat [B, H,
C]`` = softmax(scores) . latent, which the caller expands through ``w_uv``.
What the compiler makes of the einsum form reads every slot to the batch's
attended bucket (the decode programs are bucketed by the LONGEST live
context) and first copies the layer's window of the cache out for its three
consumers (PERF.md, PR 46: 4.67 + 3.77 ms of a 25.6 ms step for bytes that
cost 0.3).

- **In place.** The rows' leaf ``[La, B, 1, S, W]`` and the scales' ``[La, B,
  2, S]`` pass whole and stay in HBM; the layer is a prefetched scalar, as
  ``delta.py`` reads a layer of its state and ``paged.py`` one of its pool: a
  ``pallas_call`` cannot fuse the layer scan's slice of a carried leaf, and a
  slice in front of it would be the copy again. The leaf is only read.
- **One grid step a slot, ONE walk over the batch's live blocks.** A block is
  ``_block_rows`` positions of one slot; slot ``b`` has ``q_pos[b] // block +
  1`` of them, none where it is not ``live`` (it then reads nothing and
  returns zeros). The kernel copies block after block into VMEM itself
  (``make_async_copy``), ``depth - 1`` blocks ahead of the one it scores, and
  the walk does not drain at a slot's end: the next block in flight is the
  next live slot's first, as ``paged_v3`` walks pages since PR 48. Which slot
  follows which is a prefetched table (``nxt``), so an empty slot costs the
  walk nothing. A decode program's attended bucket is nothing to the walk (a
  block may end past it: what lies there is the slot's own and not visible);
  it is the keep mask's width alone.
- **All heads are the rows of one dot.** There is one row class (KvH = 1):
  scores ``[H, C] x [block, C]`` and ``[H, W - C] x [block, W - C]`` (the
  rotated query zero-padded over the row's padding), float32; a running
  maximum and sum in float32; values ``(e * s_lat).astype(q) @ latent``
  accumulated float32 and normalised once at the end. int8 codes are exact in
  bfloat16, and the two scales of a position multiply score and probability
  rows, lane-wise.
- **The indexer's selection rides in** as a float32 row a slot (``keep`` [B,
  A], nonzero = may be read; it already holds visibility); without it a
  position is visible up to the query's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF
from .paged import _walk_depth

# positions a block at most: what a visit moves and scores at once. A visit
# costs ~0.5 us whatever it holds and ~0.52 us a 512 positions, so larger
# blocks pay the fixed part less often and waste half a block a slot past its
# last position: at the cell's contexts (mean ~900) the kernel alone reads, ms
# a step over seven layers, 2.19 / 1.46 / 1.11 / 1.05 at 128 / 256 / 512 /
# 1,024 a block for the mix and 2.18 / 1.39 / 0.95 / 0.77 with every slot at
# 900 (hack/latent_microbench.py on the chip: PERF.md, PR 51)
_BLOCK_ROWS = 1024


def _block_rows(S: int, block: int, interpret: bool) -> int:
    """The largest divisor of a slot's ``S`` positions that is at most
    ``block``, in whole 128-lane tiles on the chip (a block's scales are a
    float32 row); 0 where there is none."""
    step = 1 if interpret else 128
    for bs in range(min(block, S) // step * step, 0, -step):
        if S % bs == 0:
            return bs
    return 0


def latent_decode_tileable(H: int, C: int, W: int, S: int,
                           interpret: bool) -> bool:
    """Whether the kernel takes H heads over slots of S rows of W channels,
    the first C of them the latent. The one statement of its shapes, as
    ``paged.paged_decode_tileable`` is of its kernel's: on the chip the
    latent and the row are whole 128-lane tiles (the row is sliced at C), the
    heads whole sublane tiles of the query's type, and a slot divides into
    blocks of whole tiles."""
    if W <= C or _block_rows(S, _BLOCK_ROWS, interpret) == 0:
        return False
    return interpret or (C % 128 == 0 and W % 128 == 0 and H % 16 == 0)


def _kernel(lay_ref, nblk_ref, nxt_ref, pos_ref, qa_ref, qr_ref, *rest,
            C: int, bs: int, depth: int, scale: float, quant: bool,
            masked: bool):
    """Refs in order: prefetched scalars (layer [1], live blocks a slot [B],
    ``nxt`` [B + 1]: the first slot with a block at or after j, B where none,
    the query's position [B]); q_abs [H, C] and the padded q_rope [H, W - C]
    VMEM blocks of the slot; with ``masked`` the slot's keep rows [blocks,
    bs] float32; the rows' leaf and with ``quant`` the scales' leaf, in HBM;
    the output block [H, C]; scratch that outlives a grid step: row buffers
    [depth, bs, W], (scale buffers [depth, 2, bs],) acc [H, C], m and l [H,
    1] float32, the copies' semaphores, and the walk in SMEM: slot, block and
    that slot's block count of the next copy to start, and the number of
    blocks scored so far, whose remainder by ``depth`` is the buffer in
    turn."""
    keep_ref = None
    if masked:
        keep_ref, *rest = rest
    rows_hbm, *rest = rest
    sc_hbm = sbuf = None
    if quant:
        sc_hbm, o_ref, buf, sbuf, acc_ref, m_ref, l_ref, sem, walk = rest
    else:
        o_ref, buf, acc_ref, m_ref, l_ref, sem, walk = rest
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    lay = lay_ref[0]
    cdt = qa_ref.dtype

    def copies(slot, blk, at):
        start = pl.multiple_of(blk * bs, bs)
        yield pltpu.make_async_copy(
            rows_hbm.at[lay, slot, 0, pl.ds(start, bs)], buf.at[at],
            sem.at[0, at])
        if quant:
            yield pltpu.make_async_copy(
                sc_hbm.at[lay, slot, :, pl.ds(start, bs)], sbuf.at[at],
                sem.at[1, at])

    def fetch(fb, fi, fend, at):
        """Start the copies of slot ``fb``'s block ``fi`` (nothing once the
        batch's last block is on its way) and step to the pair after it: a
        slot's last block is followed by the next live slot's first."""
        @pl.when(fb < nslots)
        def _start():
            for c in copies(fb, fi, at):
                c.start()

        nb = nxt_ref[jnp.minimum(fb + 1, nslots)]
        last = fi + 1 >= fend
        return (jnp.where(last, nb, fb), jnp.where(last, 0, fi + 1),
                jnp.where(last, nblk_ref[jnp.minimum(nb, nslots - 1)], fend))

    @pl.when(b == 0)
    def _prime():
        # depth - 1 blocks in flight before the first wait, once a call
        first = nxt_ref[0]
        state = (first, jnp.int32(0),
                 nblk_ref[jnp.minimum(first, nslots - 1)])
        for j in range(depth - 1):
            state = fetch(*state, j)
        walk[0], walk[1], walk[2], walk[3] = *state, jnp.int32(0)

    qp = pos_ref[b]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    qa, qr = qa_ref[...], qr_ref[...]
    nt = (((1,), (1,)), ((), ()))               # [H, c] x [bs, c] -> [H, bs]

    def body(i, carry):
        *ahead, n = carry
        ahead = fetch(*ahead, jax.lax.rem(n + depth - 1, depth))
        at = jax.lax.rem(n, depth)
        for c in copies(0, 0, at):              # a wait reads shape and sem
            c.wait()
        rows = buf[at]                          # [bs, W]
        lat = rows[:, :C].astype(cdt)
        s = jax.lax.dot_general(qa, lat, nt,
                                preferred_element_type=jnp.float32)
        s_rot = jax.lax.dot_general(qr, rows[:, C:].astype(cdt), nt,
                                    preferred_element_type=jnp.float32)
        if quant:
            sc = sbuf[at]                       # [2, bs]: latent, rotated key
            s_lat_scale = sc[0:1, :]
            s = (s * s_lat_scale + s_rot * sc[1:2, :]) * scale
        else:
            s = (s + s_rot) * scale
        if masked:
            ok = keep_ref[pl.ds(i, 1), :] > 0.0
        else:
            ok = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1) <= qp
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a block none of whose positions may be read leaves m at NEG_INF
        p = jnp.where(m_cur > NEG_INF / 2, jnp.exp(s - m_cur), 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quant:
            p = p * s_lat_scale
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(cdt), lat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur
        return (*ahead, n + 1)

    walk[0], walk[1], walk[2], walk[3] = jax.lax.fori_loop(
        0, nblk_ref[b], body, (walk[0], walk[1], walk[2], walk[3]))
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)


def latent_decode(rows, row, q_abs, q_rope, q_pos, live, keep, scale: float,
                  *, block: int = 0, interpret: bool = False):
    """One position of absorbed latent attention against layer ``row``.

    rows    the cache's leaf [La, B, 1, S, W], W = C + dr + padding, or int8
            {"q": that, "s": [La, B, 2, S] float32: a position's scales for
            the latent part and the rotated key}. Read, never written.
    row     int32 scalar, traced or not.
    q_abs   [B, H, C], the queries through ``w_uk``; q_rope [B, H, dr], or
            as many channels of the row past C as the query scores (the
            rotated key's second code, ``quantize_latent``: the caller's
            query then holds its share of it).
    q_pos   [B] int32, each query's position: slot b reads positions [0,
            q_pos[b]], its own row already written.
    live    [B]: a slot with 0 reads nothing and returns zeros.
    keep    None, or [B, A] (bool or number) over the first A <= S
            positions: those a slot may read, visibility included
            (``decoder._index_mask``); none past A.
    block   positions a block at most (0: ``_BLOCK_ROWS``).
    Returns o_lat [B, H, C] (q_abs.dtype), or None where the shapes do not
    tile (:func:`latent_decode_tileable`)."""
    quant = isinstance(rows, dict)
    codes = rows["q"] if quant else rows
    B, H, C = q_abs.shape
    S, W = codes.shape[-2:]
    if not latent_decode_tileable(H, C, W, S, interpret):
        return None
    bs = _block_rows(S, block or _BLOCK_ROWS, interpret)
    depth = _walk_depth(bs * (W * codes.dtype.itemsize + (8 if quant else 0)))
    i32 = jnp.int32
    q_pos = jnp.clip(q_pos.astype(i32), 0,
                     (S if keep is None else keep.shape[1]) - 1)
    n_live = jnp.where(jnp.reshape(live, (B,)) > 0,
                       jax.lax.div(q_pos, i32(bs)) + 1, 0).astype(i32)
    slots = jnp.arange(B + 1, dtype=i32)
    nxt = jax.lax.cummin(jnp.where(jnp.append(n_live, 1) > 0, slots, B),
                         reverse=True)
    q_rope = jnp.pad(q_rope.astype(q_abs.dtype),
                     ((0, 0), (0, 0), (0, W - C - q_rope.shape[-1])))

    def slot_block(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda b, *_: (b,) + (0,) * len(shape))

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    in_specs = [slot_block(H, C), slot_block(H, W - C)]
    args = [q_abs, q_rope]
    if keep is not None:
        nblk = -(-keep.shape[1] // bs)
        keep = jnp.pad(keep.astype(jnp.float32),
                       ((0, 0), (0, nblk * bs - keep.shape[1])))
        in_specs.append(slot_block(nblk, bs))
        args.append(keep.reshape(B, nblk, bs))
    in_specs.append(hbm)
    args.append(codes)
    scratch = [pltpu.VMEM((depth, bs, W), codes.dtype)]
    if quant:
        in_specs.append(hbm)
        args.append(rows["s"])
        scratch.append(pltpu.VMEM((depth, 2, bs), jnp.float32))
    scratch += [pltpu.VMEM((H, C), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.SemaphoreType.DMA((2, depth)),
                pltpu.SMEM((4,), i32)]
    return pl.pallas_call(
        functools.partial(_kernel, C=C, bs=bs, depth=depth, scale=scale,
                          quant=quant, masked=keep is not None),
        name="latent_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B,), in_specs=in_specs,
            out_specs=slot_block(H, C), scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, H, C), q_abs.dtype),
        # sequential: copies, semaphores and the walk's state cross steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(row, (1,)).astype(i32), n_live, nxt, q_pos, *args)
