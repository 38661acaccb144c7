"""Window attention's one-position step over each slot's own live ring rows.

``ring_decode`` is the T == 1 form of ``models/decoder._ring_attend`` for a
long ring: for every slot it reads the layer's keys and values where they lie
in the rings ``[Lw, B, KvH, W, hd]`` and returns softmax(q . k) . v over the
ring slots that hold a visible position. The einsum form slices the first
``min(bucket, W)`` slots of EVERY slot's ring, and the decode programs are
bucketed by the LONGEST live context: SmallThinker's six window layers read
4,096 deep for slots that hold ~900 positions (ledger, PR 51: 3.16 ms of a
14.8 ms step for bytes that cost 0.40).

- **In place.** The four ring leaves (K and V codes, their scales
  ``[Lw, B, KvH, W]``; two plain leaves where the cache is not quantized) pass
  whole and stay in HBM; the layer is a prefetched scalar, as ``latent.py``
  reads a layer of its rows: a ``pallas_call`` cannot fuse the layer scan's
  slice of a carried leaf, and a slice in front of it would be the copy
  again. The rings are only read: the new position's row is written before
  the call (``decoder._ring_put``). The scales go in heads ahead of slots,
  ``[Lw, KvH, B, W]``, the order the compiler keeps that leaf in through a
  chunk for the row write's scatter: a transpose that moves nothing, where
  the declared order would be re-laid whole in front of every layer's call.
- **What a slot reads.** Keys are stored rotated and the new position is
  already in its slot ``lengths % W``, so the kernel needs no positions: ring
  slot j holds position j until the ring wraps (visible iff j <= lengths) and
  a position inside the window ever after. Slot ``b`` reads its first
  ``min(lengths[b] + 1, W)`` ring slots, none where ``n_valid[b]`` is 0 (it
  then returns zeros). The caller's attended bucket is nothing to the walk.
- **One grid step a slot, ONE walk over the batch's live blocks**, as
  ``latent.py`` walks rows and ``paged.py`` pages: a block is ``bs`` ring
  slots of one slot's ring across ALL its kv heads (four copies: K, V, their
  scales; a copy from HBM takes whole sublane tiles, so the scales of the
  ``_SCALE_ROWS`` slots that share a tile come together and the slot's own
  row is picked in VMEM), ``depth - 1`` blocks in flight ahead of the one
  scored, and the walk does not drain at a slot's end: the next block in
  flight is the next live slot's first (``nxt``, a prefetched table).
- **The arithmetic is ``quant_cache.attend_hf_q``'s**, by the paged kernel's
  own update (``paged._flash_page_update``): the G = H / KvH query heads of a
  kv head, padded to a sublane tile, are the rows of a dot batched over KvH;
  float32 scores times the key's scale a position, a running maximum and sum
  in float32, ``(p * value scale).astype(q) @ codes`` accumulated float32 and
  normalised once. int8 codes are exact in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF
from .latent import _block_rows
from .paged import _flash_page_update, _walk_depth

# ring slots a block at most: what a visit moves and scores at once, over all
# kv heads (SmallThinker: 1,056 bytes a ring slot over four heads, 1,280 with
# the scales' tile neighbours). The walk runs at the copies' rate (0.75-0.87 us
# a visit of 655 KB at 512), so a larger block only wastes more of a slot's
# last one: six layers, 64 slots, ms a step at 256 / 512 / 1,024 a block: the
# served mix (4 of 64 past 2,048, mean ~900) 0.905 / 0.734 / 0.791, every slot
# at 900 0.914 / 0.668 / 0.591, at 3,600 3.10 / 2.31 / 2.19
# (hack/ring_microbench.py on the chip: PERF.md section 6, PR 52)
_BLOCK_ROWS = 512
_SCALE_ROWS = 8     # slots whose float32 scales share a tile's sublanes


def ring_decode_tileable(B: int, H: int, KvH: int, hd: int, W: int,
                         interpret: bool) -> bool:
    """Whether the kernel takes B slots' H query heads over rings of W slots
    of KvH heads of hd channels. The one statement of its shapes, as
    ``latent.latent_decode_tileable`` is of its kernel's: whole query groups
    a kv head; on the chip a head is whole 128-lane tiles, a ring divides
    into blocks of whole tiles (a block's scales are float32 rows) and the
    slots into whole sublane tiles (``_SCALE_ROWS``: the scales arrive a
    tile's slots at a time)."""
    if KvH <= 0 or H % KvH or _block_rows(W, _BLOCK_ROWS, interpret) == 0:
        return False
    return interpret or (hd % 128 == 0 and B % _SCALE_ROWS == 0)


def _kernel(lay_ref, nblk_ref, nxt_ref, last_ref, q_ref, k_hbm, v_hbm, *rest,
            bs: int, depth: int, scale: float, softcap: float, quant: bool,
            kvh: int, gp: int, sg: int, cdt):
    """Refs in order: prefetched scalars (layer [1], live blocks a slot [B],
    ``nxt`` [B + 1]: the first slot with a block at or after j, B where none,
    the last visible ring slot [B]); the slot's queries [1, KvH, Gp, hd] in
    VMEM; the K and V rings and with ``quant`` their scales, in HBM; the
    output block; scratch that outlives a grid step: K and V buffers [depth,
    KvH, bs, hd], (scale buffers [depth, KvH, sg, bs],) acc [KvH, Gp, hd], m
    and l [KvH, Gp, 1] float32, the copies' semaphores, and the walk in SMEM:
    slot, block and that slot's block count of the next copy to start, and
    the number of blocks scored so far, whose remainder by ``depth`` is the
    buffer in turn."""
    ks_hbm = vs_hbm = ksbuf = vsbuf = None
    if quant:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, acc_ref, m_ref,
         l_ref, sem, walk) = rest
    else:
        o_ref, kbuf, vbuf, acc_ref, m_ref, l_ref, sem, walk = rest
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    lay = lay_ref[0]

    def copies(slot, blk, at):
        start = pl.multiple_of(blk * bs, bs)
        for j, (src, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
            yield pltpu.make_async_copy(
                src.at[lay, slot, :, pl.ds(start, bs)], dst.at[at],
                sem.at[j, at])
        if quant:       # heads ahead of slots, a tile's ``sg`` slots at once
            group = pl.ds(pl.multiple_of(slot // sg * sg, sg), sg)
            for j, (src, dst) in enumerate(((ks_hbm, ksbuf),
                                            (vs_hbm, vsbuf)), 2):
                yield pltpu.make_async_copy(
                    src.at[lay, :, group, pl.ds(start, bs)], dst.at[at],
                    sem.at[j, at])

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

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    qv = q_ref[0]                                   # [KvH, Gp, hd]
    last_slot = last_ref[b]
    mine = pl.ds(jax.lax.rem(b, sg), 1)             # this slot's row of scales

    def body(i, carry):
        *ahead, n = carry
        ahead = fetch(*ahead, jax.lax.rem(n + depth - 1, depth))
        at = jax.lax.rem(n, depth)
        for c in copies(0, 0, at):              # a wait reads shape and sem
            c.wait()
        # a ring slot is visible iff it is at or under the slot's last one:
        # the update's own test of a key's position against the query's
        _flash_page_update(
            qv, kbuf[at], vbuf[at],
            ksbuf[at, :, mine, :] if quant else None,
            vsbuf[at, :, mine, :] if quant else None,
            m_ref, l_ref, acc_ref, k_start=i * bs, qp=last_slot, scale=scale,
            softcap=softcap, window=0, ps=bs, kvh=kvh, gp=gp, cdt=cdt)
        return (*ahead, n + 1)

    walk[0], walk[1], walk[2], walk[3] = jax.lax.fori_loop(
        0, nblk_ref[b], body, (walk[0], walk[1], walk[2], walk[3]))
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def ring_decode(k_ring, v_ring, row, q, lengths, n_valid, scale: float,
                softcap: float = 0.0, *, block: int = 0,
                interpret: bool = False):
    """One position of window attention against layer ``row`` of the rings.

    k_ring, v_ring  the rings' leaves [Lw, B, KvH, W, hd], or int8 {"q":
             that, "s": [Lw, B, KvH, W] float32, a scale a head a slot}.
             Read, never written: the new position is already in its slot.
    row      int32 scalar, traced or not.
    q        [B, H, hd], rotated as the keys are.
    lengths  [B] int32, each query's position: slot b reads ring slots [0,
             min(lengths[b] + 1, W)).
    n_valid  [B]: a slot with 0 reads nothing and returns zeros.
    block    ring slots a block at most (0: ``_BLOCK_ROWS``).
    Returns [B, H, hd] (q.dtype), or None where the shapes do not tile
    (:func:`ring_decode_tileable`)."""
    quant = isinstance(k_ring, dict)
    k_arr, v_arr = (k_ring["q"], v_ring["q"]) if quant else (k_ring, v_ring)
    B, H, hd = q.shape
    KvH, W = k_arr.shape[2:4]
    if k_arr.shape[4] != hd or not ring_decode_tileable(B, H, KvH, hd, W,
                                                        interpret):
        return None
    bs = _block_rows(W, block or _BLOCK_ROWS, interpret)
    sg = _SCALE_ROWS if B % _SCALE_ROWS == 0 else 1     # 1: interpreted only
    depth = _walk_depth(KvH * bs * (
        hd * (k_arr.dtype.itemsize + v_arr.dtype.itemsize)
        + (8 * sg if quant else 0)))
    i32 = jnp.int32
    n_rows = jnp.where(jnp.reshape(n_valid, (B,)) > 0,
                       jnp.clip(lengths.astype(i32) + 1, 1, W), 0)
    n_blk = jax.lax.div(n_rows + (bs - 1), i32(bs))
    slots = jnp.arange(B + 1, dtype=i32)
    nxt = jax.lax.cummin(jnp.where(jnp.append(n_blk, 1) > 0, slots, B),
                         reverse=True)
    G = H // KvH
    Gp = -(-G // 8) * 8                 # a kv head's queries, whole sublanes
    qg = q.reshape(B, KvH, G, hd)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    cdt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    slot_block = pl.BlockSpec((1, KvH, Gp, hd), lambda b, *_: (b, 0, 0, 0))
    in_specs = [slot_block, hbm, hbm]
    args = [qg, k_arr, v_arr]
    scratch = [pltpu.VMEM((depth, KvH, bs, hd), k_arr.dtype),
               pltpu.VMEM((depth, KvH, bs, hd), v_arr.dtype)]
    if quant:
        in_specs += [hbm, hbm]
        # [Lw, KvH, B, W]: the order the compiler lays the scales' leaf in
        # for the row write's scatter (slots the sublanes of a tile, heads
        # ahead of them), so this transpose moves nothing; the leaf as it is
        # declared would be re-laid whole in front of every layer's call
        # (2 x 25 MB there and back, PERF.md section 6, PR 52). A copy takes
        # whole tiles, so a block's scales come with its seven neighbours'
        args += [k_ring["s"].transpose(0, 2, 1, 3),
                 v_ring["s"].transpose(0, 2, 1, 3)]
        scratch += [pltpu.VMEM((depth, KvH, sg, bs), jnp.float32)] * 2
    scratch += [pltpu.VMEM((KvH, Gp, hd), jnp.float32),
                pltpu.VMEM((KvH, Gp, 1), jnp.float32),
                pltpu.VMEM((KvH, Gp, 1), jnp.float32),
                pltpu.SemaphoreType.DMA((4 if quant else 2, depth)),
                pltpu.SMEM((4,), i32)]
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, depth=depth, scale=scale,
                          softcap=softcap, quant=quant, kvh=KvH, gp=Gp,
                          sg=sg, cdt=cdt),
        name="ring_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B,), in_specs=in_specs,
            out_specs=slot_block, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, KvH, Gp, hd), q.dtype),
        # sequential: copies, semaphores and the walk's state cross steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(row, (1,)).astype(i32), n_blk, nxt, n_rows - 1, *args)
    return out[:, :, :G].reshape(B, H, hd)
