"""The paged pool's writer: one (slot, position) at a time, all KV heads.

``paged_kv_write`` puts a decode step's (or an extend tail's) new rows into
layer ``layer`` of page pools in place. One grid step per (row, position)
index n: the kernel reads the destination's native tile-row group of every
head — codes ``[KvH, G, hd]`` at rows ``off // G * G`` of page ``pg[n]``
(G = 32 int8 rows, 16 bfloat16, 8 float32: an aligned group, so no DMA
ever splits a packed tile) and the page's scale rows ``[KvH, sp]`` — into
VMEM, replaces row ``off % G`` (lane ``off`` of the scales), and writes the
group back. The XLA scatter it replaces addressed one index per (slot,
HEAD, position), 82-109 ns each and serial: 1,024 indices a tensor a layer
for phi-2's 32 MHA slots, half its decode step (PERF.md, PR 30).

Pools pass whole (``pl.ANY``) and aliased to the outputs, the layer as a
prefetched scalar, as ``paged_decode_attention`` takes them: a pallas_call
cannot fuse the layer scan's slice of a stacked pool (PR 25).

Reads run one index ahead of the writes (two buffers a stream). Index n+1
is NOT read ahead where it names the page index n writes — an extend
tail's consecutive positions, two vacant slots' rows bound for the trash
page — since the read would pass the write it must follow; it then waits
for that write. Indices two apart never overlap in flight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def tile_rows(dtype) -> int:
    """Rows (second-minor) of the native TPU tile of ``dtype``."""
    return 32 // jnp.dtype(dtype).itemsize


def _kernel(lay_ref, pg_ref, off_ref, *refs, n_idx: int, groups, wides):
    """refs: per stream its new-rows block, then per stream its pool (the
    aliased input, unused), then per stream the output pool, then per
    stream a [2, ...] VMEM buffer, then the DMA semaphores [2, S, 2]."""
    S = len(groups)
    new = refs[:S]
    pools = refs[2 * S:3 * S]
    bufs = refs[3 * S:4 * S]
    sem = refs[4 * S]
    n = pl.program_id(0)
    lay = lay_ref[0]
    slot = n % 2

    def window(s, i):
        """Stream s's destination of index i: the tile-row group of every
        head (codes), the page's row of every head (scales)."""
        pg = pg_ref[i]
        if groups[s] is None:
            return pools[s].at[lay, pg]
        r0 = pl.multiple_of(off_ref[i] // groups[s] * groups[s], groups[s])
        return pools[s].at[lay, pg, :, pl.ds(r0, groups[s]), :]

    def read(i, sl):
        return [pltpu.make_async_copy(window(s, i), bufs[s].at[sl],
                                      sem.at[0, s, sl]) for s in range(S)]

    def write(i, sl):
        return [pltpu.make_async_copy(bufs[s].at[sl], window(s, i),
                                      sem.at[1, s, sl]) for s in range(S)]

    nxt = jnp.minimum(n + 1, n_idx - 1)
    prv = jnp.maximum(n - 1, 0)
    ahead = jnp.logical_and(n > 0, pg_ref[n] != pg_ref[prv])  # read by n-1?

    @pl.when(n > 0)
    def _():
        for c in write(prv, 1 - slot):
            c.wait()

    @pl.when(jnp.logical_not(ahead))
    def _():
        for c in read(n, slot):
            c.start()

    @pl.when(jnp.logical_and(n + 1 < n_idx, pg_ref[nxt] != pg_ref[n]))
    def _():
        for c in read(nxt, 1 - slot):
            c.start()

    for c in read(n, slot):
        c.wait()
    off = off_ref[n]
    for s in range(S):
        old = bufs[s][slot]
        if groups[s] is None:            # scales [KvH, 1, sp]: lane ``off``
            hit = jax.lax.broadcasted_iota(jnp.int32, old.shape, 2) == off
        else:                            # codes [KvH, G, hd]: row off % G
            hit = (jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
                   == off % groups[s])
        # select in 32 bits: a mask of a packed type is a layout of its own
        out = jnp.where(hit, new[s][0].astype(wides[s]), old.astype(wides[s]))
        bufs[s][slot] = out.astype(old.dtype)
    for c in write(n, slot):
        c.start()

    @pl.when(n == n_idx - 1)
    def _():
        for c in write(n, slot):
            c.wait()


def paged_kv_write(pools, layer, pg, off, rows, *, interpret: bool = False):
    """Write ``rows`` into layer ``layer`` of ``pools`` at (page, offset).

    pools  tuple of page pools: code pools ``[L, P, KvH, ps, hd]`` (int8,
           bfloat16, float32) and scale pools ``[L, P, KvH, sp]`` float32,
           in any mix; returned in the same order, updated in place.
    pg, off  [B, T] int32: the physical page and the offset in it of each
           (row, position); every page must lie in the pool (callers send
           out-of-table blocks to the trash page).
    rows   per pool the new values ``[B, T, KvH, hd]`` (codes, already of
           the pool's dtype and width) or ``[B, T, KvH]`` (scales).
    Returns None where the chip's tiling cannot hold the pools' page size
    (the caller then takes the XLA form and says so).
    """
    N = pg.size
    groups, wides, args, in_specs, scratch = [], [], [], [], []
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    for pool, r in zip(pools, rows):
        L, P, KvH = pool.shape[:3]
        wide = (jnp.float32 if jnp.issubdtype(pool.dtype, jnp.floating)
                else jnp.int32)
        if pool.ndim == 5:
            ps, hd = pool.shape[3:]
            G = min(tile_rows(pool.dtype), ps)
            if not interpret and (ps % tile_rows(pool.dtype) or hd % 128):
                return None
            block = (1, KvH, 1, hd)
            r = r.reshape(N, KvH, 1, hd)
            scratch.append(pltpu.VMEM((2, KvH, G, hd), pool.dtype))
        else:
            # a unit axis before the lanes: a [KvH, sp] buffer of few heads
            # is a tile of its own kind, and the DMA would have to know it
            sp = pool.shape[3]
            G = None
            if not interpret and sp % 128:
                return None
            block = (1, KvH, 1, sp)
            r = jnp.broadcast_to(r.reshape(N, KvH, 1, 1), (N, KvH, 1, sp))
            scratch.append(pltpu.VMEM((2, KvH, 1, sp), pool.dtype))
        groups.append(G)
        wides.append(wide)
        args.append(r.astype(wide))
        in_specs.append(pl.BlockSpec(block, lambda n, *_: (n, 0, 0, 0)))
    S = len(pools)
    flat = [p if p.ndim == 5 else p.reshape(*p.shape[:3], 1, p.shape[3])
            for p in pools]
    scratch.append(pltpu.SemaphoreType.DMA((2, S, 2)))
    out = pl.pallas_call(
        functools.partial(_kernel, n_idx=N, groups=tuple(groups),
                          wides=tuple(wides)),
        name="paged_kv_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N,),
            in_specs=in_specs + [any_] * S, out_specs=[any_] * S,
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in flat],
        input_output_aliases={3 + S + s: s for s in range(S)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      pg.reshape(N).astype(jnp.int32), off.reshape(N).astype(jnp.int32),
      *args, *flat)
    return tuple(o.reshape(p.shape) for o, p in zip(out, pools))
