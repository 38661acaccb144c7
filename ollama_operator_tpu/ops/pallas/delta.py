"""The gated delta rule's one-position step, one pass over the state.

``delta_update`` advances layer ``row`` of a delta stack's state leaf
``[Ld, B, H, dk, dv]`` float32 by one position, in place. For a block of
heads of one slot the kernel brings each head's matrix ``S0`` ``[dk, dv]``
into VMEM once and does the recurrence as written, on the vector unit, in
float32 (a leaf that lays P heads side by side along lanes, ``[Ld, B, H / P,
dk, P dv]``, is taken a group of P at a time: each head's scalars and
columns are spread over its own lanes, the arithmetic is the same)::

    S' = exp(g) S0;  r = S'^T k;  u = beta (v - r);
    S1 = S' + k u^T;  o = S1^T q

then writes ``S1`` back to where ``S0`` lay and ``o`` out: the state is read
once and written once. The compiler's form of the same six steps is four
passes (``S'`` and ``S'^T k``; the correction and the write; ``S1^T q``): it
reads the state three times and writes it once (models/decoder.py
``_delta_rule``; PERF.md, PRs 44-45).

The leaf passes whole and aliased to the output, the layer as a prefetched
scalar in the index maps, as ``ops/pallas/quant.py`` reads a layer of
stacked weights and ``kv_write.py`` writes one of a page pool: a pallas_call
cannot fuse the layer scan's slice of the carried leaf, and a slice would be
a copy of 100 MB a layer.

``k`` and ``q`` are wanted along sublanes (a column ``[dk, 1]`` a head, to
broadcast over the matrix's lanes), so the caller's rows are turned outside
the kernel: one array ``[B, blocks, dk, 2 hb]``, a block's keys then its
queries, a head a lane. ``exp(g)`` and ``beta`` are a scalar a head, read
from SMEM. A slot with nothing real (``live`` 0) has its matrices copied
through, so it keeps its very bits (``1 * S0 + k * 0`` would turn a -0.0
into +0.0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what a block's state may take of VMEM, in and out, each double-buffered: a
# quarter of the 16 MiB a kernel is given by default on a v5e. The DMAs set
# the pace (the published heads time alike from 5 to 30 a block: my chip run,
# PR 45), and the body is unrolled a head and traced anew for each decode
# program of a warm plan, so the smaller block is the cheaper set-up (ten of
# the published heads: 0.10 s a program to trace and lower, thirty 0.24)
_VMEM_BUDGET = 4 << 20


def _padded_bytes(dk: int, dv: int) -> int:
    """Bytes of one head's float32 matrix in (8, 128) tiles."""
    return (-(-dk // 8) * 8) * (-(-dv // 128) * 128) * 4


def heads_per_block(H: int, dk: int, dv: int) -> int:
    """The most heads (a divisor of H) whose matrices fit the budget in and
    out, double-buffered; 0 where one head does not."""
    for hb in range(H, 0, -1):
        if H % hb == 0 and 4 * hb * _padded_bytes(dk, dv) <= _VMEM_BUDGET:
            return hb
    return 0


def delta_tileable(H: int, dk: int, dv: int, interpret: bool = False) -> bool:
    """Whether the kernel takes H matrices of ``[dk, dv]`` a slot (heads, or
    groups of heads side by side) on the chip: whole float32 sublane tiles
    along dk (a column of ``k`` is sliced and broadcast tile by tile), and
    one matrix within the budget."""
    if interpret:
        return True
    return dk % 8 == 0 and heads_per_block(H, dk, dv) > 0


def _kernel(row_ref, live_ref, a_ref, beta_ref, kq_ref, v_ref, s_ref,
            o_ref, out_ref, *, H: int, gb: int, P: int):
    del row_ref                                   # the index maps' alone
    b, blk = pl.program_id(0), pl.program_id(1)
    live = live_ref[b] > 0
    hb, W = gb * P, s_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    def spread(xs):
        """One value a head of a group -> over each head's own lanes."""
        out = xs[-1]
        for p in range(P - 2, -1, -1):
            out = jnp.where(lane < (p + 1) * (W // P), xs[p], out)
        return out

    @pl.when(live)
    def _():
        at = b * H + blk * hb                     # the block's first head
        for i in range(gb):
            heads = range(i * P, (i + 1) * P)
            k = spread([kq_ref[:, h:h + 1] for h in heads])   # [dk, 1 | W]
            q = spread([kq_ref[:, hb + h:hb + h + 1] for h in heads])
            Sp = spread([a_ref[at + h] for h in heads]) * s_ref[i]
            r = jnp.sum(Sp * k, axis=0, keepdims=True)        # [1, W]
            u = spread([beta_ref[at + h] for h in heads]) \
                * (v_ref[i:i + 1, :] - r)
            S1 = Sp + k * u
            out_ref[i] = S1
            o_ref[i:i + 1, :] = jnp.sum(S1 * q, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def delta_update(ssm, row, q, k, v, a, beta, live, *, hb: int = 0,
                 interpret: bool = False):
    """One position of the gated delta rule on row ``row`` of ``ssm``.

    ssm   [Ld, B, G, dk, W] float32, the carried leaf: G = H groups of W =
          dv lanes, or P heads side by side a group (G = H / P, W = P dv:
          head P g + p in lanes [p dv, (p + 1) dv) of group g). Returned
          updated at ``[row]`` alone, in place (aliased).
    row   int32 scalar, traced or not.
    q, k  [B, H, dk] float32, normalised as the rule wants them.
    v     [B, H, dv] float32.
    a, beta  [B, H] float32: the decay exp(g) and the step size.
    live  [B]: a slot with 0 keeps its state's bits and reads out zeros.
    hb    heads a block (a divisor of H, a multiple of P; 0: the most the
          budget holds).
    Returns (o [B, H, dv], ssm), or None where the chip's tiling cannot
    hold the heads (the caller then takes the compiler's form and says so).
    """
    _, B, G, dk, W = ssm.shape
    H, dv = v.shape[1:]
    P = H // G
    if not delta_tileable(G, dk, W, interpret):
        return None
    gb = hb // P or heads_per_block(G, dk, W) or G
    nb, hb = G // gb, gb * P
    f32 = jnp.float32

    def cols(x):                    # [B, H, dk] -> [B, nb, dk, hb]
        return x.astype(f32).reshape(B, nb, hb, dk).transpose(0, 1, 3, 2)

    kq = jnp.concatenate([cols(k), cols(q)], axis=-1)
    state = pl.BlockSpec((None, None, gb, dk, W),
                         lambda b, j, row, *_: (row[0], b, j, 0, 0))
    rows = pl.BlockSpec((None, None, gb, W), lambda b, j, *_: (b, j, 0, 0))
    o, ssm = pl.pallas_call(
        functools.partial(_kernel, H=H, gb=gb, P=P),
        name="delta_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, nb),
            in_specs=[pl.BlockSpec((None, None, dk, 2 * hb),
                                   lambda b, j, *_: (b, j, 0, 0)),
                      rows, state],
            out_specs=[rows, state]),
        out_shape=[jax.ShapeDtypeStruct((B, nb, gb, W), f32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.reshape(row, (1,)).astype(jnp.int32),
      jnp.reshape(live, (B,)).astype(jnp.int32),
      a.astype(f32).reshape(B * H), beta.astype(f32).reshape(B * H),
      kq, v.astype(f32).reshape(B, nb, gb, W), ssm)
    return o.reshape(B, H, dv), ssm
