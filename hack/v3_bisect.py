"""Bisect which construct in the v3 paged kernel the TPU's Mosaic lowering
refuses (interpret mode passes all of them). Each probe isolates one
suspect — the constructs that once crashed the chip's lowering of the v3
kernel:

  p1  batched dot_general (batch dim = KvH) on VMEM values
  p2  dynamic leading-index read of a VMEM scratch buffer (buf[slot])
  p3  make_async_copy HBM.at[lay, pg] -> VMEM scratch, traced indices
  p4  fori_loop with traced (SMEM-scalar) bounds containing pl.when+DMA
  p5  3-D broadcasted_iota + 3-D flash-style elementwise chain

The probes compile for a v5e that is described, not attached (the TPU's
compiler ships with jaxlib), so this runs anywhere:

    JAX_PLATFORMS=cpu python hack/v3_bisect.py

The kernels themselves, at tinyllama's real shapes, are held by
tests/test_chip_compile.py.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental import topologies
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding


def check(name, fn, *shapes):
    try:
        jax.jit(fn).lower(*shapes).compile()
        print(f"OK   {name}", flush=True)
        return True
    except Exception as e:
        msg = str(e).split("\n")[0][:160]
        print(f"FAIL {name}: {type(e).__name__}: {msg}", flush=True)
        return False


def main():
    KvH, Gp, ps, hd = 4, 8, 64, 128
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def arg(shape, dtype):
        """A described-device argument: shapes compile, nothing runs."""
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    ok = True

    # p1: batched dot_general
    def k1(q_ref, k_ref, o_ref):
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        o_ref[...] = s

    def p1(q, k):
        return pl.pallas_call(
            k1,
            out_shape=jax.ShapeDtypeStruct((KvH, Gp, ps), jnp.float32),
        )(q, k)

    ok &= check("p1 batched dot_general", p1,
                arg((KvH, Gp, hd), jnp.bfloat16),
                arg((KvH, ps, hd), jnp.bfloat16))

    # p2: dynamic leading-index scratch read
    def k2(i_ref, x_ref, o_ref, buf):
        buf[...] = jnp.stack([x_ref[...], x_ref[...] * 2])
        o_ref[...] = buf[i_ref[0] % 2]

    def p2(i, x):
        return pl.pallas_call(
            k2,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec((ps, hd), lambda g, i: (0, 0))],
                out_specs=pl.BlockSpec((ps, hd), lambda g, i: (0, 0)),
                scratch_shapes=[pltpu.VMEM((2, ps, hd), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((ps, hd), jnp.float32),
        )(i, x)

    ok &= check("p2 dynamic scratch read", p2, arg((1,), jnp.int32),
                arg((ps, hd), jnp.float32))

    # p3: manual DMA from HBM with traced indices
    def k3(lay_ref, tbl_ref, hbm_ref, o_ref, buf, sem):
        pg = tbl_ref[0]
        cp = pltpu.make_async_copy(hbm_ref.at[lay_ref[0], pg],
                                   buf.at[0], sem.at[0])
        cp.start()
        cp.wait()
        o_ref[...] = buf[0].astype(jnp.float32)

    def p3(lay, tbl, pool):
        return pl.pallas_call(
            k3,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
                out_specs=pl.BlockSpec((KvH, ps, hd),
                                       lambda g, *p: (0, 0, 0)),
                scratch_shapes=[pltpu.VMEM((2, KvH, ps, hd), jnp.int8),
                                pltpu.SemaphoreType.DMA((2,))]),
            out_shape=jax.ShapeDtypeStruct((KvH, ps, hd), jnp.float32),
        )(lay, tbl, pool)

    pool = arg((2, 5, KvH, ps, hd), jnp.int8)
    ok &= check("p3 manual HBM DMA", p3, arg((1,), jnp.int32),
                arg((4,), jnp.int32), pool)

    # p4: dynamic fori_loop with pl.when + DMA inside
    def k4(len_ref, tbl_ref, hbm_ref, o_ref, buf, sem):
        n = len_ref[0] // ps + 1

        def dma(i, slot):
            return pltpu.make_async_copy(hbm_ref.at[0, tbl_ref[i]],
                                         buf.at[slot], sem.at[slot])
        dma(0, 0).start()
        acc0 = jnp.zeros((ps, hd), jnp.float32)

        def body(i, acc):
            slot = i % 2

            @pl.when(i + 1 < n)
            def _():
                dma(i + 1, (i + 1) % 2).start()
            dma(i, slot).wait()
            return acc + buf[slot][0].astype(jnp.float32)

        acc = jax.lax.fori_loop(0, n, body, acc0)
        o_ref[...] = acc

    def p4(ln, tbl, pool):
        return pl.pallas_call(
            k4,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
                out_specs=pl.BlockSpec((ps, hd), lambda g, *p: (0, 0)),
                scratch_shapes=[pltpu.VMEM((2, KvH, ps, hd), jnp.int8),
                                pltpu.SemaphoreType.DMA((2,))]),
            out_shape=jax.ShapeDtypeStruct((ps, hd), jnp.float32),
        )(ln, tbl, pool)

    ok &= check("p4 dynamic loop + DMA", p4, arg((1,), jnp.int32),
                arg((4,), jnp.int32), pool)

    # p5: 3-D iota + flash chain
    def k5(s_ref, o_ref, m_ref, l_ref):
        s = s_ref[...]
        pos = jax.lax.broadcasted_iota(jnp.int32, (KvH, Gp, ps), 2)
        s = jnp.where(pos <= 40, s, -1e30)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_cur) + jnp.sum(
            p, axis=-1, keepdims=True)
        m_ref[...] = m_cur
        o_ref[...] = p

    def p5(s):
        return pl.pallas_call(
            k5,
            out_shape=jax.ShapeDtypeStruct((KvH, Gp, ps), jnp.float32),
            scratch_shapes=[pltpu.VMEM((KvH, Gp, 1), jnp.float32),
                            pltpu.VMEM((KvH, Gp, 1), jnp.float32)],
        )(s)

    ok &= check("p5 3-D iota+flash", p5, arg((KvH, Gp, ps), jnp.float32))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
