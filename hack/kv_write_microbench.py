"""The paged pool's writer: its forms by index count, on the chip.

For the int8 pools of the benchmark's two dense cells (phi-2: MHA 32/32,
32 slots, 64-token pages; starcoder2-3b: GQA 24/2, 64 slots, 128-token
pages) and a GQA 32/8 pool between them: the time of one decode step's
write (T = 1: one new K row, one new V row and their two scales per slot
per layer) through

  per_head   pool.at[i, pg, arange(KvH), off]: one scatter index per
             (slot, head); the form every PR before 30 served
  per_head_u the same, telling XLA the indices are unique (rows bound for
             the trash page carry an out-of-range page and drop)
  windowed   pool.at[i, pg, :, off]: one index per slot, the window spans
             the slot's heads
  windowed_u the same with unique indices, as per_head_u
  kernel     ops/pallas/kv_write.paged_kv_write: one pallas_call a layer,
             the four pools whole and aliased, one (slot, position) a grid
             step: the slot's tile-row group read, one row replaced, written

as the decoder runs it: inside a ``lax.scan`` over the L layers with the
four pools as the donated carry, ``--steps`` steps a call, each at the next
offset of every slot's current page. A one-tile pallas reader of the four
pools sits in every layer beside the write (``none`` is that reader alone):
the served step's attention kernel pins the pools' layout the same way, and
a form that would rather have another layout pays for it here as it would
there. Reported per form: milliseconds a step (all layers, four tensors),
nanoseconds an index of the per-head count, the compiled program's
temporaries, and whether the pools' bytes equal ``per_head``'s.

Usage (the chip): python hack/kv_write_microbench.py [--quick]
Here (compiles every form for a described v5e, runs nothing):
    JAX_PLATFORMS=cpu python hack/kv_write_microbench.py --compile-only
Writes chiprun_out/kv_write_microbench.json and prints a table.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# name: L, pages, KvH, page size, slots (the cells' resolved engines; the
# GQA 32/8 pool is Mistral-7B's heads at phi-2's page geometry)
SHAPES = {
    "phi-2": (32, 160, 32, 64, 32),
    "starcoder2-3b": (30, 768, 2, 128, 64),
    "gqa-32-8": (32, 320, 8, 64, 32),
}
HD = 128                      # the pool's lane-padded head_dim
SP = 128                      # the scale pool's lane-padded page size


def forms():
    import jax.numpy as jnp

    def per_head(pool, i, vals, pg, off, unique=False):
        vals = jnp.moveaxis(vals, 2, 1)                 # [B, KvH, T(, hd)]
        hx = jnp.arange(vals.shape[1])[None, :, None]
        at = pool.at[i, pg[:, None, :], hx, off[:, None, :]]
        return (at.set(vals, mode="drop", unique_indices=True) if unique
                else at.set(vals))

    def windowed(pool, i, vals, pg, off, unique=False):
        at = pool.at[i, pg, :, off]
        return (at.set(vals, mode="drop", unique_indices=True) if unique
                else at.set(vals))

    def xla(one, unique):
        def write(pools, i, new, pg, off):
            if unique:      # the trash page's rows drop instead of colliding
                pg = jnp.where(pg == 0, pools[0].shape[1], pg)
            return tuple(one(p, i, v, pg, off, unique)
                         for p, v in zip(pools, new))
        return write

    def kernel(pools, i, new, pg, off):
        from ollama_operator_tpu.ops.pallas import kv_write as KW
        return KW.paged_kv_write(pools, i, pg, off, new)

    return {"none": lambda pools, i, new, pg, off: pools,
            "per_head": xla(per_head, False),
            "per_head_u": xla(per_head, True),
            "windowed": xla(windowed, False),
            "windowed_u": xla(windowed, True),
            "kernel": kernel}


def program(form, shape, steps):
    """``steps`` decode steps' writes of every layer in one program."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    L, _P, KvH, ps, B = shape

    def pin_kernel(lay_ref, kq, ks, vq, vs, o_ref, b8, b32, sem):
        lay = lay_ref[0]
        acc = jnp.zeros((8, 128), jnp.float32)
        for src, buf in ((kq, b8), (vq, b8), (ks, b32), (vs, b32)):
            cp = pltpu.make_async_copy(src.at[lay, 0, 0], buf, sem.at[0])
            cp.start()
            cp.wait()
            acc = acc + buf[:1, :].astype(jnp.float32)
        o_ref[...] = acc

    def pin(pools, i):
        # scale pools with a unit axis before the lanes, as the attention
        # kernel takes them (ops/pallas/paged.py)
        pools = [p if p.ndim == 5 else p.reshape(*p.shape[:3], 1, SP)
                 for p in pools]
        any_ = pl.BlockSpec(memory_space=pl.ANY)
        return pl.pallas_call(
            pin_kernel, name="pool_reader",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,), in_specs=[any_] * 4,
                out_specs=pl.BlockSpec((8, 128), lambda g, lay: (0, 0)),
                scratch_shapes=[pltpu.VMEM((ps, HD), jnp.int8),
                                pltpu.VMEM((1, SP), jnp.float32),
                                pltpu.SemaphoreType.DMA((1,))]),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        )(jnp.reshape(i, (1,)), *pools)

    def run(pools, codes, scales, tables, start):
        def step(s, carry):
            pools, acc = carry
            pos = start + s                             # [B]
            pg = tables[jnp.arange(B), pos // ps][:, None]
            off = (pos % ps)[:, None]
            cq = codes + s.astype(jnp.int8)             # new values a step
            cs = scales + s.astype(jnp.float32)
            new = (cq, cs, -cq, -cs)

            def layer(c, i):
                pools, acc = c
                pools = form(pools, i, new, pg, off)
                return (pools, acc + pin(pools, i)), None
            return lax.scan(layer, (pools, acc),
                            jnp.arange(L, dtype=jnp.int32))[0]
        return lax.fori_loop(0, steps, step,
                             (pools, jnp.zeros((8, 128), jnp.float32)))
    return jax.jit(run, donate_argnums=(0,))


def arg_shapes(shape, sharding=None):
    import jax
    import jax.numpy as jnp
    L, P, KvH, ps, B = shape
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    pools = (sds((L, P, KvH, ps, HD), jnp.int8),
             sds((L, P, KvH, SP), jnp.float32)) * 2
    return (pools, sds((B, 1, KvH, HD), jnp.int8),
            sds((B, 1, KvH), jnp.float32),
            sds((B, (P - 1) // B), jnp.int32), sds((B,), jnp.int32))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--forms", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    table = forms()
    if args.forms:
        table = {k: table[k] for k in args.forms.split(",")}
    shapes = {k: v for k, v in SHAPES.items()
              if not args.quick or k != "gqa-32-8"}

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        for sname, shape in shapes.items():
            pool_bytes = shape[0] * shape[1] * shape[2] * shape[3] * HD
            for fname, form in table.items():
                try:
                    c = program(form, shape, args.steps).lower(
                        *arg_shapes(shape, one)).compile()
                    tmp = c.memory_analysis().temp_size_in_bytes
                    print(f"{sname:14s} {fname:11s} temporaries "
                          f"{tmp / 2**20:8.1f} MiB (one code pool "
                          f"{pool_bytes / 2**20:.0f} MiB)", flush=True)
                except Exception as e:  # noqa: BLE001 — what the compiler refuses
                    print(f"{sname:14s} {fname:11s} "
                          f"{type(e).__name__}: {str(e)[:300]}", flush=True)
        return 0

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        print("needs the TPU", file=sys.stderr)
        return 1

    results = []
    for sname, shape in shapes.items():
        L, P, KvH, ps, B = shape
        nblk = (P - 1) // B

        def fresh():
            def make(key):
                k1, k2 = jax.random.split(key)
                q = jax.random.randint(k1, (L, P, KvH, ps, HD), -127, 128,
                                       jnp.int8)
                s = jax.random.uniform(k2, (L, P, KvH, SP), jnp.float32)
                return q, s, -q, -s
            return jax.jit(make)(jax.random.PRNGKey(0))

        codes = jax.random.randint(jax.random.PRNGKey(1), (B, 1, KvH, HD),
                                   -90, 90, jnp.int8)
        scales = jax.random.uniform(jax.random.PRNGKey(2), (B, 1, KvH),
                                    jnp.float32)
        # slot b owns pages 1 + b*nblk ...; page 0 is the trash page, and
        # two slots in eight write there (a vacant slot's row)
        tables = 1 + np.arange(B * nblk, dtype=np.int32).reshape(B, nblk)
        tables[::4] = 0
        tables = jnp.asarray(tables)
        start = jnp.asarray((np.arange(B) * 7) % (ps * (nblk - 1)), jnp.int32)

        ref = None
        for fname, form in table.items():
            row = {"shape": sname, "form": fname, "slots": B, "kv_heads": KvH,
                   "layers": L, "page_size": ps}
            try:
                fn = program(form, shape, args.steps)
                c = fn.lower(fresh(), codes, scales, tables, start).compile()
                row["temporaries_mib"] = round(
                    c.memory_analysis().temp_size_in_bytes / 2**20, 1)
                pools, _ = c(fresh(), codes, scales, tables, start)
                jax.block_until_ready(pools)
                # the trash page holds whatever collided there last
                got = [np.asarray(p[:, 1:]) for p in pools]
                if fname == "per_head":
                    ref = got
                elif ref is not None and fname != "none":
                    row["bytes_equal"] = all(
                        np.array_equal(a, b) for a, b in zip(got, ref))
                del got
                best = float("inf")
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    pools, acc = c(pools, codes, scales, tables, start)
                    jax.block_until_ready(acc)
                    best = min(best, time.perf_counter() - t0)
                del pools
                row["ms_per_step"] = round(best / args.steps * 1e3, 4)
            except Exception as e:  # noqa: BLE001 — a form the chip refuses
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            results.append(row)
            print(json.dumps(row), flush=True)
        base = next((r.get("ms_per_step") for r in results
                     if r["shape"] == sname and r["form"] == "none"), None)
        for r in results:
            if r["shape"] == sname and base and "ms_per_step" in r:
                r["write_ms_per_step"] = round(r["ms_per_step"] - base, 4)
                r["ns_per_head_index"] = round(
                    r["write_ms_per_step"] * 1e6 / (B * KvH * 4 * L), 1)

    print(f"\n{'shape':14s} {'form':11s} {'write ms/step':>13s} "
          f"{'ns/(slot,head)':>14s} {'temp MiB':>9s} equal")
    for r in results:
        print(f"{r['shape']:14s} {r['form']:11s} "
              f"{r.get('write_ms_per_step', float('nan')):13.3f} "
              f"{r.get('ns_per_head_index', float('nan')):14.1f} "
              f"{r.get('temporaries_mib', float('nan')):9.1f} "
              f"{r.get('bytes_equal', r.get('error', ''))}")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kv_write_microbench.json", "w") as f:
        json.dump({"device": dev.device_kind, "steps": args.steps,
                   "rows": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
