"""The paged decode kernel alone, on the engine's own pool, at the two dense
cells' resolved shapes: what a slot visit and a page cost in the parent's
kernel (one pipeline a slot: every slot's first page waited for in full) and
in the change's (one pipeline over the batch's pages), and where the
parent's visit goes.

  starcoder2  30 layers, 64 slots, GQA 24/2, 128-token int8 pages + scales,
              sliding window 4096, 768 pages
  phi-2       32 layers, 32 slots, MHA 32/32, head_dim 80 in 128 lanes,
              64-token int8 pages + scales, 160 pages

Lengths: ``mix`` uniform 32-448 (what ``decode-saturated``'s window holds),
``long`` 128-1,280 (``decode-long``'s), ``deep`` a uniform 2,048 (ROADMAP
S4's question; where the cell's pool is too small for it, table entries
repeat pages: a copy costs what it costs whichever slot asked). A call is
one decode step's attention as the decoder runs it: a ``lax.scan`` over the
layers, the layer index a traced scalar, ``--steps`` steps a program.

Forms: ``parent`` (the file ``--parent`` names, or ``git show <rev>:...``),
``change`` (the tree's kernel at the depth its own rule gives),
``change@D`` (the same kernel held to D buffers, to see what the rule should
say), ``NAME`` for every ``--also NAME=file`` (another form of the kernel's
file, for the builder who chooses between forms), and the parent's
visit with a part removed, as far as that can be done (``--split``):
``p.nomath`` copies and waits but scores nothing, ``p.nodma`` scores
whatever lies in the buffers and copies nothing, ``p.empty`` does neither
(the grid step, the q and output blocks, the scalar loop), ``p.2desc``
is ``p.nomath`` without the two scale copies. ``none`` is the program
without the kernel. The change's outputs are asserted bit-equal to the
parent's, layer by layer, at every length set.

Usage (the chip; the copy there has no .git, so export the parent first:
``mkdir -p .chip_parent && git archive <rev> | tar -x -C .chip_parent``):
    python hack/paged_microbench.py [--cells a,b] [--lengths a,b] [--sweep]
Here: JAX_PLATFORMS=cpu python hack/paged_microbench.py --compile-only
      (compiles every form for a described v5e, runs nothing), or
      ... --rehearse (toy sizes through the interpreter, times mean nothing)
Writes chiprun_out/paged_microbench.json and prints a table.
"""
from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

KERNEL = "ollama_operator_tpu/ops/pallas/paged.py"
PARENT_REV = "a203b40"          # the commit PR 48 was cut from
PARENT_DIR = os.path.join(ROOT, ".chip_parent")

# layers, slots, query heads, kv heads, query head_dim, page size, pool
# pages (with the trash page), sliding window: the cells' resolved engines
CELLS = {"starcoder2": (30, 64, 24, 2, 128, 128, 768, 4096),
         "phi-2": (32, 32, 32, 32, 80, 64, 160, 0)}
TOY = {"starcoder2": (2, 8, 8, 2, 128, 8, 24, 20),
       "phi-2": (2, 4, 4, 4, 80, 8, 12, 0)}
LENGTHS = {"mix": (32, 448), "long": (128, 1280), "deep": (2048, 2048)}
TOY_LENGTHS = {"mix": (2, 28), "long": (8, 80), "deep": (128, 128)}
SWEEP = (2, 3, 4)


def load_kernel(where: str, name: str):
    """Another ``ops/pallas/paged.py`` (a file, a tree or a git revision)
    as a module beside the tree's."""
    if os.path.isfile(where):
        src = open(where).read()
    elif os.path.isfile(os.path.join(where, KERNEL)):
        src = open(os.path.join(where, KERNEL)).read()
    else:
        src = subprocess.run(["git", "show", f"{where}:{KERNEL}"], cwd=ROOT,
                             check=True, capture_output=True, text=True).stdout
    name = f"ollama_operator_tpu.ops.pallas._paged_{name}"
    spec = importlib.util.spec_from_loader(name, loader=None)
    mod = importlib.util.module_from_spec(spec)
    mod.__package__ = "ollama_operator_tpu.ops.pallas"
    sys.modules[name] = mod
    exec(compile(src, f"<{name} {KERNEL}>", "exec"), mod.__dict__)
    return mod


def split_call(parent, q, k_pool, v_pool, layer, tables, lengths, scale,
               softcap=0.0, sliding_window=0, *, nblk, interpret=False,
               math=True, dma=True, scales=True):
    """The parent's kernel (one grid step a slot, two buffers) with a part
    left out; with all three kept it is the parent's, and the caller checks
    that it then reads as the parent does."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, _, H, hd_q = q.shape
    k_arr, v_arr = k_pool["q"], v_pool["q"]
    L, P, KvH, ps, hd = k_arr.shape
    G = H // KvH
    Gp = max(8, -(-G // 8) * 8)
    sp = k_pool["s"].shape[-1]
    window = sliding_window
    qg = jnp.pad(q.reshape(B, KvH, G, hd_q),
                 ((0, 0), (0, 0), (0, Gp - G), (0, hd - hd_q)))

    def kernel(lay_ref, len_ref, tbl_ref, q_ref, k_hbm, v_hbm, ks_hbm,
               vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, acc_ref, m_ref,
               l_ref, sem):
        b = pl.program_id(0)
        lay = lay_ref[0]
        qp = len_ref[b]
        nlive = qp // ps + 1
        start = jnp.int32(0)
        if window:
            start = jnp.maximum(start, (qp - window + 1) // ps)

        def copies(i, slot):
            if not dma:
                return []
            pg = tbl_ref[b, i]
            out = [pltpu.make_async_copy(k_hbm.at[lay, pg], kbuf.at[slot],
                                         sem.at[0, slot]),
                   pltpu.make_async_copy(v_hbm.at[lay, pg], vbuf.at[slot],
                                         sem.at[1, slot])]
            if scales:
                out += [pltpu.make_async_copy(ks_hbm.at[lay, pg],
                                              ksbuf.at[slot], sem.at[2, slot]),
                        pltpu.make_async_copy(vs_hbm.at[lay, pg],
                                              vsbuf.at[slot], sem.at[3, slot])]
            return out

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, parent.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(start < nlive)
        def _prime():
            for c in copies(start, start % 2):
                c.start()
        qv = q_ref[0]

        def body(i, _):
            slot = i % 2

            @pl.when(i + 1 < nlive)
            def _prefetch():
                for c in copies(i + 1, (i + 1) % 2):
                    c.start()
            for c in copies(i, slot):
                c.wait()
            if math:
                parent._flash_page_update(
                    qv, kbuf[slot], vbuf[slot], ksbuf[slot][:, :, :ps],
                    vsbuf[slot][:, :, :ps], m_ref, l_ref, acc_ref,
                    k_start=i * ps, qp=qp, scale=scale, softcap=softcap,
                    window=window, ps=ps, kvh=KvH, gp=Gp, cdt=jnp.bfloat16)
            return 0
        jax.lax.fori_loop(start, nlive, body, 0)
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    blk = pl.BlockSpec((1, KvH, Gp, hd), lambda b, *pref: (b, 0, 0, 0))
    out = pl.pallas_call(
        kernel, name="paged_split",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[blk, hbm, hbm, hbm, hbm], out_specs=blk,
            scratch_shapes=[
                pltpu.VMEM((2, KvH, ps, hd), k_arr.dtype),
                pltpu.VMEM((2, KvH, ps, hd), v_arr.dtype),
                pltpu.VMEM((2, KvH, 1, sp), jnp.float32),
                pltpu.VMEM((2, KvH, 1, sp), jnp.float32),
                pltpu.VMEM((KvH, Gp, hd), jnp.float32),
                pltpu.VMEM((KvH, Gp, 1), jnp.float32),
                pltpu.VMEM((KvH, Gp, 1), jnp.float32),
                pltpu.SemaphoreType.DMA((4, 2))]),
        out_shape=jax.ShapeDtypeStruct((B, KvH, Gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      tables.astype(jnp.int32), qg, k_arr, v_arr,
      k_pool["s"].reshape(L, P, KvH, 1, -1), v_pool["s"].reshape(L, P, KvH, 1, -1))
    return out[:, :, :G, :hd_q].reshape(B, 1, H, hd_q)


def held(change, depth: int):
    """The tree's kernel with its depth rule overruled for one trace."""
    def call(*a, **kw):
        rule = change._walk_depth
        change._walk_depth = lambda page_bytes: depth
        try:
            return change.paged_decode_attention(*a, **kw)
        finally:
            change._walk_depth = rule
    return call


def forms(parent, change, also: dict, split: bool, sweep: bool):
    out = {"none": None, "parent": parent.paged_decode_attention,
           "change": change.paged_decode_attention}
    out.update({n: m.paged_decode_attention for n, m in also.items()})
    if split:
        def cut(**left_out):
            return functools.partial(split_call, parent, **left_out)
        out.update({"p.whole": cut(), "p.nomath": cut(math=False),
                    "p.nodma": cut(dma=False),
                    "p.empty": cut(math=False, dma=False),
                    "p.2desc": cut(math=False, scales=False)})
    if sweep:
        out.update({f"change@{d}": held(change, d) for d in SWEEP})
    return out


def program(form, L: int, steps: int, scale: float, window: int, nblk: int,
            interpret: bool, keep_layers: bool):
    """``steps`` decode steps' attention over every layer in one program;
    ``keep_layers`` hands back every layer's output of one step instead of
    their sum (the bit-equality check's operand)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(kp, vp, q, tables, lengths):
        def layer(acc, i):
            if form is None:
                out = q * (1.0 + i.astype(q.dtype))
            else:
                out = form(q, kp, vp, i, tables, lengths, scale, 0.0, window,
                           nblk=nblk, interpret=interpret)
            return acc + out, (out if keep_layers else None)
        layers = jnp.arange(L, dtype=jnp.int32)
        if keep_layers:
            return lax.scan(layer, jnp.zeros_like(q), layers)[1]
        return lax.fori_loop(
            0, steps, lambda s, acc: lax.scan(layer, acc, layers)[0],
            jnp.zeros_like(q))
    return jax.jit(run)


def pool_shapes(shape, sharding=None):
    import jax
    import jax.numpy as jnp
    L, B, H, KvH, hd_q, ps, P, _ = shape
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    pool = {"q": sds((L, P, KvH, ps, 128), jnp.int8),
            "s": sds((L, P, KvH, -(-ps // 128) * 128), jnp.float32)}
    return pool, sds((B, 1, H, hd_q), jnp.bfloat16)


def draw(shape, span, seed: int):
    """Lengths uniform over ``span`` and a block table of distinct pages
    while the pool has them (page 0 is the engine's trash page)."""
    import numpy as np
    _, B, _, _, _, ps, P, _ = shape
    rng = np.random.default_rng(seed)
    lengths = rng.integers(span[0], span[1] + 1, B).astype(np.int32)
    nblk = int(lengths.max()) // ps + 1
    pages = rng.permutation(np.arange(1, P))
    tables = np.resize(pages, (B, nblk)).astype(np.int32)
    return lengths, tables, nblk


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--parent", default="")
    ap.add_argument("--also", action="append", default=[],
                    metavar="NAME=FILE")
    ap.add_argument("--cells", default="starcoder2,phi-2")
    ap.add_argument("--lengths", default="mix,long,deep")
    ap.add_argument("--split", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=48)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ollama_operator_tpu.ops.pallas import paged as change
    parent = load_kernel(args.parent or (
        PARENT_DIR if os.path.isdir(PARENT_DIR) else PARENT_REV), "parent")
    also = {n: load_kernel(f, n) for n, f in
            (a.split("=", 1) for a in args.also)}
    table = forms(parent, change, also, args.split, args.sweep)
    shapes, spans = (TOY, TOY_LENGTHS) if args.rehearse else (CELLS, LENGTHS)
    interpret = args.rehearse
    steps = 2 if args.rehearse else args.steps
    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU here: --compile-only or --rehearse", file=sys.stderr)
        return 2

    rows = []
    for cell in args.cells.split(","):
        shape = shapes[cell]
        L, B, H, KvH, hd_q, ps, P, window = shape
        scale = 1.0 / hd_q ** 0.5
        if not args.compile_only:
            keys = jax.random.split(jax.random.key(args.seed), 5)
            pool_sds, q_sds = pool_shapes(shape)

            def pool(kq, ks):
                return {"q": jax.random.randint(kq, pool_sds["q"].shape, -127,
                                                128, jnp.int8),
                        "s": jax.random.uniform(ks, pool_sds["s"].shape,
                                                jnp.float32, 0.005, 0.02)}
            kp, vp = pool(keys[0], keys[1]), pool(keys[2], keys[3])
            q = jax.random.normal(keys[4], q_sds.shape, jnp.bfloat16)
        for which in args.lengths.split(","):
            lengths, tables, nblk = draw(shape, spans[which], args.seed)
            first = np.maximum(0, (lengths - window + 1) // ps) if window else 0
            pages = int((lengths // ps + 1 - first).sum())
            # the split and the sweep are read where the cells serve
            names = [n for n in table if which == "mix" or n in
                     ("none", "parent", "change", *also)]
            times, layers = {}, {}
            for name in names:
                build = functools.partial(
                    program, table[name], L, steps, scale, window, nblk,
                    interpret)
                if args.compile_only:
                    pool_sds, q_sds = pool_shapes(shape, sharding)
                    sds = functools.partial(jax.ShapeDtypeStruct,
                                            dtype=jnp.int32, sharding=sharding)
                    t0 = time.time()
                    txt = build(False).lower(
                        pool_sds, pool_sds, q_sds, sds(tables.shape),
                        sds(lengths.shape)).compile().as_text()
                    assert name == "none" or "tpu_custom_call" in txt, name
                    print(f"{cell:11s} {which:5s} {name:12s} compiles "
                          f"({time.time() - t0:.1f} s)", flush=True)
                    continue
                ops = (kp, vp, q, jnp.asarray(tables), jnp.asarray(lengths))
                if name in ("parent", "change", "p.whole", *also) or "@" in name:
                    layers[name] = np.asarray(
                        build(True)(*ops).astype(jnp.float32))
                fn = build(False)
                fn(*ops).block_until_ready()
                took = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    fn(*ops).block_until_ready()
                    took.append(time.perf_counter() - t0)
                times[name] = statistics.median(took) / steps
            if args.compile_only:
                continue
            for name, got in layers.items():
                assert np.array_equal(got, layers["parent"]), (
                    f"{cell} {which} {name}: not the parent's bits "
                    f"(max |d| {np.abs(got - layers['parent']).max()})")
            for name in names[1:]:
                ms = (times[name] - times["none"]) * 1e3
                rows.append({
                    "cell": cell, "lengths": which, "form": name,
                    "ms_step": ms, "us_visit": ms * 1e3 / (L * B),
                    "us_page": ms * 1e3 / (L * pages),
                    "pages_a_slot": pages / B,
                    "bit_equal_to_parent": name in layers or None})
                r = rows[-1]
                print(f"{cell:11s} {which:5s} {name:12s} {ms:8.3f} ms a step "
                      f"{r['us_visit']:7.3f} us a visit {r['us_page']:7.3f} "
                      f"us a page ({r['pages_a_slot']:.2f} pages a slot)"
                      + ("  bit-equal" if name in layers else ""), flush=True)
        if not args.compile_only:
            del kp, vp, q
    if rows and not args.rehearse:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "paged_microbench.json"), "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "steps": steps, "reps": args.reps, "rows": rows}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
