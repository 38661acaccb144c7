"""The latent-attention decode chunk at the cell's shapes, on the chip, through
the engine's OWN decode program: seeded weights as the harness makes them, the
resolved slots all admitted (64 tokens each) and their lengths then set by
hand to the contexts a variant names (the cache's content is no matter to a
timing), ``Engine.decode_n`` of a chunk timed. Variants tell the parts apart:
the attended bucket (all slots shallow; a few slots deep, as the cell's mix has
them; all deep) and ``no_index`` (``index_topk`` past the bucket: no scores, no
top-k). Before them the decode KERNEL alone (``ops/pallas/latent.py``, PR 51)
over a leaf of the cell's shape at the same contexts: ms a layer and us a live
row, by positions a block (``--blocks``), so that what is left of a visit can
be priced as ``hack/paged_microbench.py`` prices ``paged_v3``'s.

    chiprun -- python hack/latent_microbench.py [--kernel-only] [--blocks 256,512,1024]

Prints one JSON line a variant: ms a step (the median of ``--reps`` chunks)
and the device's peak memory. ``--compile-only`` compiles the kernel's
variants for a described v5e here and runs nothing; ``--rehearse`` runs them
at toy shapes through the interpreter. The builder's tool for a chip call, not
part of the benchmark."""
import argparse
import dataclasses
import gc
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


MIXED = [300, 500, 800, 1100, 1400, 700, 900, 1200] * 7 + [
    2300, 2700, 3100, 3600, 600, 1000, 1300, 1700]
CONTEXTS = [("all 900", [900]), ("all 1900", [1900]),
            ("the mix: 4 of 64 past 2048", MIXED), ("all 3600", [3600])]


def kernel_alone(cfg, args):
    """The kernel over every layer of a leaf of the cell's shape, a chunk's
    worth of steps in one program (the leaf whole, the layer a traced
    scalar, as the decode program calls it): ms a layer and us a live row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ollama_operator_tpu.ops.pallas import latent as LK
    toy = args.rehearse
    La, B, S = (2, 4, 128) if toy else (cfg.n_full_layers, 64, 4096)
    H, C, dr = cfg.n_heads, cfg.kv_latent_dim, cfg.qk_rope_dim
    W = cfg.cache_row_dims[1]
    dt = jnp.float32 if toy else jnp.bfloat16
    steps = 2 if toy else 32
    shapes = dict(q=((La, B, 1, S, W), jnp.int8), s=((La, B, 2, S), jnp.float32),
                  q_abs=((B, H, C), dt), q_rope=((B, H, dr), dt),
                  keep=((B, S), jnp.float32))

    def chunk(block, masked):
        def run(q, s, q_abs, q_rope, keep, pos, live):
            def layer(i, acc):
                o = LK.latent_decode(
                    {"q": q, "s": s}, jax.lax.rem(i, La), q_abs, q_rope, pos,
                    live, keep if masked else None, (C + dr) ** -0.5,
                    block=block, interpret=toy)
                return acc + o.astype(jnp.float32)
            return jax.lax.fori_loop(0, La * steps, layer,
                                     jnp.zeros((B, H, C), jnp.float32))
        return jax.jit(run)

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        sds = {k: jax.ShapeDtypeStruct(*v, sharding=one)
               for k, v in shapes.items()}
        vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one)
        for block in args.blocks:
            for masked in (False, True):
                chunk(block, masked).lower(*sds.values(), vec, vec).compile()
                print(json.dumps(dict(compiled="latent_decode", block=block,
                                      masked=masked)), flush=True)
        return
    rng = np.random.default_rng(0)
    arrs = [jnp.asarray(rng.integers(-127, 128, shapes["q"][0]), jnp.int8),
            jnp.asarray(rng.uniform(0.004, 0.02, shapes["s"][0]), jnp.float32),
            jnp.asarray(rng.normal(size=shapes["q_abs"][0]), dt),
            jnp.asarray(rng.normal(size=shapes["q_rope"][0]), dt),
            jnp.ones(shapes["keep"][0], jnp.float32)]
    for name, lens in ([("toy", [5, 100, 64, 1])] if toy else CONTEXTS):
        lens = np.resize(np.asarray(lens, np.int32), B)
        live = jnp.ones((B,), jnp.int32)
        for block in args.blocks:
            # the keep mask rides in where the deepest context passes
            # index_topk, as in the decode program of that bucket
            masked = bool(lens.max() >= cfg.index_topk) and not toy
            fn = chunk(block, masked)
            times = []
            for _ in range(args.reps + 1):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*arrs, jnp.asarray(lens), live))
                times.append(time.perf_counter() - t0)
            best = min(times[1:]) / (La * steps)
            bs = LK._block_rows(S, block or LK._BLOCK_ROWS, toy)
            print(json.dumps(dict(
                kernel="latent_decode", variant=name, block=bs, masked=masked,
                ms_a_layer=1e3 * best, ms_a_step=1e3 * best * La,
                us_a_live_row=1e6 * best / float(lens.sum() + B),
                us_a_block=1e6 * best / float((lens // bs + 1).sum()),
                live_rows=int(lens.sum() + B))), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="glm-5")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--blocks", default="0",
                    type=lambda s: [int(x) for x in s.split(",")],
                    help="positions a block, several to sweep (0: the "
                         "kernel's own _BLOCK_ROWS)")
    ap.add_argument("--kernel-only", action="store_true")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import server_child as sc
    from ollama_operator_tpu.runtime import compile_cache
    from ollama_operator_tpu.runtime.engine import Engine, SlotOptions
    from ollama_operator_tpu.server.app import device_memory
    compile_cache.enable()
    conf = sc.load_conf(os.path.join("benchmark", "configs",
                                     args.config + ".json"), args.rehearse)
    cfg = sc.model_config(conf, args.rehearse)
    kernel_alone(cfg, args)
    if args.kernel_only or args.compile_only or args.rehearse:
        return
    _w, ecfg = sc.resolve(cfg, jax.default_backend(), False)
    params = sc.make_weights(cfg, 4600000001, 0, jnp.bfloat16)
    rng = np.random.default_rng(0)
    greedy = SlotOptions(temperature=0.0, repeat_penalty=1.0)
    n = ecfg.decode_chunk

    def timed(cfg_, variants):
        eng = Engine(cfg_, params, mesh=None, ecfg=ecfg)
        B = eng.n_slots
        for s in range(B):
            eng.admit(s, rng.integers(3, cfg.vocab_size, (64,)
                                      ).astype(np.int32), greedy)
        for name, lens in variants:
            lens = np.resize(np.asarray(lens, np.int64), B)
            times = []
            for rep in range(args.reps + 1):
                eng._host_lengths[:] = lens
                eng.lengths = eng._g(lens.astype(np.int32), eng._slot_sh)
                t0 = time.perf_counter()
                eng.decode_n(n)
                times.append(time.perf_counter() - t0)
            print(json.dumps(dict(
                variant=name, slots=B, bucket=eng._attn_bucket(0),
                ms_a_step=1e3 * statistics.median(times[1:]) / n,
                chunks_ms=[round(1e3 * t, 1) for t in times[1:]],
                first_call_s=round(times[0], 1),
                peak_gb=max(d["peak_bytes_in_use"]
                            for d in device_memory()) / 1e9)), flush=True)
        del eng
        gc.collect()

    timed(cfg, CONTEXTS)
    timed(dataclasses.replace(cfg, index_topk=8192),
          [("the mix, no_index", MIXED)])


if __name__ == "__main__":
    main()
