"""The latent-attention decode chunk at the cell's shapes, on the chip, through
the engine's OWN decode program: seeded weights as the harness makes them, the
resolved slots all admitted (64 tokens each) and their lengths then set by
hand to the contexts a variant names (the cache's content is no matter to a
timing), ``Engine.decode_n`` of a chunk timed. Variants tell the parts apart:
the attended bucket (all slots shallow; a few slots deep, as the cell's mix has
them; all deep) and ``no_index`` (``index_topk`` past the bucket: no scores, no
top-k).

    chiprun -- python hack/latent_microbench.py

Prints one JSON line a variant: ms a step (the median of ``--reps`` chunks)
and the device's peak memory. The builder's tool for a chip call, not part of
the benchmark."""
import argparse
import dataclasses
import gc
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="glm-5")
    ap.add_argument("--reps", type=int, default=3)
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
                                     args.config + ".json"), False)
    cfg = sc.model_config(conf, False)
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

    mixed = [300, 500, 800, 1100, 1400, 700, 900, 1200] * 7 + [
        2300, 2700, 3100, 3600, 600, 1000, 1300, 1700]
    timed(cfg, [("all 900", [900]), ("all 1900", [1900]),
                ("the mix: 4 of 64 past 2048", mixed), ("all 3600", [3600])])
    timed(dataclasses.replace(cfg, index_topk=8192),
          [("the mix, no_index", mixed)])


if __name__ == "__main__":
    main()
