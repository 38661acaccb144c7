"""A causal convolution's carried inputs on a decode step: the gather against
the select, on the chip.

``decoder._causal_conv`` keeps, for the next call, the K-1 inputs before each
row's last real position. With one new position a row either takes it (the
old inputs shifted by one, then the new one) or sits out (the old ones), and
the three hybrid cells' decode steps run that for every recurrent layer:

  olmo     ``olmo-hybrid-7b``: 9 delta layers, 32 slots, 4 taps over q, k, v
           (11,520 channels), silu
  granite  ``granite-4.0-h-small``: 9 Mamba-2 layers, 32 slots, 4 taps over
           x, B, C (8,448 channels), bias + silu
  lfm2     ``lfm2-8b-a1b``: 12 short convolutions, 32 slots, 3 taps (2,048
           channels), bare

One decode step's convolutions of a cell through

  gather   the form every PR before 47 served (``tests/test_conv_carry.py``
           keeps it as its reference): ``jax.vmap(lax.dynamic_slice_in_dim)``
           over the slots, which the chip's compiler runs at olmo's width as
           a loop of one-row ``dynamic-update-slice``s a layer
  select   ``decoder._causal_conv`` as it stands: for one position a
           ``where`` over whole arrays
  none     the taps alone, the leaf handed on as it came (what the
           multiply-adds and making the inputs cost)

as the decoder runs it: inside a ``lax.scan`` over the layers with the leaf
as the donated carry, ``--steps`` steps a call. Reported per cell and form:
milliseconds a step, the carry's part (less ``none``'s), microseconds a
layer, and whether outputs and leaf equal the gather's bit for bit.

Usage (the chip): python hack/conv_microbench.py [--cells a,b]
Here (compiles every form for a described v5e, runs nothing):
    JAX_PLATFORMS=cpu python hack/conv_microbench.py --compile-only
Writes chiprun_out/conv_microbench.json and prints a table.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# layers, slots, taps, channels, bias + activation: the cells' resolved engines
CELLS = {"olmo": (9, 32, 4, 11520, "silu"),
         "granite": (9, 32, 4, 8448, "bias+silu"),
         "lfm2": (12, 32, 3, 2048, "bare")}


def forms():
    from ollama_operator_tpu.models import decoder
    # the gather form, kept where the tests hold the select to it
    from test_conv_carry import by_gather as gather

    def none(conv, row, new, w, n_valid, bias=None, act=None):
        return gather(conv, row, new, w, n_valid, bias, act)[0], conv

    return {"none": none, "gather": gather, "select": decoder._causal_conv}


def program(form, dress: str, steps: int):
    """``steps`` decode steps' convolutions of every layer in one program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(conv, new, w, bias, live):
        def step(s, carry):
            def layer(c, xs):
                conv, acc = c
                i, new, w, bias = xs
                out, conv = form(
                    conv, i, new + (1e-3 * s).astype(new.dtype), w, live,
                    bias if "bias" in dress else None,
                    jax.nn.silu if "silu" in dress else None)
                return (conv, acc + out), None
            return lax.scan(layer, carry, (
                jnp.arange(conv.shape[0], dtype=jnp.int32), new, w, bias))[0]
        acc = jnp.zeros(new.shape[1:], jnp.float32)
        return lax.fori_loop(0, steps, step, (conv, acc))
    return jax.jit(run, donate_argnums=(0,))


def arg_shapes(cell: str, sharding=None):
    import jax
    import jax.numpy as jnp
    L, B, K, C, _ = CELLS[cell]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (sds((L, B, K - 1, C), jnp.float32),
            sds((L, B, 1, C), jnp.bfloat16), sds((L, K, C), jnp.bfloat16),
            sds((L, C), jnp.bfloat16), sds((B,), jnp.int32))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--cells", default=",".join(CELLS))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    table = forms()
    cells = args.cells.split(",")

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        for cell in cells:
            for name, form in table.items():
                c = program(form, CELLS[cell][4], args.steps).lower(
                    *arg_shapes(cell, one)).compile()
                m = c.memory_analysis()
                print(f"{cell:8s} {name:7s} loops {c.as_text().count(' while('):2d}"
                      f" temporaries {m.temp_size_in_bytes / 2**20:7.1f} MiB,"
                      f" the leaf {m.alias_size_in_bytes / 2**20:.0f} MiB",
                      flush=True)
        return 0

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        print("needs the TPU", file=sys.stderr)
        return 1

    results = []
    for cell in cells:
        L, B, K, C, dress = CELLS[cell]
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        new = jax.random.normal(ks[0], (L, B, 1, C)).astype(jnp.bfloat16)
        w = jax.random.normal(ks[1], (L, K, C)).astype(jnp.bfloat16)
        bias = jax.random.normal(ks[2], (L, C)).astype(jnp.bfloat16)
        # every fourth slot sits the steps out: it keeps its bits
        live = jnp.asarray(np.arange(B) % 4 != 3, jnp.int32)

        def fresh():
            return jax.jit(lambda key: jax.random.normal(
                key, (L, B, K - 1, C)))(ks[3])

        ref, rows = None, []
        for name, form in table.items():
            row = {"cell": cell, "form": name}
            c = program(form, dress, args.steps).lower(
                fresh(), new, w, bias, live).compile()
            row["loops"] = c.as_text().count(" while(")
            conv, acc = c(fresh(), new, w, bias, live)
            got = (np.asarray(conv), np.asarray(acc))
            if name == "gather":
                ref = got
            elif name == "select":
                row["leaf_bit_equal"] = bool(np.array_equal(got[0], ref[0]))
                row["outputs_bit_equal"] = bool(np.array_equal(got[1], ref[1]))
            best = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                conv, acc = c(conv, new, w, bias, live)
                jax.block_until_ready(acc)
                best = min(best, time.perf_counter() - t0)
            del conv, got
            row["ms_per_step"] = round(best / args.steps * 1e3, 4)
            rows.append(row)
            print(json.dumps(row), flush=True)
        base = rows[0]["ms_per_step"]
        for r in rows[1:]:
            r["carry_ms_per_step"] = round(r["ms_per_step"] - base, 4)
            r["carry_us_per_layer"] = round(
                1e3 * r["carry_ms_per_step"] / L, 2)
        results += rows

    print(f"\n{'cell':8s} {'form':7s} {'ms/step':>8s} {'carry ms/step':>13s} "
          f"{'us/layer':>9s} {'loops':>5s} leaf / outputs equal the gather's")
    for r in results:
        print(f"{r['cell']:8s} {r['form']:7s} {r['ms_per_step']:8.3f} "
              f"{r.get('carry_ms_per_step', float('nan')):13.3f} "
              f"{r.get('carry_us_per_layer', float('nan')):9.2f} "
              f"{r['loops']:5d} {r.get('leaf_bit_equal', '')} "
              f"{r.get('outputs_bit_equal', '')}")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/conv_microbench.json", "w") as f:
        json.dump({"device": dev.device_kind, "steps": args.steps,
                   "cells": CELLS, "rows": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
