"""The gated delta rule's one-position update: its forms, on the chip.

At the shapes of the benchmark's ``olmo-hybrid-7b.decode-saturated`` cell
(nine linear layers, 32 slots, 30 heads of keys 96 / values 192, a float32
matrix a head: 1.35 GB of state read and written a step, 1.65 ms at 819
GB/s; 1.80 GB and 2.2 ms over the rows as they lie, padded from 192 to 256
lanes), the time of one decode step's nine updates through

  four_pass  ``decoder._delta_rule``'s T == 1 branch as the compiler runs
             it: S' and S'^T k, the correction and the write, S1^T q: three
             reads of the state and a write; the form every PR before 45
             served
  two_read   PR 44's algebra: both read-outs from S0 in one MXU pass
             (r0 = S0^T k, p0 = S0^T q; o = exp(g) p0 + (k . q) u), then the
             update: two reads and a write
  kernel     ops/pallas/delta.delta_update: one pallas_call a layer, the
             leaf whole and aliased, a block of heads of one slot a grid
             step: read once, written once (``kernel@N``: N heads a block)
  packed     the same kernel over a leaf that lays two heads side by side
             along lanes, ``[Ld, B, H/2, dk, 2 dv]``: 384 lanes, three whole
             tiles, no padding (what ``decoder.empty_state`` makes of the
             cell's heads since PR 45)

as the decoder runs it: inside a ``lax.scan`` over the layers with the leaf
as the donated carry, ``--steps`` steps a call. ``none`` is the scan with
the update left out (what making the inputs costs). Reported per form:
milliseconds a step less ``none``'s, the share of the unpadded bytes' time
(``delta_state_roofline``'s arithmetic), the compiled program's temporaries
and how far the state and the read-outs lie from ``four_pass``'s.

Usage (the chip): python hack/delta_microbench.py [--forms a,b]
Here (compiles every form for a described v5e, runs nothing):
    JAX_PLATFORMS=cpu python hack/delta_microbench.py --compile-only
Writes chiprun_out/delta_microbench.json and prints a table.
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

# layers, slots, heads, key width, value width: the cell's resolved engine
SHAPE = (9, 32, 30, 96, 192)
HBM_BYTES_S = 819e9
BLOCKS = (5, 10, 15, 30)


def pack(ssm, P: int):
    """The leaf [Ld, B, H, dk, dv] as ``decoder._delta_packed`` lays a row."""
    import jax

    from ollama_operator_tpu.models import decoder
    return jax.vmap(lambda S: decoder._delta_packed(S, P))(ssm)


def unpack(ssm, P: int):
    import jax

    from ollama_operator_tpu.models import decoder
    return jax.vmap(lambda S: decoder._delta_unpacked(S, P))(ssm)


def forms():
    import jax.numpy as jnp
    from jax import lax

    from ollama_operator_tpu.models import decoder
    from ollama_operator_tpu.ops.pallas import delta as D

    def sliced(rule):
        def update(ssm, row, q, k, v, a, beta, live):
            S0 = lax.dynamic_index_in_dim(ssm, row, 0, keepdims=False)
            o, S1 = rule(S0, q, k, v, a, beta)
            S1 = jnp.where((live > 0)[:, None, None, None], S1, S0)
            return o, lax.dynamic_update_index_in_dim(ssm, S1, row, 0)
        return update

    def four_pass(S0, q, k, v, a, beta):
        o, S1 = decoder._delta_rule(None, S0, q[:, None], k[:, None],
                                    v[:, None], jnp.log(a)[:, None],
                                    beta[:, None])
        return o[:, 0], S1

    def two_read(S0, q, k, v, a, beta):
        both = jnp.einsum("bhjd,bhdv->bhjv", jnp.stack([k, q], 2), S0,
                          precision=lax.Precision.HIGHEST)
        u = beta[..., None] * (v - a[..., None] * both[:, :, 0])
        S1 = a[..., None, None] * S0 + k[..., None] * u[:, :, None, :]
        o = a[..., None] * both[:, :, 1] + (k * q).sum(-1, keepdims=True) * u
        return o, S1

    out = {"none": lambda ssm, row, q, k, v, a, beta, live: (v, ssm),
           "four_pass": sliced(four_pass), "two_read": sliced(two_read)}
    for hb in BLOCKS:
        out[f"kernel@{hb}"] = functools.partial(D.delta_update, hb=hb)
    # the same kernel: the leaf's shape says which layout it is given
    out["packed@30"], out["packed@10"] = out["kernel@30"], out["kernel@10"]
    return out


def program(form, steps: int):
    """``steps`` decode steps' updates of every layer in one program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(ssm, q, k, v, a, beta, live):
        Ld = ssm.shape[0]

        def step(s, carry):
            def layer(c, xs):
                ssm, acc = c
                i, q, k, v, a, beta = xs
                o, ssm = form(ssm, i, q, k, v + 1e-3 * s, a, beta, live)
                # the kernel reads zeros out of a slot with nothing real
                return (ssm, acc + o * live[:, None, None]), None
            return lax.scan(layer, carry, (jnp.arange(Ld, dtype=jnp.int32),
                                           q, k, v, a, beta))[0]
        return lax.fori_loop(0, steps, step, (ssm, jnp.zeros_like(v[0])))
    return jax.jit(run, donate_argnums=(0,))


def arg_shapes(packed: bool, sharding=None):
    import jax
    import jax.numpy as jnp
    Ld, B, H, dk, dv = SHAPE
    sds = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=sharding)
    leaf = (Ld, B, H // 2, dk, 2 * dv) if packed else SHAPE
    return (sds(leaf), sds((Ld, B, H, dk)), sds((Ld, B, H, dk)),
            sds((Ld, B, H, dv)), sds((Ld, B, H)), sds((Ld, B, H)),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sharding))


def main() -> int:
    ap = argparse.ArgumentParser()
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

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        for name, form in table.items():
            try:
                c = program(form, args.steps).lower(
                    *arg_shapes(name.startswith("packed"), one)).compile()
                m = c.memory_analysis()
                print(f"{name:10s} temporaries "
                      f"{m.temp_size_in_bytes / 2**20:8.1f} MiB, the leaf "
                      f"{m.alias_size_in_bytes / 2**20:.0f} MiB", flush=True)
            except Exception as e:  # noqa: BLE001 — what the compiler refuses
                print(f"{name:10s} {type(e).__name__}: {str(e)[:400]}",
                      flush=True)
        return 0

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        print("needs the TPU", file=sys.stderr)
        return 1

    Ld, B, H, dk, dv = SHAPE
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (Ld, B, H, dk)) * dk ** -1.0
    k = jax.random.normal(ks[1], (Ld, B, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (Ld, B, H, dv))
    a = jnp.exp(-jax.nn.softplus(jax.random.normal(ks[3], (Ld, B, H))))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (Ld, B, H)))
    # every fourth slot holds nothing real: it keeps its bits
    live = jnp.asarray(np.arange(B) % 4 != 3, jnp.int32)

    def fresh(packed):
        ssm = jax.jit(lambda key: jax.random.normal(key, SHAPE) * 0.3)(ks[5])
        return jax.jit(lambda x: pack(x, 2))(ssm) if packed else ssm

    state_bytes = 2 * 4 * Ld * B * H * dk * dv
    results, ref = [], None
    for name, form in table.items():
        packed = name.startswith("packed")
        row = {"form": name}
        try:
            c = program(form, args.steps).lower(
                fresh(packed), q, k, v, a, beta, live).compile()
            row["temporaries_mib"] = round(
                c.memory_analysis().temp_size_in_bytes / 2**20, 1)
            ssm, acc = c(fresh(packed), q, k, v, a, beta, live)
            jax.block_until_ready(acc)
            got = (np.asarray(unpack(ssm, 2) if packed else ssm),
                   np.asarray(acc))
            if name == "four_pass":
                ref = got
            elif ref is not None and name != "none":
                row["state_max_diff"] = float(np.abs(got[0] - ref[0]).max())
                row["readout_max_diff"] = float(np.abs(got[1] - ref[1]).max())
                dead = np.asarray(live) == 0
                row["dead_slots_bit_equal"] = bool(np.array_equal(
                    got[0][:, dead], ref[0][:, dead]))
            del got
            best = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                ssm, acc = c(ssm, q, k, v, a, beta, live)
                jax.block_until_ready(acc)
                best = min(best, time.perf_counter() - t0)
            del ssm
            row["ms_per_step"] = round(best / args.steps * 1e3, 4)
        except Exception as e:  # noqa: BLE001 — a form the chip refuses
            row["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        results.append(row)
        print(json.dumps(row), flush=True)
    base = next((r.get("ms_per_step") for r in results
                 if r["form"] == "none"), 0.0) or 0.0
    for r in results:
        if "ms_per_step" in r and r["form"] != "none":
            r["update_ms_per_step"] = round(r["ms_per_step"] - base, 4)
            r["state_roofline_pct"] = round(
                100 * state_bytes / HBM_BYTES_S
                / (r["update_ms_per_step"] * 1e-3), 1)

    print(f"\n{'form':10s} {'update ms/step':>14s} {'roofline %':>10s} "
          f"{'temp MiB':>9s} state / read-out from four_pass")
    for r in results:
        print(f"{r['form']:10s} "
              f"{r.get('update_ms_per_step', float('nan')):14.3f} "
              f"{r.get('state_roofline_pct', float('nan')):10.1f} "
              f"{r.get('temporaries_mib', float('nan')):9.1f} "
              f"{r.get('state_max_diff', '')} {r.get('readout_max_diff', '')}"
              f" {r.get('error', '')}")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/delta_microbench.json", "w") as f:
        json.dump({"device": dev.device_kind, "steps": args.steps,
                   "shape": SHAPE, "rows": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
