"""int8 weight-only matmul: the three forms by row count, on the chip.

For the matmul shapes of the benchmark's two configurations (starcoder2-3b,
phi-2) and N rows in {1 ... 1024}: the time of one matmul through

  grouped   ops/quant.qmm_grouped   (XLA; the N <= 16 form)
  dense     ops/quant.qmm_dense     (XLA; dequantize the weight, one dot)
  kernel    ops/pallas/quant.qmm_pallas (fused: int8 tiles dequantized in
            VMEM), reading its layer of the stack in place, as the decoder
            runs it (models/decoder.py _scan_layers)
  kernel_sliced  the same kernel on the scan's slice of the stack: the copy
            a pallas_call's operand costs

as the decoder runs it: inside a ``lax.scan`` over L stacked layers, each
step reading its own weight, so nothing is read twice from a warm buffer
and a slice's cost is in the number. Reported per form: microseconds a
matmul and int8 GB/s (code bytes + f32 scale bytes over the time; 819 GB/s
is the chip's peak). ``--tiles`` also sweeps the kernel's tile sizes.

Usage (needs the chip): python hack/qmm_microbench.py [--tiles] [--quick]
Writes chiprun_out/qmm_microbench.json and prints a table.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = [
    ("starcoder2.wqkv", 3072, 3584), ("starcoder2.wo", 3072, 3072),
    ("starcoder2.w_up", 3072, 12288), ("starcoder2.w_down", 12288, 3072),
    ("phi-2.wqkv", 2560, 7680), ("phi-2.wo", 2560, 2560),
    ("phi-2.w_up", 2560, 10240), ("phi-2.w_down", 10240, 2560),
    ("phi-2.lm_head", 2560, 51200),
]
ROWS = (1, 8, 16, 32, 64, 128, 256, 1024)
STACK_BYTES = 160 << 20       # codes a stack holds: no layer is read warm
PASS_BYTES = 16 << 30         # codes one timed call reads


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from ollama_operator_tpu.ops import quant as Q
    from ollama_operator_tpu.ops.pallas import quant as PQ

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        print("needs the TPU", file=sys.stderr)
        return 1

    def stack(K, O, L, seed):
        def make(key):
            kq, ks = jax.random.split(key)
            q = jax.random.randint(kq, (L, K, O), -127, 128, jnp.int8)
            s = jax.random.uniform(ks, (L, K // Q.GROUP, O), jnp.float32,
                                   1e-3, 2e-3)
            return {"q": q, "s": s}
        return jax.jit(make)(jax.random.PRNGKey(seed))

    def scanned(form, reps):
        """``reps`` passes over the stack inside ONE program: a call costs
        ~0.6 ms before the device starts (measured: the same scan read 183
        us a layer on the host's clock and 102 in the trace), which a
        handful of 50 us matmuls would not outweigh."""
        def run(x, w):
            L, _K, O = w["q"].shape

            def one_pass(_, acc):
                # x hangs on the carry, so no pass can be hoisted or merged
                xr = x + (acc[:1, :1] * 0).astype(x.dtype)

                def body(acc, l):
                    return acc + form(xr, w, l), None
                return lax.scan(body, acc, jnp.arange(L, dtype=jnp.int32))[0]
            return lax.fori_loop(0, reps, one_pass,
                                 jnp.zeros((x.shape[0], O), jnp.float32))
        return jax.jit(run)

    def timed(form, x, w, L, nbytes):
        # 16 GB of codes a call: 21 ms at the chip's peak, 0.2 s at a tenth
        reps = max(1, min(200, PASS_BYTES // (L * nbytes)))
        fn = scanned(form, reps)
        out = fn(x, w)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, w))
            best = min(best, time.perf_counter() - t0)
        return best / (L * reps), out / reps

    def layer(w, l):
        return {k: Q.layer_of(v, l) for k, v in w.items()}

    # every form reads layer l of the stack w: the XLA forms and
    # kernel_sliced through a slice (fused into an XLA consumer's read,
    # copied for a pallas_call), kernel in place
    forms = {
        "grouped": lambda x, w, l: Q.qmm_grouped(x, layer(w, l), jnp.float32),
        "dense": lambda x, w, l: Q.qmm_dense(x, layer(w, l), jnp.float32),
        "kernel_sliced": lambda x, w, l: PQ.qmm_pallas(x, **layer(w, l)),
        "kernel": lambda x, w, l: PQ.qmm_pallas(x, w["q"], w["s"], layer=l),
    }
    shapes = SHAPES[2:4] + SHAPES[8:] if args.quick else SHAPES
    rows_set = (16, 64, 1024) if args.quick else ROWS
    results = []
    for name, K, O in shapes:
        L = max(2, min(30, STACK_BYTES // (K * O)))
        w = stack(K, O, L, 1)
        nbytes = K * O + (K // Q.GROUP) * O * 4
        for N in rows_set:
            x = jax.random.normal(jax.random.PRNGKey(N), (N, K), jnp.bfloat16)
            row = {"shape": name, "K": K, "O": O, "N": N, "layers": L}
            ref = None
            for fname, form in forms.items():
                # the grouped form's partial: N x K/32 x O f32 per layer
                if fname == "grouped" and N * (K // 32) * O * 4 > (1 << 30):
                    continue
                try:
                    t, out = timed(form, x, w, L, nbytes)
                except Exception as e:  # noqa: BLE001 — a form that cannot
                    row[fname + "_error"] = f"{type(e).__name__}: {str(e)[:200]}"
                    continue
                row[fname + "_us"] = round(t * 1e6, 1)
                row[fname + "_gbs"] = round(nbytes / t / 1e9, 1)
                if ref is None:
                    ref = out
                else:
                    err = float(jnp.abs(out - ref).max()
                                / jnp.abs(ref).max())
                    row[fname + "_rel_err"] = round(err, 5)
            results.append(row)
            print(json.dumps(row), flush=True)

    tiles = []
    if args.tiles:
        sweep = [(512, 512, 256), (512, 512, 512), (512, 1024, 256),
                 (1024, 512, 256), (1024, 1024, 256), (1024, 1024, 1024),
                 (1024, 2048, 256), (2048, 1024, 256), (512, 2048, 256)]
        keep = PQ._tiles, PQ._CHUNK
        for name, K, O in (SHAPES[2], SHAPES[3], SHAPES[4], SHAPES[8]):
            L = max(2, min(30, STACK_BYTES // (K * O)))
            w = stack(K, O, L, 1)
            nbytes = K * O + (K // Q.GROUP) * O * 4
            for N in (32, 64, 256):
                x = jax.random.normal(jax.random.PRNGKey(N), (N, K),
                                      jnp.bfloat16)
                for bk, bo, ck in sweep:
                    if K % bk or O % bo:
                        continue
                    PQ._tiles = lambda *_a, _t=(bk, bo): _t
                    PQ._CHUNK = ck
                    row = {"shape": name, "N": N, "bk": bk, "bo": bo,
                           "chunk": ck}
                    try:
                        t, _ = timed(forms["kernel"], x, w, L, nbytes)
                        row["us"] = round(t * 1e6, 1)
                        row["gbs"] = round(nbytes / t / 1e9, 1)
                    except Exception as e:  # noqa: BLE001 — VMEM, tiling
                        row["error"] = f"{type(e).__name__}: {str(e)[:160]}"
                    tiles.append(row)
                    print(json.dumps(row), flush=True)
        PQ._tiles, PQ._CHUNK = keep

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/qmm_microbench.json", "w") as f:
        json.dump({"device": dev.device_kind, "forms": results,
                   "tiles": tiles}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
