"""The probe of a cell's configuration alone, over many seeds, on the chip: the
WHOLE of ``server_child.probe()`` as the cell's child runs it (the preset held
to the configuration's file, the zero-config resolution, weights made from the
seed as the harness makes them, both paths, each judged against the
configuration's reference under its own sets), without the server and the
window. It reads each path's logits and its largest shortfall, seed by seed,
and, for a reference that has ``forward_rounded``, runs the control on the
first seeds: the reference with every activation through float8, the step
below bfloat16, under its own sets, which has to FAIL.

    chiprun -- python3 benchmark/checks/probe_at_width.py \\
        --config granite-4.0-h-small [--seeds 24] [--control-seeds 2]

Writes ``chiprun_out/probe_at_width.<config>.json``; the last line of stdout
is the summary. ``--rehearse`` runs the toy on any backend.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=24)
    ap.add_argument("--first-seed", type=int, default=2_100_000_000)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--seconds-limit", type=float, default=2400.0,
                    help="start no further seed once this long has passed")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import server_child as sc
    from benchmark.choices import SITE
    from ollama_operator_tpu.runtime import compile_cache
    from ollama_operator_tpu.server.app import device_memory

    backend = jax.default_backend()
    sc.need(args.rehearse or backend == "tpu",
            f"this check reads the chip; JAX initialised {backend!r}")
    if backend == "tpu":
        compile_cache.enable()
    conf = sc.load_conf(os.path.join(BENCH, "configs", args.config + ".json"),
                        args.rehearse)
    cfg = sc.model_config(conf, args.rehearse)
    weights, ecfg = sc.resolve(cfg, backend, False)
    bits = {"int8": 8, "int4": 4}.get(weights, 0)
    wdtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    ref = sc.load_reference(conf)
    control = (jax.jit(lambda p, t: ref.forward_rounded(
        p, conf, t, jnp.float8_e4m3fn))
        if hasattr(ref, "forward_rounded") else None)

    lines = []
    sc.say = lambda **rec: lines.append(rec)
    rows, t0 = [], time.perf_counter()
    for n in range(args.seeds):
        if time.perf_counter() - t0 > args.seconds_limit:
            break
        seed = args.first_seed + n
        params = sc.make_weights(cfg, seed, bits, wdtype,
                                 tuple(conf.get("omit_leaves", ())))
        del lines[:]
        t1 = time.perf_counter()
        try:
            ok = sc.probe(cfg, ecfg, params, conf, seed)
        except sc.ChildFailure as e:
            ok = False
            lines.append(dict(phase="failed", error=str(e)))
        row = dict(seed=seed, ok=ok, seconds=time.perf_counter() - t1,
                   compared={k: v["value"] for k, v in sc.COMPARED.items()},
                   skipped=[r["compared"] for r in lines if "skipped" in r],
                   not_own={r["compared"]:
                            r["positions_not_the_references_own"]
                            for r in lines if r.get("phase") == "choices"},
                   failed=[r["error"] for r in lines
                           if r.get("phase") == "failed"],
                   peak_bytes=max(d["peak_bytes_in_use"]
                                  for d in device_memory()))
        if control is not None and n < args.control_seeds and ok:
            T = min(sc.PROBE_TOKENS, ecfg.max_seq_len // 2)
            rng = np.random.default_rng([seed, 0x9e0be])
            prompt = rng.integers(3, cfg.vocab_size, (T,))
            tokens = np.append(prompt, int(rng.integers(3, cfg.vocab_size)))
            c_logits, c_sets = control(params, jnp.asarray(tokens, jnp.int32))
            del lines[:]
            row["control_ok"] = sc.judge(
                "control", np.asarray(c_logits, np.float32)[-2:],
                {SITE: np.asarray(c_sets[SITE])}, ref, params, conf, tokens)
            row["control"] = {k: v["value"] for k, v in sc.COMPARED.items()
                              if "control" in k}
        rows.append(row)
        print(json.dumps(row), flush=True)
        # the probe's engines hold the weights through closures of their own
        del params
        gc.collect()

    def readings(word):
        """Every seed's numbers of one kind ("shortfall", or the logits'
        "vs_reference"), the control's left out."""
        return [v for r in rows for k, v in r["compared"].items()
                if word in k and "control" not in k
                and ("shortfall" in k) == (word == "shortfall")]

    controls = [r for r in rows if "control_ok" in r]
    summary = dict(
        config=args.config, device=jax.devices()[0].device_kind,
        backend=backend, rehearse=args.rehearse, weights=weights,
        slots=ecfg.max_slots, paged=bool(ecfg.paged), seeds=len(rows),
        probe_passes=sum(r["ok"] for r in rows),
        logits_vs_reference=[min(readings("vs_reference"), default=None),
                             max(readings("vs_reference"), default=None)],
        shortfall=[min(readings("shortfall"), default=None),
                   max(readings("shortfall"), default=None)],
        served_vs_plain_skipped=sum(len(r["skipped"]) for r in rows),
        positions_not_own_max=max(
            (v for r in rows for v in r["not_own"].values()), default=None),
        control_seeds=len(controls),
        control_passes=sum(r["control_ok"] for r in controls),
        control_readings=[r["control"] for r in controls],
        peak_bytes=max((r["peak_bytes"] for r in rows), default=None),
        seconds_a_seed=[min((r["seconds"] for r in rows), default=None),
                        max((r["seconds"] for r in rows), default=None)],
        seconds=time.perf_counter() - t0)
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"probe_at_width.{args.config}.json"),
              "w") as f:
        json.dump(dict(summary=summary, rows=rows), f, indent=1)
    print(json.dumps(summary))
    good = (summary["probe_passes"] == len(rows) > 0
            and not summary["control_passes"])
    return 0 if good else 1


if __name__ == "__main__":
    sys.exit(main())
