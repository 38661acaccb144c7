"""One check of the yardstick on the chip, at the shape that matters (no
configuration, no preset, no cell): the WHOLE of ``server_child.probe()`` on
the program's expert path at hidden 4096, 6 layers, 128 experts of width 512,
4 a token, one shared expert, 256 positions and one decode step, served as the
zero-config server resolves such a model (bfloat16 weights made from the seed
as the harness makes them, an int8 cache, contiguous: ``resolve_paged_default``
turns paging off for experts), both of its paths (the served kernels and
``kernels="xla"``), against the fixture's reference
(``tests/fixtures/routed.reference.py``) at the highest matmul precision.

Over the seeds it counts how many the probe passes, reads each path's logits
under its own sets and its largest shortfall, and counts what the two rules
this one replaced would have said of the same logits: each path against
``forward`` (the reference under ITS own sets) at ``LOGITS_TOL``, and served
against plain wherever the two chose alike at the compared position alone. The
control (the reference with every activation through float8, under its own
sets) has to fail on each of its seeds.

    chiprun -- python3 benchmark/checks/routed_at_width.py [--seeds 32]
    JAX_PLATFORMS=cpu python3 benchmark/checks/routed_at_width.py --toy

Writes ``chiprun_out/routed_at_width.json``; the last line of stdout is the
table's summary.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

WIDTH = dict(hidden_size=4096, num_hidden_layers=6, num_attention_heads=32,
             num_key_value_heads=8, head_dim=128, moe_intermediate_size=512,
             n_routed_experts=128, num_experts_per_tok=4,
             shared_intermediate_size=512, vocab_size=32768, max_seq_len=4096,
             max_position_embeddings=4096)
TOY = dict(hidden_size=256, num_hidden_layers=3, num_attention_heads=4,
           num_key_value_heads=2, head_dim=64, moe_intermediate_size=64,
           n_routed_experts=32, num_experts_per_tok=4,
           shared_intermediate_size=64, vocab_size=1024, max_seq_len=512,
           max_position_embeddings=512)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=32)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--seconds-limit", type=float, default=1800.0,
                    help="start no further seed once this long has passed")
    ap.add_argument("--toy", action="store_true",
                    help="small sizes, any backend: a rehearsal of the script")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import server_child as sc
    from benchmark import work
    from benchmark.choices import SITE
    from ollama_operator_tpu.models.config import get_config
    from ollama_operator_tpu.runtime import compile_cache

    backend = jax.default_backend()
    sc.need(args.toy or backend == "tpu",
            f"this check reads the chip; JAX initialised {backend!r}")
    if backend == "tpu":
        compile_cache.enable()
    conf = work.load_conf(os.path.join(BENCH, "tests", "fixtures",
                                       "routed.json"))
    conf.update(TOY if args.toy else WIDTH)
    cfg = dataclasses.replace(
        get_config(conf["preset"]),
        **{ours: conf[theirs] for ours, theirs in conf["holds"]})
    if backend != "tpu":
        cfg = dataclasses.replace(cfg, kernels="interpret",
                                  mm_kernels="interpret")
    weights, ecfg = sc.resolve(cfg, backend, False)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[weights]
    ref = sc.load_reference(conf)
    own = jax.jit(lambda p, t: ref.forward(p, conf, t)[-2:])
    control = jax.jit(lambda p, t: ref.forward_rounded(
        p, conf, t, jnp.float8_e4m3fn))

    lines, paths = [], {}
    sc.say = lambda **rec: lines.append(rec)
    judge = sc.judge

    def judge_and_keep(label, logits2, chosen, *rest):
        paths[label] = (np.stack(logits2), chosen[SITE])
        return judge(label, logits2, chosen, *rest)
    sc.judge = judge_and_keep

    def rel(a, b):
        return [float(x) for x in np.abs(a - b).max(1) / np.abs(b).max(1)]

    rows, t0 = [], time.perf_counter()
    for n in range(args.seeds):
        if time.perf_counter() - t0 > args.seconds_limit:
            break
        seed = 2_000_000_000 + n
        params = sc.make_weights(cfg, seed, 0, dtype)
        del lines[:]
        paths.clear()
        t1 = time.perf_counter()
        try:
            ok = sc.probe(cfg, ecfg, params, conf, seed)
        except sc.ChildFailure as e:
            ok = False
            lines.append(dict(phase="failed", error=str(e)))
        row = dict(seed=seed, ok=ok, seconds=time.perf_counter() - t1,
                   lines=list(lines))
        row["judged"] = {r["compared"]: r["rel"] for r in lines
                         if r.get("phase") == "logits" and "rel" in r}
        row["skipped"] = [r["compared"] for r in lines if "skipped" in r]
        row["shortfall"] = {r["compared"]: r["shortfall_max"] for r in lines
                            if r.get("phase") == "choices"}
        row["not_own"] = {r["compared"]: r["positions_not_the_references_own"]
                          for r in lines if r.get("phase") == "choices"}
        if len(paths) == 2:
            T = paths["served"][1].shape[1] - 1
            # the probe's own draw of the prompt and the decode step's token
            rng = np.random.default_rng([seed, 0x9e0be])
            prompt = rng.integers(3, cfg.vocab_size, (T,))
            tokens = jnp.asarray(np.append(
                prompt, int(rng.integers(3, cfg.vocab_size))), jnp.int32)
            own2 = np.asarray(own(params, tokens), np.float32)
            (lk, sk), (lx, sx) = paths["served"], paths["program plain"]
            # what PR 23's rule reads: a path against the reference under
            # the reference's own sets
            row["old_rel"] = {"served": rel(lk, own2),
                              "program plain": rel(lx, own2)}
            # what the rule this one replaced would have compared: served
            # against plain where the two chose alike AT the compared
            # position, whatever the earlier ones chose
            alike_at = [bool((sk[:, at] == sx[:, at]).all())
                        for at in (T - 1, T)]
            alike_up_to = [bool((sk[:, :at + 1] == sx[:, :at + 1]).all())
                           for at in (T - 1, T)]
            row.update(between_rel=rel(lk, lx), alike_at=alike_at,
                       alike_up_to=alike_up_to,
                       sets_differ=int((sk != sx).any(-1).sum()))
        if n < args.control_seeds and len(paths) == 2:
            c_logits, c_sets = control(params, tokens)
            del lines[:]
            row["control_ok"] = judge(
                "control", np.asarray(c_logits, np.float32)[-2:],
                {SITE: np.asarray(c_sets[SITE])}, ref, params, conf, tokens)
            row["control_rel"] = [r["rel"] for r in lines
                                  if r["phase"] == "logits"]
            row["control_shortfall"] = [
                r for r in lines if r["phase"] == "choices"
            ][0]["shortfall_max"]
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items() if k != "lines"}),
              flush=True)
        # the probe's engines hold the weights through closures of their own
        del params
        gc.collect()

    whole = [r for r in rows if "old_rel" in r]
    controls = [r for r in rows if "control_ok" in r]
    vs_ref = [v for r in rows for k, v in r["judged"].items()
              if k.endswith("vs reference")]
    between = [v for r in rows for k, v in r["judged"].items()
               if k.endswith("served vs program plain")]
    old_rule = [x for r in whole
                for x, alike in zip(r["between_rel"], r["alike_at"]) if alike]
    summary = dict(
        device=jax.devices()[0].device_kind, backend=backend, toy=args.toy,
        sizes={k: conf[k] for k in WIDTH}, positions=sc.PROBE_TOKENS + 1,
        weights=weights, kv=(ecfg.cache_dtype if isinstance(
            ecfg.cache_dtype, str) else jnp.dtype(ecfg.cache_dtype).name),
        paged=bool(ecfg.paged), slots=ecfg.max_slots,
        seeds=len(rows), probe_passes=sum(r["ok"] for r in rows),
        sets_brought_out_of_both_paths=len(whole),
        path_vs_reference_rel_max=max(vs_ref, default=None),
        path_vs_reference_rel_min=min(vs_ref, default=None),
        shortfall_max=max((v for r in rows for v in r["shortfall"].values()),
                          default=None),
        positions_not_own_max=max(
            (v for r in rows for v in r["not_own"].values()), default=None),
        served_vs_plain_compared=len(between),
        served_vs_plain_skipped=sum(len(r["skipped"]) for r in rows),
        served_vs_plain_rel_max=max(between, default=None),
        old_reference_rule_fails=sum(
            max(max(v) for v in r["old_rel"].values()) > sc.LOGITS_TOL
            for r in whole),
        old_reference_rule_rel_max=max(
            (max(max(v) for v in r["old_rel"].values()) for r in whole),
            default=None),
        old_between_rule_compared=len(old_rule),
        old_between_rule_over_tol=sum(x > sc.LOGITS_TOL for x in old_rule),
        old_between_rule_rel_max=max(old_rule, default=None),
        control_seeds=len(controls),
        control_passes=sum(r["control_ok"] for r in controls),
        control_rel_min=min((min(r["control_rel"]) for r in controls),
                            default=None),
        control_shortfall_min=min((r["control_shortfall"] for r in controls),
                                  default=None),
        seconds=time.perf_counter() - t0)
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "routed_at_width.json"), "w") as f:
        json.dump(dict(summary=summary, rows=rows), f, indent=1)
    print(json.dumps(summary))
    good = (summary["probe_passes"] == len(rows) == len(whole)
            and not summary["control_passes"])
    return 0 if good else 1


if __name__ == "__main__":
    sys.exit(main())
