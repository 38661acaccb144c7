"""Latent attention's served path against its reference PAST ``index_topk``
positions, on the chip: the probe (``server_child.probe``) compares 256
positions and one decode step, where the indexer chooses nothing. This check
admits a prompt of ``--prompt`` tokens through the engine's own pieces (its
admission program for the first ``--piece`` tokens, its extend programs for
the rest, the slot parked in between as the scheduler parks it), then runs
``--steps`` decode steps through ``forward_with_cache`` over the engine's
cache trees, one slot active among the resolved slots. The path's own sets of
BOTH sites (the router's, tapped by ``choices.py``; the indexer's, by
``index_choices.py``; every piece and every step) go to the reference's
``forward_chosen`` over the whole sequence, which holds the decode steps'
logits at ``LOGITS_TOL`` and every position's shortfall, of both sites, at
``CHOICE_TOL``. The control (the reference with every activation through
float8, under its own sets) has to FAIL by one of the two.

    chiprun -- python3 benchmark/checks/sparse_at_width.py \\
        --config glm-5 [--prompt 3072] [--steps 32] [--seeds 2]

Writes ``chiprun_out/sparse_at_width.<config>.json``; the last line of stdout
is the summary. ``--rehearse`` runs the toy on any backend."""

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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompt", type=int, default=3072)
    ap.add_argument("--piece", type=int, default=256)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_300_000_000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from benchmark import index_choices as ic
    from benchmark import server_child as sc
    from benchmark.choices import SITE, record_choices
    from ollama_operator_tpu.models import decoder
    from ollama_operator_tpu.runtime import compile_cache
    from ollama_operator_tpu.runtime.engine import Engine
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
    if args.rehearse:
        ecfg = dataclasses.replace(ecfg, max_slots=4, cache_dtype=jnp.int8,
                                   min_prefill_bucket=16)
    bits = {"int8": 8, "int4": 4}.get(weights, 0)
    wdtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    ref = sc.load_reference(conf)
    sc.need(hasattr(ref, "forward_chosen") and hasattr(ref, "forward_rounded")
            and getattr(ref, "INDEX_SITE", None) == ic.SITE,
            "this check is for a reference whose indexer chooses")
    T, N, P, topk = args.prompt, args.steps, args.piece, conf["index_topk"]
    sc.need(T % P == 0 and T > topk,
            "the prompt is whole pieces and passes index_topk")
    sc.need(T + N < min(ecfg.max_seq_len, cfg.max_seq_len),
            "prompt + steps pass the served context")
    L = conf["num_hidden_layers"]
    chosen_fn = jax.jit(lambda p, t, c: ref.forward_chosen(p, conf, t, c))
    control = jax.jit(lambda p, t: ref.forward_rounded(
        p, conf, t, jnp.float8_e4m3fn))

    def against_reference(params, tokens, logits, routes, kept):
        """(largest |difference| of ``logits`` [N, V] from the reference's
        last N positions under the given sets of both sites, as a share of
        its largest |logit|; the largest shortfall)."""
        want, short = chosen_fn(
            params, jnp.asarray(tokens, jnp.int32),
            {SITE: jnp.asarray(routes, jnp.int32),
             ic.SITE: jnp.asarray(kept, jnp.int32)})
        want = np.asarray(want[-N:], np.float32)
        return (float(np.abs(logits - want).max() / np.abs(want).max()),
                float(np.asarray(short).max()))

    def index_sets(masks, first, n, row=0):
        """[L, n, topk] of positions first .. first + n - 1 from the masks
        one run handed out: none where nothing was chosen, else for each
        layer in turn its blocks of queries in turn."""
        if not masks:
            return np.broadcast_to(ic.keep_all(first, n, topk), (L, n, topk))
        sc.need(len(masks) % L == 0, f"{ic.SITE} handed out {len(masks)} "
                f"masks for {L} layers")
        per = len(masks) // L
        return np.stack([ic.sets_of(np.concatenate(
            [m[row] for m in masks[i * per:(i + 1) * per]])[:n], topk)
            for i in range(L)])

    rows, t0 = [], time.perf_counter()
    for n in range(args.seeds):
        seed = args.first_seed + n
        params = sc.make_weights(cfg, seed, bits, wdtype,
                                 tuple(conf.get("omit_leaves", ())))
        rng = np.random.default_rng([seed, 0x5ba5])
        tokens = rng.integers(3, cfg.vocab_size, (T + N,)).astype(np.int32)
        routes, kept = [], []
        with record_choices() as chosen, ic.record_index() as index:
            # the engine is built inside the block, so its programs are
            # traced with the taps in: nothing else ever runs them
            eng = Engine(cfg, params, mesh=None, ecfg=ecfg)
            for start in range(0, T, P):
                if start:
                    eng.release(0, park=True)
                    eng.extend(0, tokens[:start + P], start)
                else:
                    eng.admit(0, tokens[:P])
                calls = chosen.calls()
                sc.need(len(calls) == 1, f"{SITE} handed out {len(calls)} "
                        f"calls in the piece at {start}")
                routes.append(calls[0][:, :P])
                kept.append(index_sets(index.masks(), start, P))
            B = eng.n_slots
            active = jnp.zeros((B,), jnp.int32).at[0].set(1)

            def served(p, kc, vc, steps, lengths):
                def step(carry, tok):
                    kc, vc, lengths = carry
                    lg, kc, vc = decoder.forward_with_cache(
                        p, eng.cfg, jnp.full((B, 1), tok, jnp.int32), kc, vc,
                        lengths, attn_len=eng._attn_bucket(N),
                        n_valid=active)
                    return (kc, vc, lengths + active), lg[0, 0]
                _, dec = lax.scan(step, (kc, vc, lengths), steps)
                return dec

            # step j feeds tokens[T + j - 1]... the prompt's last piece
            # sampled a token the check does not use: the steps are forced,
            # so position T + j holds tokens[T + j]
            logits = np.asarray(jax.jit(served, donate_argnums=(1, 2))(
                eng.params, eng.k_cache, eng.v_cache,
                jnp.asarray(tokens[T:]), eng.lengths), np.float32)
            calls = chosen.calls()
            masks = index.masks()
        sc.need(len(calls) == 1, f"{SITE} handed out {len(calls)} calls in "
                "the decode steps")
        Lr = routes[0].shape[0]
        routes.append(calls[0][:, 0].reshape(N, Lr, -1).transpose(1, 0, 2))
        sc.need(len(masks) == N * L, f"{ic.SITE} handed out {len(masks)} "
                f"masks in {N} steps of {L} layers")
        kept.append(np.stack(
            [np.concatenate([ic.sets_of(masks[j * L + i][0], topk)
                             for j in range(N)]) for i in range(L)]))
        routes = np.concatenate(routes, axis=1)
        kept = np.concatenate(kept, axis=1)
        peak = max(d["peak_bytes_in_use"] for d in device_memory())
        del eng
        gc.collect()
        rel, short = against_reference(params, tokens, logits, routes, kept)
        ok = bool(np.isfinite(logits).all() and rel <= sc.LOGITS_TOL
                  and short <= sc.CHOICE_TOL)
        c_logits, c_sets = control(params, jnp.asarray(tokens))
        c_rel, c_short = against_reference(
            params, tokens, np.asarray(c_logits[-N:], np.float32),
            np.asarray(c_sets[SITE]), np.asarray(c_sets[ic.SITE]))
        c_ok = c_rel <= sc.LOGITS_TOL and c_short <= sc.CHOICE_TOL
        rows.append(dict(
            seed=seed, ok=ok, logits_rel=rel, shortfall=short,
            control_ok=bool(c_ok), control_logits_rel=c_rel,
            control_shortfall=c_short, peak_bytes=peak, positions=T + N,
            positions_that_chose=int((kept[0, :, -1] >= 0).sum())))
        print(json.dumps(rows[-1]), flush=True)
        del params
        gc.collect()

    summary = dict(
        config=args.config, device=jax.devices()[0].device_kind,
        backend=backend, rehearse=args.rehearse, prompt=T, piece=P, steps=N,
        index_topk=topk, slots=ecfg.max_slots, seeds=len(rows),
        passes=sum(r["ok"] for r in rows),
        control_passes=sum(r["control_ok"] for r in rows),
        logits_tol=sc.LOGITS_TOL, choice_tol=sc.CHOICE_TOL,
        logits_rel=[min(r["logits_rel"] for r in rows),
                    max(r["logits_rel"] for r in rows)],
        shortfall=[min(r["shortfall"] for r in rows),
                   max(r["shortfall"] for r in rows)],
        control_logits_rel=[r["control_logits_rel"] for r in rows],
        control_shortfall=[r["control_shortfall"] for r in rows],
        peak_bytes=max(r["peak_bytes"] for r in rows),
        seconds=time.perf_counter() - t0)
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"sparse_at_width.{args.config}.json"),
              "w") as f:
        json.dump(dict(summary=summary, rows=rows), f, indent=1)
    print(json.dumps(summary))
    return 0 if summary["passes"] == len(rows) > 0 == summary[
        "control_passes"] else 1


if __name__ == "__main__":
    sys.exit(main())
