"""Window attention's served path against its reference PAST the window, on
the chip: the benchmark's harness serves every cell at 4,096 positions
(``server_child.SERVER_DEFAULT_CTX``), so in no cell a window layer of 4,096
ever drops a key, and ``long_at_width.py`` cannot state a context. This check
builds the engine at ``--ctx`` positions (8,192: rings of 4,096 beside full
rows of 8,192), admits a prompt of ``--prompt`` tokens (4,096 + 512: the rings
wrap, and the last pieces' queries no longer see the first positions) through
the engine's own pieces (its admission program for the first ``--piece``
tokens, its extend programs for the rest, the slot parked in between as the
scheduler parks it), then runs ``--steps`` decode steps through
``forward_with_cache`` over the engine's cache trees, one slot active among
the resolved slots: the ring written a row a slot and read whole. The path's
own sets (the router's, tapped by ``choices.py``; every piece and every step)
go to the reference's ``forward_chosen`` over the whole sequence (full-length
keys under a window mask, no ring), which holds the decode steps' logits at
``LOGITS_TOL`` and every position's shortfall at ``CHOICE_TOL``. The control
(the reference with every activation through float8, under its own sets) has
to FAIL by one of the two.

    chiprun -- python3 benchmark/checks/past_window.py \\
        --config smallthinker-21b-a3b [--ctx 8192] [--prompt 4608] \\
        [--steps 32] [--seeds 2]

Writes ``chiprun_out/past_window.<config>.json``; the last line of stdout is
the summary. ``--rehearse`` runs the toy on any backend (``--ctx 128 --prompt
96 --piece 16 --steps 6``: a window of 8)."""

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
    ap.add_argument("--ctx", type=int, default=8192)
    ap.add_argument("--prompt", type=int, default=4608)
    ap.add_argument("--piece", type=int, default=256)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

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
    # the one thing the harness cannot state: the served context
    ecfg = dataclasses.replace(ecfg, max_seq_len=args.ctx)
    if args.rehearse:
        ecfg = dataclasses.replace(ecfg, max_slots=4, cache_dtype=jnp.int8,
                                   min_prefill_bucket=16)
    bits = {"int8": 8, "int4": 4}.get(weights, 0)
    wdtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    ref = sc.load_reference(conf)
    sc.need(hasattr(ref, "forward_chosen") and hasattr(ref, "forward_rounded"),
            "this check is for a reference that makes choices")
    T, N, P, W = (args.prompt, args.steps, args.piece,
                  cfg.sliding_window)
    sc.need(W and T % P == 0 and T > W,
            "the prompt is whole pieces and passes the window")
    sc.need(T + N < min(ecfg.max_seq_len, cfg.max_seq_len),
            "prompt + steps pass the served context")
    chosen_fn = jax.jit(lambda p, t, c: ref.forward_chosen(p, conf, t, c))
    control = jax.jit(lambda p, t: ref.forward_rounded(
        p, conf, t, jnp.float8_e4m3fn))

    def against_reference(params, tokens, logits, routes):
        """(largest |difference| of ``logits`` [N, V] from the reference's
        last N positions under the given sets, as a share of its largest
        |logit|; the largest shortfall)."""
        want, short = chosen_fn(params, jnp.asarray(tokens, jnp.int32),
                                {SITE: jnp.asarray(routes, jnp.int32)})
        want = np.asarray(want[-N:], np.float32)
        return (float(np.abs(logits - want).max() / np.abs(want).max()),
                float(np.asarray(short).max()))

    rows, t0 = [], time.perf_counter()
    for n in range(args.seeds):
        seed = args.first_seed + n
        params = sc.make_weights(cfg, seed, bits, wdtype,
                                 tuple(conf.get("omit_leaves", ())))
        rng = np.random.default_rng([seed, 0x9a57])
        tokens = rng.integers(3, cfg.vocab_size, (T + N,)).astype(np.int32)
        routes = []
        with record_choices() as chosen:
            # the engine is built inside the block, so its programs are
            # traced with the tap in: nothing else ever runs them
            eng = Engine(cfg, params, mesh=None, ecfg=ecfg)
            sc.need(eng.cfg.sliding_window == W < eng.max_seq,
                    "the rings are the published window's")
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

            # the prompt's last piece sampled a token the check does not
            # use: the steps are forced, so position T + j holds tokens[T + j]
            logits = np.asarray(jax.jit(served, donate_argnums=(1, 2))(
                eng.params, eng.k_cache, eng.v_cache,
                jnp.asarray(tokens[T:]), eng.lengths), np.float32)
            calls = chosen.calls()
        sc.need(len(calls) == 1, f"{SITE} handed out {len(calls)} calls in "
                "the decode steps")
        Lr = routes[0].shape[0]
        routes.append(calls[0][:, 0].reshape(N, Lr, -1).transpose(1, 0, 2))
        routes = np.concatenate(routes, axis=1)
        peak = max(d["peak_bytes_in_use"] for d in device_memory())
        ring_bytes, slots = eng.cache_bytes["window"], eng.n_slots
        del eng
        gc.collect()
        rel, short = against_reference(params, tokens, logits, routes)
        ok = bool(np.isfinite(logits).all() and rel <= sc.LOGITS_TOL
                  and short <= sc.CHOICE_TOL)
        c_logits, c_sets = control(params, jnp.asarray(tokens))
        c_rel, c_short = against_reference(
            params, tokens, np.asarray(c_logits[-N:], np.float32),
            np.asarray(c_sets[SITE]))
        c_ok = c_rel <= sc.LOGITS_TOL and c_short <= sc.CHOICE_TOL
        rows.append(dict(
            seed=seed, ok=ok, logits_rel=rel, shortfall=short,
            control_ok=bool(c_ok), control_logits_rel=c_rel,
            control_shortfall=c_short, peak_bytes=peak, positions=T + N,
            positions_past_the_window=T + N - W))
        print(json.dumps(rows[-1]), flush=True)
        del params
        gc.collect()

    summary = dict(
        config=args.config, device=jax.devices()[0].device_kind,
        backend=backend, rehearse=args.rehearse, ctx=args.ctx, prompt=T,
        piece=P, steps=N, sliding_window=W, slots=slots,
        ring_bytes=ring_bytes, seeds=len(rows),
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
    with open(os.path.join(out, f"past_window.{args.config}.json"),
              "w") as f:
        json.dump(dict(summary=summary, rows=rows), f, indent=1)
    print(json.dumps(summary))
    return 0 if summary["passes"] == len(rows) > 0 == summary[
        "control_passes"] else 1


if __name__ == "__main__":
    sys.exit(main())
