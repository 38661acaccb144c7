"""One decode step's window layers alone, on the chip: the ring's write and
the attention over it (``decoder._ring_attend``, scope ``attn.window``) of six
layers at 64 slots, int8 rings, by the ring's form and the attended depth:

    chiprun -- python hack/ring_microbench.py

``select`` is the ring advanced by a select over the whole ring (the form
PR 39 brought, ``_RING_SELECT_MAX`` above W), ``rows`` a row a slot
(``_ring_put``); ``depth`` the attended prefix the ring is read to (the whole
ring where it is W or more). At K-EXAONE's shape (8 kv heads, W = 128) and at
SmallThinker's (4 kv heads, W = 4,096). Times a chunk of 32 steps, rings
donated, ms a step; writes ``chiprun_out/ring_microbench.json``.
``--rehearse`` runs toy shapes on any backend."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.models.config import PRESETS

REHEARSE = "--rehearse" in sys.argv
STEPS, LW = 32, 6


def time_form(name, form, depth, B, start):
    cfg = decoder._kind_cfgs(PRESETS[name])[1]
    if REHEARSE:
        import dataclasses
        cfg = dataclasses.replace(cfg, sliding_window=min(
            cfg.sliding_window, 64))
    W, KvH, H, hd = cfg.sliding_window, cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    decoder._RING_SELECT_MAX = (1 << 30) if form == "select" else 0
    key = jax.random.key(0)

    def ring():
        return {"q": jnp.zeros((LW, B, KvH, W, hd), jnp.int8),
                "s": jnp.ones((LW, B, KvH, W), jnp.float32)}
    q = jax.random.normal(key, (B, 1, H, hd), jnp.bfloat16)
    kv = jax.random.normal(key, (B, 1, KvH, hd), jnp.bfloat16)
    live = jnp.ones((B,), jnp.int32)

    def chunk(kr, vr, lengths):
        def step(carry, _):
            kr, vr, lengths = carry

            def layer(win, row):
                out, win = decoder._ring_attend(
                    cfg, q, kv, kv, win, row, lengths, live, 0.088, depth)
                return win, out.astype(jnp.float32).sum()
            (kr, vr), outs = lax.scan(layer, (kr, vr), jnp.arange(LW))
            return (kr, vr, lengths + 1), outs.sum()
        (kr, vr, lengths), outs = lax.scan(step, (kr, vr, lengths), None,
                                           length=STEPS)
        return kr, vr, outs.sum()
    fn = jax.jit(chunk, donate_argnums=(0, 1))
    kr, vr = ring(), ring()
    lengths = jnp.full((B,), start, jnp.int32)
    kr, vr, out = fn(kr, vr, lengths)
    jax.block_until_ready(out)
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        kr, vr, out = fn(kr, vr, lengths)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best / STEPS


def main():
    B = 4 if REHEARSE else 64
    plan = [("k-exaone-236b-a23b", "select", None, 300),
            ("k-exaone-236b-a23b", "rows", None, 300),
            ("smallthinker-21b-a3b", "select", None, 300),
            ("smallthinker-21b-a3b", "rows", None, 300),
            ("smallthinker-21b-a3b", "rows", 512, 300),
            ("smallthinker-21b-a3b", "rows", 1024, 700),
            ("smallthinker-21b-a3b", "rows", 2048, 1500),
            ("smallthinker-21b-a3b", "select", 1024, 700)]
    rows = []
    for name, form, depth, start in plan:
        if REHEARSE:
            depth, start = depth and 32, 20
        ms = time_form(name, form, depth, B, start)
        rows.append(dict(config=name, form=form, depth=depth, slots=B,
                         ms_per_step=ms))
        print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    if not REHEARSE:
        with open(os.path.join(out, "ring_microbench.json"), "w") as f:
            json.dump(dict(device=jax.devices()[0].device_kind, rows=rows),
                      f, indent=1)


if __name__ == "__main__":
    main()
