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

``kernel`` is ``rows`` with the read through ``ops/pallas/ring.ring_decode``
(``attn.window`` whole: the row write, then each slot's own live ring slots),
``kernel-alone`` the six calls of the kernel with nothing written, each by
ring slots a block (``--blocks 256,512,1024``) and by the slots' contexts:
every slot at 900, every slot at 3,600, and the served cell's mix (4 of 64
past 2,048, mean ~900: the bucket is 4,096 there, which the einsum forms
read for every slot). ``--kernel-only`` skips the einsum forms.
``--rehearse`` runs toy shapes on any backend."""
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.models.config import PRESETS
from ollama_operator_tpu.ops.pallas import ring as ring_kernel

REHEARSE = "--rehearse" in sys.argv
STEPS, LW = 32, 6
SMALLTHINKER = "smallthinker-21b-a3b"


def contexts(what, B):
    """[B] starting lengths: one number for every slot, or the cell's mix."""
    if what != "mix":
        return np.full((B,), what, np.int32)
    rng = np.random.default_rng(52)
    mix = rng.integers(300, 1300, (B,))
    mix[rng.permutation(B)[:B // 16]] = np.linspace(2200, 3000, B // 16)
    return mix.astype(np.int32)


def time_form(name, form, depth, B, start, block=0):
    """ms a step of six window layers in ``form``; ``start`` [B] or one."""
    alone = form == "kernel-alone"
    cfg = decoder._kind_cfgs(PRESETS[name])[1]
    cfg = dataclasses.replace(
        cfg, kernels="xla" if not form.startswith("kernel")
        else "interpret" if REHEARSE else "pallas")
    if REHEARSE:
        cfg = dataclasses.replace(cfg, sliding_window=min(
            cfg.sliding_window, 64))
    W, KvH, H, hd = cfg.sliding_window, cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    decoder._RING_SELECT_MAX = (1 << 30) if form == "select" else 0
    ring_kernel._BLOCK_ROWS = block or ring_kernel._BLOCK_ROWS
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
                if alone:
                    out = ring_kernel.ring_decode(
                        *win, row, q[:, 0], lengths, live, 0.088,
                        interpret=REHEARSE)
                else:
                    out, win = decoder._ring_attend(
                        cfg, q, kv, kv, win, row, lengths, live, 0.088,
                        depth)
                return win, out.astype(jnp.float32).sum()
            (kr, vr), outs = lax.scan(layer, (kr, vr), jnp.arange(LW))
            return (kr, vr, lengths + 1), outs.sum()
        (kr, vr, lengths), outs = lax.scan(step, (kr, vr, lengths), None,
                                           length=STEPS)
        return kr, vr, outs.sum()
    fn = jax.jit(chunk, donate_argnums=(0, 1))
    kr, vr = ring(), ring()
    lengths = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
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
    B = 8 if REHEARSE else 64
    blocks = [256, 512, 1024]
    if "--blocks" in sys.argv:
        blocks = [int(x) for x in
                  sys.argv[sys.argv.index("--blocks") + 1].split(",")]
    plan = [("k-exaone-236b-a23b", "select", None, 300, 0),
            ("k-exaone-236b-a23b", "rows", None, 300, 0),
            (SMALLTHINKER, "select", None, 300, 0),
            (SMALLTHINKER, "rows", None, 300, 0),
            (SMALLTHINKER, "rows", 512, 300, 0),
            (SMALLTHINKER, "rows", 1024, 700, 0),
            (SMALLTHINKER, "rows", 2048, 1500, 0),
            (SMALLTHINKER, "select", 1024, 700, 0),
            (SMALLTHINKER, "rows", None, "mix", 0)]
    if "--kernel-only" in sys.argv:
        plan = []
    plan += [(SMALLTHINKER, form, None, what, block)
             for block in blocks for form in ("kernel-alone", "kernel")
             for what in (900, 3600, "mix")]
    rows = []
    for name, form, depth, what, block in plan:
        start = contexts(what, B)
        if REHEARSE:
            depth, start, block = depth and 32, start % 61, block // 32
        ms = time_form(name, form, depth, B, start, block)
        rows.append(dict(config=name, form=form, depth=depth, slots=B,
                         contexts=what, block=block, ms_per_step=ms))
        if form.startswith("kernel"):
            # the walk's visits a step: a slot's blocks up to its position,
            # at the chunk's middle step, over six layers
            visits = LW * int(np.sum(
                -(-np.minimum(start + STEPS // 2 + 1,
                              64 if REHEARSE else 4096) // max(block, 1))))
            rows[-1].update(visits_per_step=visits,
                            us_per_visit=1e3 * ms / visits)
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
