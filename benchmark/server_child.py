"""The one process that holds the chip: makes the configuration's weights on
the device from the seed, proves the served path against the plain reference,
serves the model with the program's own ``serve()``, and then takes commands
from the parent on stdin (trace, memory, quit). Every line it prints on stdout
is one JSON object; the parent relays them.

The serving configuration is what a bare ``Model`` CR gets: nothing is set but
the server's default ``max_seq_len``, and the program's own ``resolve_*``
functions decide the rest. No ``TPU_*`` serving knob is set here.

**What a configuration's file may say** beside its published keys: ``preset``
(the program's ModelConfig), ``reference`` (the plain forward pass, below),
``work`` (its own work arithmetic, ``work.py``), ``holds`` (a list of [preset
field, file key]: the sizes the preset is held to; the dense pairs of ``HOLDS``
where absent) and ``rehearse`` (preset fields of its ``--rehearse`` toy, over
``TOY``; a toy of ``TOY``'s sizes where absent).

**The reference's contract.** ``<name>.reference.py`` exports ``forward(params,
conf, tokens [T]) -> logits [T, V]``: float32, highest matmul precision, no
import from the program. A *choice* is "keep k of n by score" made inside the
forward pass: the experts a router keeps. Where two scores lie closer than
rounding moves them, two sound paths keep different members and everything
downstream differs by a member's whole output: the float32 reference is right,
the served path is right, and their logits differ by several times
``LOGITS_TOL``. So a reference whose model makes choices exports as well

    forward_chosen(params, conf, tokens [T], chosen) -> (logits [T, V],
                                                         shortfall [T])

``chosen`` maps a site's name (the program's device scope, ``moe.route``) to
the sets the path under test kept there, ``[L, T, k]`` int32. The reference
computes every score itself in float32, takes the given sets IN PLACE OF its
own top-k (gates and everything downstream from its own scores over the given
set) and returns for each position the largest amount, over the layers and
sites, by which a given member's score lies below the reference's own k-th
best, as a share of that site's largest |score| at that position (0 where the
given set is the reference's own). The probe then holds EACH path it runs (the
served kernels, the program's plain path) to two things: its logits within
``LOGITS_TOL`` of the reference's under that path's own sets, and no shortfall
over ``CHOICE_TOL`` at any position. The two paths are held to each other only
where they chose alike in every layer at every position up to the compared
one; a position that chose differently feeds its keys and values to every
later one, so from there on the two are two sound answers and the comparison
prints ``skipped: chose differently``. Nothing of the mathematics is left out:
the sets come from the path under test (``choices.py``), every number from the
reference. A reference without ``forward_chosen`` is probed against
``forward`` alone.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark import work  # noqa: E402

MODEL = "bench"
SERVER_DEFAULT_CTX = 4096       # server/__main__.py --max-seq-len default
PROBE_TOKENS = 256
PROBE_PAGES = 32                # pool of the probe's own small engine

# Largest |difference| of logits allowed, as a share of the reference's
# largest |logit|. Served path and reference read the same quantized weights;
# the served path multiplies in bf16, keeps int8 keys and values, and sums in
# another order (flash tiles, post-dot scales). On the chip the four
# comparisons read 1.0-1.9% over 9 seeds and both configurations (PR 23,
# calls 2-7), at most 0.5 points apart within one comparison, so 3% is about
# five of those steps above the largest reading. It holds the kernels to the
# plain arithmetic. It cannot hold the precision: the reference reads the
# leaves the program resolved, whatever their type. ``resolution_ok`` does.
LOGITS_TOL = 0.03

# Largest shortfall allowed: how far below the reference's own k-th best score
# a member that the path under test kept may lie, as a share of the position's
# largest |score|. A sound path moves a score by about the share of the
# largest score by which it moves a logit, and the shortfall is the widest of
# some thousands of such gaps a run (every layer of every position), so it
# reads about twice the logits' reading. Set between two readings (PERF.md
# section 2), both on the chip at hidden 4096 / 128 experts / 4 a token / 6
# layers in bfloat16, 32 seeds each. Sound: the whole probe, both paths, a
# sixth to a quarter of whose positions keep in some layer a set that is not
# the reference's own: 0.77-1.77% over 64 readings; the plain prefill alone,
# an earlier call: 0.77-1.54%, one seed 2.09%. The control, the reference
# through float8: 23.7-46.8% on 8 readings. So 8% lies 3.8 times over the
# sound largest and 3.0 times under the control's smallest.
# A router that keeps members at random reads about 60%.
CHOICE_TOL = 0.08

# --rehearse: the configuration's preset cut to a toy (CPU, interpret
# kernels); a configuration's own "rehearse" fields go over these
TOY = dict(dim=64, n_layers=2, n_heads=8, head_dim=16, ffn_dim=128,
           vocab_size=4096, max_seq_len=512)

# [preset field, file key]: the sizes a preset is held to where the
# configuration's file has no "holds" of its own
HOLDS = (("vocab_size", "vocab_size"), ("dim", "hidden_size"),
         ("n_layers", "num_hidden_layers"),
         ("n_heads", "num_attention_heads"),
         ("n_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
         ("ffn_dim", "intermediate_size"))


def say(**rec) -> None:
    print(json.dumps(rec), flush=True)


class ChildFailure(Exception):
    pass


def need(cond, what: str) -> None:
    if not cond:
        raise ChildFailure(what)


def toy_fields(conf: dict) -> dict:
    """The preset fields of the configuration's ``--rehearse`` toy: its own
    ``rehearse`` over ``TOY``; without one, ``TOY`` with full attention, and
    as many key/value heads as query heads where the model has that."""
    if "rehearse" in conf:
        return {**TOY, **conf["rehearse"]}
    mha = conf.get("num_key_value_heads") == conf["num_attention_heads"]
    return dict(TOY, n_kv_heads=TOY["n_heads"] if mha else 2,
                sliding_window=0)


def load_conf(path: str, rehearse: bool) -> dict:
    conf = work.load_conf(path)
    if rehearse:
        toy = toy_fields(conf)
        conf.update({theirs: toy[ours]
                     for ours, theirs in conf.get("holds", HOLDS)
                     if ours in toy})
        conf.update(max_position_embeddings=toy["max_seq_len"],
                    max_seq_len=toy["max_seq_len"])
    return conf


def model_config(conf: dict, rehearse: bool):
    """The program's ModelConfig of the file's preset, held to the file's
    published sizes (a preset that drifts from its source is an error)."""
    from ollama_operator_tpu.models.config import get_config
    cfg = get_config(conf["preset"])
    if rehearse:
        cfg = dataclasses.replace(cfg, **toy_fields(conf))
    for ours, theirs in conf.get("holds", HOLDS):
        need(getattr(cfg, ours) == conf[theirs],
             f"preset {conf['preset']!r}: {ours}={getattr(cfg, ours)} is not "
             f"the configuration file's {theirs}={conf[theirs]}")
    return cfg


def byte_tokenizer(vocab_size: int):
    """Byte-fallback llama tokenizer over a synthetic vocabulary (a copy of
    bench.py's ``_bench_tokenizer``): any text encodes one token a byte, there
    is no EOS (no stream ends early on random weights), and every other token
    is a filler that decodes to visible whole text, so every frame carries
    text."""
    from ollama_operator_tpu.tokenizer.tokenizer import (TT_BYTE, TT_CONTROL,
                                                         TT_NORMAL, Tokenizer)
    toks = ["<unk>", "<s>", "</s>"]
    tt = [TT_CONTROL, TT_CONTROL, TT_CONTROL]
    # ASCII bytes only: prompts are a-z, and a lone byte of 0x80-0xFF is not
    # UTF-8, so the program's StreamDecoder would hold a stream's text back
    # behind it for chunks on end and TTFT and the gaps would time that
    for i in range(128):
        toks.append(f"<0x{i:02X}>")
        tt.append(TT_BYTE)
    while len(toks) < vocab_size:
        toks.append(f"<fill{len(toks)}>")
        tt.append(TT_NORMAL)
    return Tokenizer("llama", toks[:vocab_size],
                     token_types=tt[:vocab_size], bos_id=1, eos_id=-1)


def weights_program(cfg, bits: int, dtype, omit=()):
    """The function (of a PRNG key) that makes the whole served weight tree,
    already in the type it is served in: each stacked leaf is made and
    quantized a layer at a time inside the program (``lax.map``), so the peak
    is the quantized tree plus one layer's matrix. Matrices, embeddings and
    biases are normal(0, 0.02); norm weights are ones."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ollama_operator_tpu.models import decoder
    from ollama_operator_tpu.ops.quant import (QUANT_LAYER_KEYS,
                                               QUANT_TOP_KEYS,
                                               quantize_groupwise,
                                               quantize_groupwise_int4)
    quant = {8: quantize_groupwise, 4: quantize_groupwise_int4}.get(bits)
    avals = jax.eval_shape(
        lambda k: decoder.init_params(cfg, k, dtype=dtype), jax.random.key(0))
    # leaves the program's preset has and the published model has not
    avals = {k: v for k, v in avals.items() if k not in omit}

    def one(key, name, shape, quantize):
        if name.endswith("norm_w"):
            return jnp.ones(shape, dtype)
        w = (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
        return quant(w) if quantize else w

    def build(key):
        out, n = {}, 0
        for name, sub in avals.items():
            if name == "layers":
                lo = {}
                for lk, aval in sub.items():
                    k = jax.random.fold_in(key, n)
                    n += 1
                    q = quant is not None and lk in QUANT_LAYER_KEYS
                    lo[lk] = lax.map(
                        lambda i, k=k, lk=lk, aval=aval, q=q: one(
                            jax.random.fold_in(k, i), lk, aval.shape[1:], q),
                        jnp.arange(aval.shape[0]))
                out[name] = lo
            else:
                k = jax.random.fold_in(key, n)
                n += 1
                out[name] = one(k, name, sub.shape,
                                quant is not None and name in QUANT_TOP_KEYS)
        return out

    return build


def make_weights(cfg, seed: int, bits: int, dtype, omit=()):
    """The weights, in ONE jitted call on the device, from the seed."""
    import jax
    # the hardware generator: threefry would take minutes for 7e9 normals
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    params = jax.jit(weights_program(cfg, bits, dtype, omit))(key)
    jax.block_until_ready(params)
    return params


def resolve(cfg, backend: str, rehearse: bool):
    """(weight dtype name, EngineConfig) as the zero-config server resolves
    them; the rehearsal states a small paged int8 engine instead (the CPU
    resolution is dense float32, which is not the path rehearsed)."""
    import jax.numpy as jnp

    from ollama_operator_tpu.runtime.engine import (
        EngineConfig, resolve_cache_dtype, resolve_engine_dtype,
        resolve_kv_dtype_default, resolve_serving_defaults)
    if rehearse:
        return "int8", EngineConfig(
            max_slots=8, max_seq_len=TOY["max_seq_len"], decode_chunk=8,
            cache_dtype=jnp.int8, paged=True, page_size=16, n_pages=None,
            min_prefill_bucket=64)
    dtype = resolve_engine_dtype(cfg, backend)
    ecfg = resolve_serving_defaults(
        EngineConfig(max_slots=0, max_seq_len=SERVER_DEFAULT_CTX,
                     decode_chunk=0, paged=None, page_size=0, n_pages=None,
                     cache_dtype=resolve_cache_dtype(
                         resolve_kv_dtype_default(backend))),
        cfg, None)
    return dtype, ecfg


def load_reference(conf: dict):
    """The configuration's reference module, found beside its file."""
    return work.load_module(os.path.join(conf["_dir"], conf["reference"]))


# every number the probe compared, beside its limit, under a short plain
# name: the parent prints them with the window's own (``probe_done`` carries
# them), so a run that is not correct says by how much
COMPARED: dict = {}


def compare(what: str, a, b) -> bool:
    """Largest |a - b| against ``LOGITS_TOL`` of the largest |b|, said as one
    ``phase="logits"`` line."""
    import numpy as np
    err = float(np.abs(a - b).max())
    scale = float(np.abs(b).max())
    # the largest logit is reported, not required to agree: on random
    # weights the top two lie closer than rounding moves them
    ok = err <= LOGITS_TOL * scale
    COMPARED[what.replace(": ", "_").replace(" ", "_")] = {
        "value": err / scale, "limit": LOGITS_TOL}
    say(phase="logits", compared=what, max_abs_err=err, ref_max_abs=scale,
        rel=err / scale, tolerance_rel=LOGITS_TOL,
        argmax_agree=bool(a.argmax() == b.argmax()), ok=ok)
    return ok


@functools.lru_cache(maxsize=None)
def _chosen_program(ref, conf_json: str):
    """``ref.forward_chosen`` of one configuration, compiled once."""
    import jax
    conf = json.loads(conf_json)
    return jax.jit(lambda p, t, c: ref.forward_chosen(p, conf, t, c))


def judge(label: str, logits2, chosen: dict, ref, params, conf: dict,
          tokens) -> bool:
    """A path that makes choices against the reference UNDER THE PATH'S OWN
    SETS. ``logits2`` [2, V] are the path's logits at the last two positions
    of ``tokens`` [N] (the prompt's last and the decode step's), ``chosen``
    its sets by site, [L, N, k]. Holds the logits to ``LOGITS_TOL`` and every
    position's shortfall to ``CHOICE_TOL``."""
    import jax.numpy as jnp
    import numpy as np
    given = {site: jnp.asarray(sets, jnp.int32)
             for site, sets in chosen.items()}
    logits, short = _chosen_program(ref, json.dumps(conf, sort_keys=True))(
        params, jnp.asarray(tokens, jnp.int32), given)
    ref2 = np.asarray(logits[-2:], np.float32)
    short = np.asarray(short, np.float32)
    ok = compare(f"prefill: {label} vs reference", logits2[0], ref2[0])
    ok &= compare(f"decode: {label} vs reference", logits2[1], ref2[1])
    worst = float(short.max())
    close = bool(np.isfinite(short).all() and worst <= CHOICE_TOL)
    COMPARED[f"shortfall_{label}_vs_reference".replace(" ", "_")] = {
        "value": worst, "limit": CHOICE_TOL}
    say(phase="choices", compared=f"{label} vs reference",
        sites=sorted(chosen),
        sets_recorded=int(sum(np.prod(np.shape(s)[:2])
                              for s in chosen.values())),
        positions=int(short.shape[0]),
        positions_not_the_references_own=int((short > 0).sum()),
        shortfall_max=worst, shortfall_at=int(short.argmax()),
        tolerance=CHOICE_TOL, ok=close)
    return bool(ok and close)


def probe(cfg, ecfg, params, conf: dict, seed: int) -> bool:
    """``correct`` (a): last-position prefill logits of one seeded prompt and
    the logits of one decode step against the cache, through the served
    kernel path, against (1) the benchmark's plain float32 reference over the
    same quantized weights and (2) the program's plain path (kernels="xla",
    mm_kernels="xla"). The decode step goes through the entry the resolved
    engine serves: ``forward_with_cache_paged`` over a pool of pages, or
    ``forward_with_cache`` over the contiguous cache where paging is off. The
    probe has a small engine of its own (the serving configuration with a
    pool of PROBE_PAGES pages): the logits program does not donate the cache,
    and a second copy of the served pool does not fit beside it.

    Where the reference has ``forward_chosen`` both paths run under
    ``record_choices``, and EACH is judged against the reference under its own
    sets (prefill's [L, T, k], and the decode step's row of slot 0 as position
    T). The two paths are compared with each other only where they chose alike
    in every layer at every position up to the compared one: a position that
    chose differently feeds its keys and values to all that follow."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.choices import SITE, record_choices
    from ollama_operator_tpu.models import decoder
    from ollama_operator_tpu.ops.attention import record_kernels
    from ollama_operator_tpu.runtime.engine import Engine

    COMPARED.clear()
    ref = load_reference(conf)
    routed = hasattr(ref, "forward_chosen")
    T = min(PROBE_TOKENS, ecfg.max_seq_len // 2)
    rng = np.random.default_rng([seed, 0x9e0be])
    prompt = rng.integers(3, cfg.vocab_size, (T,)).astype(np.int32)
    forced = int(rng.integers(3, cfg.vocab_size))
    pcfg = dataclasses.replace(ecfg, n_pages=PROBE_PAGES)

    def served_logits(label: str, cfg_):
        eng = Engine(cfg_, params, mesh=None, ecfg=pcfg)
        eng.admit(0, prompt)
        need(not eng.prepare_decode(1), "the probe's pool ran dry")
        nblk = -(-eng.max_seq // eng.ecfg.page_size) if eng.paged else None

        def logits_fn(p, kc, vc, tokens, step_tokens, tables, lengths):
            pre, _ks, _vs = decoder.prefill_chunk(p, eng.cfg, tokens)
            if eng.paged:
                dec, _kc, _vc = decoder.forward_with_cache_paged(
                    p, eng.cfg, step_tokens, kc, vc, tables, lengths, nblk)
            else:
                dec, _kc, _vc = decoder.forward_with_cache(
                    p, eng.cfg, step_tokens, kc, vc, lengths,
                    attn_len=eng._attn_bucket(1))
            return pre[0, T - 1], dec[0, 0]

        step = np.full((eng.n_slots, 1), forced, np.int32)
        recorder = record_choices() if routed else contextlib.nullcontext()
        with record_kernels() as picked, recorder as chosen:
            pre, dec = jax.jit(logits_fn)(
                eng.params, eng.k_cache, eng.v_cache, eng._gr(prompt[None]),
                eng._g(step, eng._slot_sh2), eng._tables_dev(), eng.lengths)
            calls = chosen.calls() if routed else None
        pre, dec = (np.asarray(x, np.float32) for x in (pre, dec))
        need(np.isfinite(pre).all() and np.isfinite(dec).all(),
             f"{label}: logits are not finite")
        fell_back = [s for s, _k, fb in picked if fb]
        say(phase="probe", path=label,
            kernels=sorted(f"{s}={k}" for s, k, _ in picked),
            fell_back=fell_back)
        need(not (fell_back and label == "served_kernels"),
             f"{label}: {fell_back} fell back to the plain path, so the "
             "served path was not the one probed")
        if not routed:
            return pre, dec, None
        need(calls, f"{label}: the reference has forward_chosen and the "
             "choice site of benchmark/choices.py handed out no set")
        need(len(calls) == 2 and calls[0].shape[1] == T
             and calls[1].shape[1] == eng.n_slots,
             f"{label}: {SITE} handed out sets of shapes "
             f"{[c.shape for c in calls]}, not prefill's [L, {T}, k] and the "
             f"decode step's [L, {eng.n_slots}, k]")
        return pre, dec, np.concatenate([calls[0], calls[1][:, :1]], 1)

    pre_k, dec_k, sets_k = served_logits("served_kernels", cfg)
    plain = dataclasses.replace(cfg, kernels="xla", mm_kernels="xla")
    pre_x, dec_x, sets_x = served_logits("program_plain_xla", plain)
    tokens = np.append(prompt, forced)
    if not routed:
        ref2 = np.asarray(jax.jit(lambda p, t: ref.forward(p, conf, t)[-2:])(
            params, jnp.asarray(tokens, jnp.int32)), np.float32)
        ok = compare("prefill: served vs reference", pre_k, ref2[0])
        ok &= compare("decode: served vs reference", dec_k, ref2[1])
        ok &= compare("prefill: served vs program plain", pre_k, pre_x)
        ok &= compare("decode: served vs program plain", dec_k, dec_x)
        return bool(ok)

    ok = judge("served", (pre_k, dec_k), {SITE: sets_k}, ref, params, conf,
               tokens)
    ok &= judge("program plain", (pre_x, dec_x), {SITE: sets_x}, ref, params,
                conf, tokens)
    # [T + 1]: some layer's set at that position is not the same in both
    differ = (sets_k != sets_x).any(axis=(0, 2))
    for what, at, a, b in (("prefill", T - 1, pre_k, pre_x),
                           ("decode", T, dec_k, dec_x)):
        name = f"{what}: served vs program plain"
        if differ[:at + 1].any():
            # two sound paths on two sides of a tie, here or at a position
            # whose keys and values this one reads: each was judged against
            # the reference under its own sets, not against the other
            say(phase="logits", compared=name, skipped="chose differently",
                positions_that_differ=int(differ[:at + 1].sum()), ok=True)
        else:
            ok &= compare(name, a, b)
    return bool(ok)


def device_line(jax) -> dict:
    import jaxlib
    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs), jax=jax.__version__,
                jaxlib=jaxlib.__version__)


def memory() -> list:
    from ollama_operator_tpu.server.app import device_memory
    return device_memory()


def command_loop(trace_dir: str, device_prefix: str) -> None:
    """Commands from the parent, one word a line, each answered by one JSON
    line with the same ``reply``."""
    import jax

    from benchmark import reduce_trace
    tracing = False
    for line in sys.stdin:
        cmd = line.strip()
        if cmd == "trace_start":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
            say(reply=cmd, t=time.time())
        elif cmd == "trace_stop":
            if tracing:
                jax.profiler.stop_trace()
                tracing = False
            say(reply=cmd, t=time.time())
        elif cmd == "reduce":
            say(reply=cmd,
                **reduce_trace.reduce_dir(trace_dir, device_prefix))
        elif cmd == "memory":
            say(reply=cmd, devices=memory())
        elif cmd == "quit":
            break
        else:
            say(reply=cmd, error="unknown command")
    if tracing:
        jax.profiler.stop_trace()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    t0 = time.perf_counter()

    import jax
    import jax.numpy as jnp

    from ollama_operator_tpu.runtime import compile_cache
    backend = jax.default_backend()
    say(phase="device", **device_line(jax))
    if not args.rehearse:
        need(backend == "tpu",
             f"the benchmark measures on a TPU; JAX initialised {backend!r}")
        need(len(jax.devices()) >= args.chips,
             f"the cell asks for {args.chips} chips; JAX found "
             f"{len(jax.devices())}")
    cache_dir = compile_cache.enable()

    from ollama_operator_tpu.ops.quant import int4_mm_kernels
    from ollama_operator_tpu.runtime.service import LoadedModel
    from ollama_operator_tpu.runtime.trace import FLIGHT
    from ollama_operator_tpu.server.app import ModelManager, serve
    from ollama_operator_tpu.server.names import ModelName

    conf = load_conf(args.config, args.rehearse)
    cfg = model_config(conf, args.rehearse)
    dtype, ecfg = resolve(cfg, backend, args.rehearse)
    bits = {"int8": 8, "int4": 4}.get(dtype, 0)
    if dtype == "int4":
        cfg = int4_mm_kernels(cfg, None)
    cache_dt = ecfg.cache_dtype
    kv_name = (cache_dt if isinstance(cache_dt, str)
               else jnp.dtype(cache_dt).name)
    say(phase="resolved", config=conf["name"], weights=dtype, kv=kv_name,
        paged=bool(ecfg.paged), max_slots=ecfg.max_slots,
        page_size=ecfg.page_size, n_pages=ecfg.n_pages,
        decode_chunk=ecfg.decode_chunk,
        max_seq_len=min(ecfg.max_seq_len, cfg.max_seq_len),
        mm_kernels=cfg.mm_kernels, kernels=cfg.kernels,
        compile_cache_dir=cache_dir,
        expected=conf["expected_resolution"])
    # the precision is part of the result: weights or a cache of another type
    # than the configuration's file states is another system, not a faster
    # one. Slots, pages and chunk are printed beside what the file expects and
    # not held: they are what a later PR may tune.
    expected = conf["expected_resolution"]
    resolution_ok = (dtype == expected["weights"]
                     and kv_name == expected["kv"])

    wdtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    params = make_weights(cfg, args.seed, bits, wdtype,
                          tuple(conf.get("omit_leaves", ())))
    say(phase="weights", seconds=round(time.perf_counter() - t0, 3),
        bytes=int(sum(x.nbytes for x in jax.tree.leaves(params))),
        devices=memory())

    t1 = time.perf_counter()
    probe_ok = probe(cfg, ecfg, params, conf, args.seed)
    say(phase="probe_done", ok=probe_ok, compared=COMPARED,
        seconds=round(time.perf_counter() - t1, 3))

    t1 = time.perf_counter()
    tok = byte_tokenizer(cfg.vocab_size)
    lm = LoadedModel(ModelName.parse(MODEL).short, cfg, params, tok,
                     ecfg=ecfg)
    lm.serving_dtype = dtype
    for ev in FLIGHT.snapshot():
        if ev["kind"] in ("warm_plan", "kernel_fallback"):
            say(phase=ev["kind"], **{k: v for k, v in ev.items()
                                     if k not in ("kind", "seq", "t_unix")})
        # a kernel that quietly gave way to the plain path is not the served
        # path the cell names. Kernel NAMES are printed and not held: a later
        # PR may bring a new one, and the logits comparison holds its numbers
        resolution_ok &= ev["kind"] != "kernel_fallback"
    say(phase="resolution", ok=bool(resolution_ok), weights=dtype, kv=kv_name,
        expected_weights=expected["weights"], expected_kv=expected["kv"])
    say(phase="engine", seconds=round(time.perf_counter() - t1, 3),
        program_kernels=lm.engine.kernels_by_kind(), devices=memory())

    store = tempfile.mkdtemp(prefix="bench-store-")
    manager = ModelManager(store, serve_models=True, default_keep_alive=-1)
    manager.loaded = lm
    httpd = serve(manager, "127.0.0.1", 0)
    overhead = len(tok.encode("a" * 32, add_bos=tok.add_bos)) - 32
    say(phase="ready", port=httpd.server_address[1], model=MODEL,
        probe_ok=probe_ok, resolution_ok=bool(resolution_ok),
        prompt_overhead_tokens=overhead,
        decode_chunk=ecfg.decode_chunk, max_slots=ecfg.max_slots,
        max_seq_len=min(ecfg.max_seq_len, cfg.max_seq_len),
        weights=dtype, kv_dtype=kv_name,
        seconds=round(time.perf_counter() - t0, 3))
    try:
        command_loop(args.trace_dir, "/host:CPU" if args.rehearse
                     else "/device:TPU:")
    finally:
        httpd.shutdown()
        httpd.server_close()
        lm.unload()
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except ChildFailure as e:
        say(phase="failed", error=str(e))
        sys.exit(1)
