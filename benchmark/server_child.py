"""The one process that holds the chip: makes the configuration's weights on
the device from the seed, proves the served path against the plain reference,
serves the model with the program's own ``serve()``, and then takes commands
from the parent on stdin (trace, memory, quit). Every line it prints on stdout
is one JSON object; the parent relays them.

The serving configuration is what a bare ``Model`` CR gets: nothing is set but
the server's default ``max_seq_len``, and the program's own ``resolve_*``
functions decide the rest. No ``TPU_*`` serving knob is set here.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
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

# --rehearse: the configuration's preset cut to a toy (CPU, interpret kernels)
TOY = dict(dim=64, n_layers=2, n_heads=8, head_dim=16, ffn_dim=128,
           vocab_size=4096, max_seq_len=512)


def say(**rec) -> None:
    print(json.dumps(rec), flush=True)


class ChildFailure(Exception):
    pass


def need(cond, what: str) -> None:
    if not cond:
        raise ChildFailure(what)


def load_conf(path: str, rehearse: bool) -> dict:
    with open(path) as f:
        conf = json.load(f)
    if rehearse:
        mha = conf["num_key_value_heads"] == conf["num_attention_heads"]
        conf.update(hidden_size=TOY["dim"], intermediate_size=TOY["ffn_dim"],
                    num_hidden_layers=TOY["n_layers"],
                    num_attention_heads=TOY["n_heads"],
                    num_key_value_heads=TOY["n_heads"] if mha else 2,
                    head_dim=TOY["head_dim"], vocab_size=TOY["vocab_size"],
                    max_position_embeddings=TOY["max_seq_len"],
                    max_seq_len=TOY["max_seq_len"])
    return conf


def model_config(conf: dict, rehearse: bool):
    """The program's ModelConfig of the file's preset, held to the file's
    published sizes (a preset that drifts from its source is an error)."""
    from ollama_operator_tpu.models.config import get_config
    cfg = get_config(conf["preset"])
    if rehearse:
        cfg = dataclasses.replace(
            cfg, **TOY, n_kv_heads=conf["num_key_value_heads"],
            sliding_window=0)
    pairs = (("vocab_size", "vocab_size"), ("dim", "hidden_size"),
             ("n_layers", "num_hidden_layers"),
             ("n_heads", "num_attention_heads"),
             ("n_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
             ("ffn_dim", "intermediate_size"))
    for ours, theirs in pairs:
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


def load_reference(conf_path: str, conf: dict):
    path = os.path.join(os.path.dirname(conf_path), conf["reference"])
    spec = importlib.util.spec_from_file_location("benchmark_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.forward


def probe(cfg, ecfg, params, conf: dict, conf_path: str, seed: int) -> bool:
    """``correct`` (a): last-position prefill logits of one seeded prompt and
    the logits of one decode step against the paged cache, through the served
    kernel path, against (1) the benchmark's plain float32 reference over the
    same quantized weights and (2) the program's plain path (kernels="xla",
    mm_kernels="xla"). The probe has a small engine of its own (the serving
    configuration with a pool of PROBE_PAGES pages): the logits program does
    not donate the pool, and a second copy of the served pool does not fit
    beside it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ollama_operator_tpu.models import decoder
    from ollama_operator_tpu.ops.attention import record_kernels
    from ollama_operator_tpu.runtime.engine import Engine

    T = min(PROBE_TOKENS, ecfg.max_seq_len // 2)
    rng = np.random.default_rng([seed, 0x9e0be])
    prompt = rng.integers(3, cfg.vocab_size, (T,)).astype(np.int32)
    forced = int(rng.integers(3, cfg.vocab_size))
    pcfg = dataclasses.replace(ecfg, n_pages=PROBE_PAGES)

    def served_logits(label: str, cfg_):
        eng = Engine(cfg_, params, mesh=None, ecfg=pcfg)
        eng.admit(0, prompt)
        need(not eng.prepare_decode(1), "the probe's pool ran dry")
        nblk = -(-eng.max_seq // eng.ecfg.page_size)

        def logits_fn(p, kc, vc, tokens, step_tokens, tables, lengths):
            pre, _ks, _vs = decoder.prefill_chunk(p, eng.cfg, tokens)
            dec, _kc, _vc = decoder.forward_with_cache_paged(
                p, eng.cfg, step_tokens, kc, vc, tables, lengths, nblk)
            return pre[0, T - 1], dec[0, 0]

        step = np.full((eng.n_slots, 1), forced, np.int32)
        with record_kernels() as picked:
            pre, dec = jax.jit(logits_fn)(
                eng.params, eng.k_cache, eng.v_cache, eng._gr(prompt[None]),
                eng._g(step, eng._slot_sh2), eng._tables_dev(), eng.lengths)
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
        del eng
        return pre, dec

    tol = LOGITS_TOL

    def compare(what: str, a, b) -> bool:
        err = float(np.abs(a - b).max())
        scale = float(np.abs(b).max())
        # the largest logit is reported, not required to agree: on random
        # weights the top two lie closer than rounding moves them
        ok = err <= tol * scale
        say(phase="logits", compared=what, max_abs_err=err, ref_max_abs=scale,
            rel=err / scale, tolerance_rel=tol,
            argmax_agree=bool(a.argmax() == b.argmax()), ok=ok)
        return ok

    pre_k, dec_k = served_logits("served_kernels", cfg)
    plain = dataclasses.replace(cfg, kernels="xla", mm_kernels="xla")
    pre_x, dec_x = served_logits("program_plain_xla", plain)
    forward = load_reference(conf_path, conf)
    ref = np.asarray(jax.jit(lambda p, t: forward(p, conf, t)[-2:])(
        params, jnp.asarray(np.append(prompt, forced), jnp.int32)), np.float32)
    ok = compare("prefill: served vs reference", pre_k, ref[0])
    ok &= compare("decode: served vs reference", dec_k, ref[1])
    ok &= compare("prefill: served vs program plain", pre_k, pre_x)
    ok &= compare("decode: served vs program plain", dec_k, dec_x)
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
    sys.path.insert(0, REPO)
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
    probe_ok = probe(cfg, ecfg, params, conf, args.config, args.seed)
    say(phase="probe_done", ok=probe_ok,
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
