#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the tp=4 path and its comparison only

Default run, one chip. This process never imports JAX (a parent that has
touched JAX holds the chip, and a child that needs it then fails or hangs):

1. it writes, with numpy from --seed, a GGUF at tinyllama's published
   widths and depth (models/config.py) with a byte-fallback vocabulary, so
   that any prompt text tokenizes;
2. it starts ONE child, `python -m ollama_operator_tpu.server`, the way a
   bare Model CR starts it: TPU_EXPECT_PLATFORM=tpu and no serving flag, so
   the server resolves int8 weights, int8 paged KV, 64 slots, 128-token
   pages, chunk 32 and the pallas kernels on its own, and compiles its whole
   warm plan;
3. over HTTP it creates the model, loads it, reads back what was resolved
   (/api/ps, /debug/events) and sends a few requests through
   /api/generate: one greedy request twice (identical tokens), eight
   concurrent streams, one follow-up that re-uses a cached prefix;
4. after the server has exited and released the chip, one more child runs
   the same weights through the engine's default kernel path and through
   the plain path (kernels="xla") and compares logits: interpret mode on a
   CPU never checked the kernels' results on the chip.

Each phase prints one JSON object on its own line; any phase that fails
ends the run non-zero with the child's last output shown. The last line is
the contract's: {"ok": true, "device": {...}}.

--chips 4 runs the same GGUF through one server with --tp 4 on all four
chips, then compares the tp=4 engine with the one-chip engine at the level
of logits, one holder of the chips at a time, and prints what every device
holds. Nothing of the one-chip run is repeated there.

--rehearse runs the whole control flow on the CPU at a toy width with the
kernels in interpret mode (section 2 of the on-chip-measurement guide). It
proves nothing about the chip and never prints an ok line.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
MODEL = "smoke"

# tinyllama as models/config.py:188-190 has it (TinyLlama-1.1B): checked
# against the preset by the children, which may import the package's config
WIDTHS = dict(vocab=32000, dim=2048, layers=22, heads=32, kv_heads=4,
              head_dim=64, ffn=5632, ctx=2048)
TOY = dict(vocab=512, dim=64, layers=2, heads=8, kv_heads=4, head_dim=16,
           ffn=128, ctx=256)

# serving knobs a zero-config start must not inherit from our environment
SERVING_ENV = ("TPU_ENGINE_DTYPE", "TPU_KV_DTYPE", "TPU_PAGED",
               "TPU_PAGE_SIZE", "TPU_N_PAGES", "TPU_MAX_SLOTS",
               "TPU_DECODE_CHUNK", "TPU_MAX_SEQ_LEN", "TPU_TENSOR_PARALLEL",
               "TPU_SEQUENCE_PARALLEL", "TPU_EXPERT_PARALLEL",
               "TPU_DATA_PARALLEL", "TPU_WARM_BUCKETS", "TPU_XLA_CACHE",
               "TPU_PREFIX_CACHE", "TPU_SPEC_DECODE", "OLLAMA_TPU_KERNELS",
               "TPU_MIN_PREFILL_BUCKET", "TPU_PREFILL_CHUNK")

# kernel-vs-plain and tp4-vs-one-chip logits: the largest |difference|
# allowed, as a share of the reference's largest |logit|. Both sides read
# the same int8 weights and write the same int8 KV; what differs is the
# order of bf16 sums (flash tiles, per-device partial sums), a few parts in
# a thousand per layer.
LOGITS_TOL = 0.06


# the server is on this host: never through a proxy the environment names
HTTP = urllib.request.build_opener(urllib.request.ProxyHandler({}))


class SmokeFailure(Exception):
    pass


def say(**rec) -> None:
    print(json.dumps(rec), flush=True)


def need(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# the checkpoint
# ---------------------------------------------------------------------------

def write_gguf(path: str, w: dict, seed: int, tick) -> dict:
    """A llama-architecture GGUF at widths ``w``: Q8_0 matrices, F32
    norms, random from ``seed``; byte-fallback vocabulary (the recipe of
    bench.py's _bench_tokenizer) with no EOS, so a stream never ends
    early on a random model. ``tick()`` runs between layers (the caller
    watches the server child it has already started)."""
    import numpy as np

    from ollama_operator_tpu.gguf import reader as R
    from ollama_operator_tpu.gguf.writer import GGUFWriter, quantize_q8_0

    rng = np.random.default_rng(seed)
    g = GGUFWriter(path)
    for k, v in (("general.architecture", "llama"),
                 ("llama.block_count", w["layers"]),
                 ("llama.embedding_length", w["dim"]),
                 ("llama.attention.head_count", w["heads"]),
                 ("llama.attention.head_count_kv", w["kv_heads"]),
                 ("llama.attention.key_length", w["head_dim"]),
                 ("llama.feed_forward_length", w["ffn"]),
                 ("llama.context_length", w["ctx"]),
                 ("llama.rope.freq_base", 10000.0),
                 ("llama.attention.layer_norm_rms_epsilon", 1e-5)):
        g.add_meta(k, v)
    toks = ["<unk>", "<s>", "</s>"] + [f"<0x{i:02X}>" for i in range(256)]
    types = [3, 3, 3] + [6] * 256            # control ×3, then byte tokens
    toks += [f"<fill{i}>" for i in range(len(toks), w["vocab"])]
    types += [1] * (w["vocab"] - len(types))  # normal
    g.add_meta("tokenizer.ggml.model", "llama")
    g.add_meta("tokenizer.ggml.tokens", toks)
    g.add_meta("tokenizer.ggml.scores", [0.0] * w["vocab"])
    g.add_meta("tokenizer.ggml.token_type", types)
    g.add_meta("tokenizer.ggml.bos_token_id", 1)

    n_params = 0

    def mat(name, rows, cols):
        nonlocal n_params
        a = rng.standard_normal((rows, cols), dtype=np.float32) * 0.02
        g.add_tensor_raw(name, (rows, cols), R.GGML_Q8_0, quantize_q8_0(a))
        n_params += rows * cols

    def ones(name, n):
        g.add_tensor_f32(name, np.ones((n,), np.float32))

    q_dim, kv_dim = w["heads"] * w["head_dim"], w["kv_heads"] * w["head_dim"]
    mat("token_embd.weight", w["vocab"], w["dim"])
    ones("output_norm.weight", w["dim"])
    mat("output.weight", w["vocab"], w["dim"])
    for i in range(w["layers"]):
        tick()
        b = f"blk.{i}."
        ones(b + "attn_norm.weight", w["dim"])
        mat(b + "attn_q.weight", q_dim, w["dim"])
        mat(b + "attn_k.weight", kv_dim, w["dim"])
        mat(b + "attn_v.weight", kv_dim, w["dim"])
        mat(b + "attn_output.weight", w["dim"], q_dim)
        ones(b + "ffn_norm.weight", w["dim"])
        mat(b + "ffn_gate.weight", w["ffn"], w["dim"])
        mat(b + "ffn_up.weight", w["ffn"], w["dim"])
        mat(b + "ffn_down.weight", w["dim"], w["ffn"])
    g.write()
    return {"n_params": n_params, "bytes": os.path.getsize(path)}


def prompt_text(n_tokens: int, seed: int) -> str:
    """Text that tokenizes to about ``n_tokens`` on the byte-fallback
    vocabulary (one token a byte, plus BOS and the SPM space prefix)."""
    import numpy as np
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
    rng = np.random.default_rng(seed)
    return bytes(rng.choice(letters, max(1, n_tokens - 4))).decode()


# ---------------------------------------------------------------------------
# the server child, over HTTP
# ---------------------------------------------------------------------------

def child_env(extra: dict) -> dict:
    """Our environment less the serving knobs, with the repo importable."""
    env = {k: v for k, v in os.environ.items() if k not in SERVING_ENV}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra)
    return env


class Server:
    def __init__(self, work: str, extra_args, env_extra, log_name: str):
        self.log_path = os.path.join(work, log_name)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        cmd = [sys.executable, "-m", "ollama_operator_tpu.server",
               "--host", "127.0.0.1", "--port", str(self.port),
               "--store", os.path.join(work, "store"),
               "--cache", os.path.join(work, "cache")] + list(extra_args)
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(cmd, env=child_env(env_extra),
                                     cwd=REPO, stdout=self.log,
                                     stderr=self.log)
        self.cmd = cmd

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.port}{path}"

    def alive(self) -> None:
        if self.proc.poll() is not None:
            raise SmokeFailure(
                f"the server exited with code {self.proc.returncode}")

    def wait_ready(self, timeout_s: float) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            self.alive()
            try:
                with HTTP.open(self.url("/api/version"), timeout=5):
                    return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                time.sleep(0.5)
        raise SmokeFailure(f"the server was not ready in {timeout_s:.0f}s")

    def get(self, path: str, timeout: float = 60):
        with HTTP.open(self.url(path), timeout=timeout) as r:
            body = r.read().decode()
        return json.loads(body) if path.startswith(("/api", "/debug")) \
            else body

    def post(self, path: str, body: dict, timeout: float):
        """POST; returns the parsed NDJSON lines of the answer (one for a
        non-streaming answer)."""
        req = urllib.request.Request(
            self.url(path), data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with HTTP.open(req, timeout=timeout) as r:
                lines = [json.loads(ln) for ln in r if ln.strip()]
        except urllib.error.HTTPError as e:
            raise SmokeFailure(f"POST {path}: HTTP {e.code}: "
                               f"{e.read().decode()[:400]}") from e
        for ln in lines:
            need("error" not in ln, f"POST {path}: {ln.get('error')}")
        return lines

    def metric(self, name: str) -> float:
        """Sum of a metric family's samples on /metrics."""
        total, seen = 0.0, False
        for ln in self.get("/metrics").splitlines():
            if ln.startswith(name) and ln[len(name):len(name) + 1] in " {":
                total += float(ln.rsplit(" ", 1)[1])
                seen = True
        need(seen, f"/metrics has no {name}")
        return total

    def events(self, kind: str) -> list:
        return self.get(f"/debug/events?kind={kind}")["events"]

    def stop(self) -> int:
        """SIGTERM (graceful drain) and wait: the chip is free once the
        process is gone."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.kill()
        return self.proc.returncode

    def kill(self) -> None:
        """Make sure nothing is left running (a no-op after stop())."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.log.close()

    def shutdown_event(self, kind: str):
        """An event of the flight-recorder dump the server prints as it
        shuts down (its last log lines)."""
        found = None
        with open(self.log_path, errors="replace") as f:
            for ln in f:
                if ln.startswith("{") and f'"kind": "{kind}"' in ln:
                    found = json.loads(ln)
        return found

    def tail(self, n: int = 60) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])


def generate(srv: Server, prompt: str, n_new: int, stream: bool,
             timeout: float = 600) -> dict:
    """One greedy /api/generate; returns the final record."""
    lines = srv.post("/api/generate", {
        "model": MODEL, "prompt": prompt, "stream": stream,
        "options": {"temperature": 0, "repeat_penalty": 1.0,
                    "num_predict": n_new}}, timeout)
    final = lines[-1]
    need(final.get("done") is True, f"stream did not finish: {final}")
    need(final.get("eval_count") == n_new,
         f"eval_count {final.get('eval_count')} != {n_new}: {final}")
    return final


def serve_and_check(gguf: str, plan: dict, srv: Server) -> None:
    """Phase 3 of the module docstring, against a started server."""
    t0 = time.monotonic()
    srv.post("/api/create", {
        "model": MODEL, "stream": False,
        "modelfile": f'FROM {gguf}\nTEMPLATE """{{{{ .Prompt }}}}"""'}, 600)
    create_s = round(time.monotonic() - t0, 1)

    # the empty generate is ollama's "load the model": transcode, quantize,
    # upload, build the engine, compile the whole warm plan
    t0 = time.monotonic()
    srv.post("/api/generate", {"model": MODEL, "prompt": ""},
             plan["load_timeout_s"])
    load_s = round(time.monotonic() - t0, 1)

    det = srv.get("/api/ps")["models"][0]["details"]
    load = srv.events("model_load")[-1]
    warm = srv.events("warm_plan")[-1]
    fallbacks = srv.events("kernel_fallback")
    say(phase="load", create_s=create_s, load_s=load_s,
        transcode_s=load["transcode_s"], quantize_s=load["quantize_s"],
        upload_s=load["upload_s"], engine_warm_s=load["engine_warm_s"],
        dequant=load["dequant"], resolved={
            k: load[k] for k in ("serving_dtype", "kv_dtype", "paged",
                                 "max_slots", "page_size", "n_pages",
                                 "decode_chunk", "max_seq_len")})
    say(phase="warm_plan", programs=warm["programs"],
        seconds=warm["seconds"], cache_hits=warm["cache_hits"],
        cache_misses=warm["cache_misses"], kernels=warm["kernels"],
        kernel_fallbacks=fallbacks)
    say(phase="device_memory_after_load", devices=load["devices"])
    need(len(load["devices"]) == plan["chips"],
         f"the server sees {len(load['devices'])} devices, not "
         f"{plan['chips']}")
    need(all(d["platform"] == plan["platform"] for d in load["devices"]),
         f"the server runs on {load['devices']}, not {plan['platform']}")
    want = plan["resolved"]
    need(det["serving_dtype"] == want["serving_dtype"]
         and det["paged"] is want["paged"]
         and det["decode_chunk"] == want["decode_chunk"],
         f"/api/ps details {det} != {want}")
    for k, v in want.items():
        need(load[k] == v, f"resolved {k}={load[k]!r}, expected {v!r}")
    need(load["dequant"] == "native",
         "the dequant library was not built and used on this machine "
         f"(path: {load['dequant']})")
    need(not fallbacks, f"a kernel gave way to einsum: {fallbacks}")
    need("paged_decode=paged_v3" in warm["kernels"].get("decode", []),
         f"the served decode programs lack the v3 paged kernel: "
         f"{warm['kernels']}")
    need("prefill=flash_prefill" in warm["kernels"].get("admit", []),
         f"the served admit programs lack the flash prefill kernel: "
         f"{warm['kernels']}")
    need(warm["programs"] > 0, "the warm plan compiled nothing")

    # one greedy request, twice: same tokens. Short of a page, so nothing
    # is donated to the prefix cache and both runs take the same programs.
    a = generate(srv, prompt_text(40, plan["seed"]), 32, stream=False)
    b = generate(srv, prompt_text(40, plan["seed"]), 32, stream=False)
    need(a["context"] == b["context"],
         "the same greedy request gave different tokens twice")
    say(phase="greedy_twice", identical=True,
        prompt_tokens=a["prompt_eval_count"], new_tokens=a["eval_count"])

    # concurrent streams over the prefill buckets
    lens = plan["stream_prompts"]
    texts = [prompt_text(n, plan["seed"] + 1 + i)
             for i, n in enumerate(lens)]
    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(len(texts)) as pool:
        futs = [pool.submit(generate, srv, t, plan["stream_new"], True)
                for t in texts]
        finals = [f.result() for f in futs]
    say(phase="concurrent_streams", streams=len(finals), all_done=True,
        new_tokens_each=plan["stream_new"],
        prompt_tokens=[f["prompt_eval_count"] for f in finals],
        seconds=round(time.monotonic() - t0, 1))

    # a follow-up that repeats an earlier prompt plus a suffix
    before = srv.metric("tpu_model_prefix_reused_tokens_total")
    generate(srv, texts[plan["followup_of"]] + " and then some more", 16,
             stream=False)
    reused = srv.metric("tpu_model_prefix_reused_tokens_total") - before
    need(reused > 0, "the follow-up re-used no cached prefix tokens")
    recompiles = srv.metric("tpu_model_recompiles_total")
    need(recompiles == 0, f"{recompiles:.0f} programs compiled while "
                          f"serving: the warm plan missed them")
    hbm = srv.metric("tpu_model_hbm_bytes_in_use")
    say(phase="after_requests", prefix_reused_tokens=int(reused),
        recompiles=0, hbm_bytes_in_use_device0=int(hbm))
    if plan["platform"] == "tpu":
        need(hbm > plan["weight_bytes_floor"] / plan["chips"],
             f"device 0 holds {hbm:.0f} bytes, less than its share of the "
             f"int8 weights ({plan['weight_bytes_floor']})")


# ---------------------------------------------------------------------------
# the children that import JAX (one at a time, after the server is gone)
# ---------------------------------------------------------------------------

def child_compare(args) -> int:
    """Logits of one prefill and one decode step through the engine's
    default kernel path, against the plain path (one chip) or against the
    one-chip engine (``--tp`` > 1: the mesh path). Prints JSON lines."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import jaxlib
    import ml_dtypes
    import numpy as np

    from ollama_operator_tpu.gguf.transcode import load_model
    from ollama_operator_tpu.models import decoder
    from ollama_operator_tpu.ops.attention import record_kernels
    from ollama_operator_tpu.ops.quant import quantize_params
    from ollama_operator_tpu.runtime import compile_cache
    from ollama_operator_tpu.runtime.engine import (
        Engine, EngineConfig, resolve_cache_dtype, resolve_engine_dtype,
        resolve_kv_dtype_default, resolve_serving_defaults)
    from ollama_operator_tpu.server.app import device_memory

    compile_cache.enable()
    devs = jax.devices()
    backend = jax.default_backend()
    need(backend == args.platform,
         f"expected JAX platform {args.platform!r}, got {backend!r}")
    need(len(devs) >= args.tp, f"--tp {args.tp} needs that many devices")
    say(phase="child_device", platform=devs[0].platform,
        kind=devs[0].device_kind, count=len(devs), jax=jax.__version__,
        jaxlib=jaxlib.__version__)

    on_cpu = backend == "cpu"
    cfg, params, _tok = load_model(
        args.gguf, cache_dir=os.path.join(args.work, "cache"),
        dtype=np.float32 if on_cpu else ml_dtypes.bfloat16)
    if on_cpu:      # the rehearsal (OLLAMA_TPU_KERNELS=interpret is set)
        ecfg = EngineConfig(max_slots=8, max_seq_len=args.max_seq_len,
                            decode_chunk=8, cache_dtype=jnp.int8,
                            paged=True, page_size=16, n_pages=None)
    else:           # what the zero-config server resolves, from its code
        need(resolve_engine_dtype(cfg, backend) == "int8",
             "the smoke's model no longer resolves to int8 weights")
        ecfg = resolve_serving_defaults(
            EngineConfig(max_slots=0, max_seq_len=args.max_seq_len,
                         decode_chunk=0, paged=None, page_size=0,
                         n_pages=None, cache_dtype=resolve_cache_dtype(
                             resolve_kv_dtype_default(backend))),
            cfg, None)
        from ollama_operator_tpu.models.config import PRESETS
        ref = PRESETS["tinyllama"]
        fields = ("vocab_size", "dim", "n_layers", "n_heads", "n_kv_heads",
                  "head_dim", "ffn_dim", "max_seq_len")
        need(all(getattr(cfg, f) == getattr(ref, f) for f in fields),
             "the smoke's widths are no longer the tinyllama preset's")
    params = quantize_params(params, bits=8)

    T = args.prompt_tokens
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(3, cfg.vocab_size, (T,)).astype(np.int32)
    forced = int(rng.integers(3, cfg.vocab_size))   # the token decoded

    def probe(label: str, ecfg_, cfg_, mesh):
        """admit() the prompt through the engine's own program (its pool,
        its tables, its kernels), then read the logits the same decoder
        functions give for the prefill's last position and for one decode
        step of the whole slot batch against that pool."""
        p = params if mesh is not None else jax.tree_util.tree_map(
            jnp.asarray, params)
        eng = Engine(cfg_, p, mesh=mesh, ecfg=ecfg_)
        mem = device_memory()[:args.tp]
        eng.admit(0, prompt)
        need(not eng.prepare_decode(1), "the pool ran dry on one prompt")
        nblk = -(-eng.max_seq // eng.ecfg.page_size)

        def logits_fn(params, kc, vc, tokens, step_tokens, tables, lengths):
            pre, _ks, _vs = decoder.prefill_chunk(params, eng.cfg, tokens,
                                                  mesh=mesh)
            dec, _kc, _vc = decoder.forward_with_cache_paged(
                params, eng.cfg, step_tokens, kc, vc, tables, lengths,
                nblk, mesh=mesh)
            return pre[0, T - 1], dec[0, 0]

        step = np.full((eng.n_slots, 1), forced, np.int32)
        with record_kernels() as picked:
            pre, dec = jax.jit(logits_fn)(
                eng.params, eng.k_cache, eng.v_cache, eng._gr(prompt[None]),
                eng._g(step, eng._slot_sh2), eng._tables_dev(), eng.lengths)
        pre, dec = (np.asarray(eng._fetch(x), np.float32)
                    for x in (pre, dec))
        need(pre.shape == dec.shape == (cfg.vocab_size,),
             f"logits of shape {pre.shape}, {dec.shape}")
        need(np.isfinite(pre).all() and np.isfinite(dec).all(),
             f"{label}: logits are not finite")
        say(phase="probe", path=label,
            kernels=sorted(f"{s}={k}" for s, k, _ in picked),
            fell_back=[s for s, _k, fb in picked if fb],
            device_memory_after_engine=mem)
        return pre, dec, mem

    def compare(what, a, b):
        err = float(np.abs(a - b).max())
        scale = float(np.abs(b).max())
        ok = err <= LOGITS_TOL * scale
        say(phase="logits", compared=what, max_abs_err=err,
            ref_max_abs=scale, rel=err / scale, tolerance_rel=LOGITS_TOL,
            argmax_agree=bool(a.argmax() == b.argmax()), ok=ok)
        return ok

    ok = True
    if args.tp > 1:
        from ollama_operator_tpu.parallel import MeshPlan, make_mesh
        mesh = make_mesh(MeshPlan.for_devices(args.tp, tp=args.tp),
                         devs[:args.tp])
        pre_m, dec_m, mem = probe(f"tp{args.tp}", ecfg, cfg, mesh)
        # sharded: no device may hold more than a generous share of what
        # the busiest holds on one chip (a quarter, plus replicated state)
        pre_1, dec_1, mem1 = probe("one_chip", ecfg, cfg, None)
        if not on_cpu:
            top = max(m["bytes_in_use"] for m in mem)
            low = min(m["bytes_in_use"] for m in mem)
            need(low > 0 and top < 1.5 * low,
                 f"the model is not spread evenly over the chips: {mem}")
        ok &= compare(f"prefill tp{args.tp} vs one chip", pre_m, pre_1)
        ok &= compare(f"decode tp{args.tp} vs one chip", dec_m, dec_1)
    else:
        plain = dataclasses.replace(cfg, kernels="xla", mm_kernels="xla")
        pre_k, dec_k, _ = probe("default_kernels", ecfg, cfg, None)
        pre_x, dec_x, _ = probe("plain_xla", ecfg, plain, None)
        ok &= compare("prefill kernels vs plain", pre_k, pre_x)
        ok &= compare("decode kernels vs plain", dec_k, dec_x)
    say(phase="child_done", ok=bool(ok),
        peak_bytes_in_use=[d["peak_bytes_in_use"] for d in device_memory()])
    return 0 if ok else 1


def run_child(work: str, gguf: str, plan: dict, tp: int) -> dict:
    """Start the compare child, relay its JSON lines, return the device it
    reported. Its stderr goes to a log shown on failure."""
    log_path = os.path.join(work, f"compare-tp{tp}.log")
    cmd = [sys.executable, os.path.abspath(__file__), "--child-compare",
           "--work", work, "--gguf", gguf, "--tp", str(tp),
           "--seed", str(plan["seed"]), "--platform", plan["platform"],
           "--max-seq-len", str(plan["max_seq_len"]),
           "--prompt-tokens", str(plan["compare_prompt"])]
    device = None
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(cmd, env=child_env(plan["env"]), cwd=REPO,
                                stdout=subprocess.PIPE, stderr=log)
        try:
            for raw in proc.stdout:
                line = raw.decode(errors="replace").rstrip("\n")
                print(line, flush=True)
                if line.startswith("{"):
                    rec = json.loads(line)
                    if rec.get("phase") == "child_device":
                        device = {k: rec[k] for k in ("platform", "kind",
                                                      "count")}
            rc = proc.wait()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    if rc != 0 or device is None:
        with open(log_path, errors="replace") as f:
            sys.stderr.write("".join(f.readlines()[-60:]))
        raise SmokeFailure(f"the compare child (tp={tp}) exited {rc}")
    return device


# ---------------------------------------------------------------------------
# the parent
# ---------------------------------------------------------------------------

def clear_native_build() -> None:
    """The dequant and grammar libraries are built on THIS machine from
    native/*.cpp by whoever needs them first: a .so carried over from
    another disk (the Makefile builds with -march=native) is never what
    ran."""
    shutil.rmtree(os.path.join(REPO, "native", "build"), ignore_errors=True)


def run(args, work: str) -> dict:
    import importlib.metadata as md
    rehearse = args.rehearse
    w = TOY if rehearse else WIDTHS
    platform = "cpu" if rehearse else "tpu"
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    say(phase="start", chips=args.chips, seed=args.seed, widths=w,
        versions=versions, rehearsal=rehearse,
        compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(REPO, ".xla_cache"))
    dropped = sorted(k for k in SERVING_ENV if k in os.environ)
    if dropped:
        say(phase="env", dropped_serving_knobs=dropped)

    child_env = {"TPU_EXPECT_PLATFORM": platform}
    if rehearse:
        child_env.update(
            JAX_PLATFORMS="cpu", OLLAMA_TPU_KERNELS="interpret",
            TPU_MIN_PREFILL_BUCKET="16",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={args.chips}")
    # max_seq_len, stream prompt tokens, new tokens a stream, compare prompt
    if rehearse:
        max_seq, streams, new, cmp_prompt = 128, [16, 24, 40, 60], 16, 64
    elif args.chips == 4:
        # the mesh path and what it is compared with, nothing else: a short
        # context keeps the warm plan (programs per bucket) small
        max_seq, streams, new, cmp_prompt = 256, [16, 60, 120, 180], 32, 128
    else:
        max_seq, new, cmp_prompt = args.max_seq_len, 64, 256
        streams = [n for n in (16, 48, 100, 200, 333, 500, 750, 1000)
                   if n + new < max_seq]
    serving = ["--max-seq-len", str(max_seq)] if max_seq != 4096 else []
    if max_seq != 4096 and not rehearse:
        say(phase="config", max_seq_len=max_seq,
            note="lowered from the server's default 4096 (the model's own "
                 "limit is 2048) so that a cold warm plan fits the time "
                 "limit; the warm-up itself stays on")
    if args.chips == 4:
        serving += ["--tp", "4"]
    if rehearse:    # stand in for what the chip's defaults resolve
        serving += ["--dtype", "int8", "--kv-dtype", "int8", "--paged",
                    "--page-size", "16", "--max-slots", "8",
                    "--decode-chunk", "8"]
    quant_params = (w["layers"] * (2 * w["dim"] * w["heads"] * w["head_dim"]
                                   + 2 * w["dim"] * w["kv_heads"]
                                   * w["head_dim"] + 3 * w["dim"] * w["ffn"])
                    + w["vocab"] * w["dim"])
    plan = dict(
        chips=args.chips, platform=platform, seed=args.seed, env=child_env,
        max_seq_len=max_seq, load_timeout_s=args.load_timeout,
        stream_prompts=streams, stream_new=new,
        followup_of=len(streams) - 1, compare_prompt=cmp_prompt,
        weight_bytes_floor=quant_params,    # one byte a quantized weight
        resolved=dict(serving_dtype="int8", kv_dtype="int8", paged=True,
                      decode_chunk=8 if rehearse else 32,
                      max_slots=8 if rehearse else 64,
                      page_size=16 if rehearse else 128))

    clear_native_build()
    # the server first: it reaches the chip (or fails to) while the
    # checkpoint is being written
    srv = Server(work, serving, child_env, "server.log")
    try:
        gguf = os.path.join(work, "smoke.gguf")
        t0 = time.monotonic()
        info = write_gguf(gguf, w, args.seed, tick=srv.alive)
        say(phase="synthesize", seconds=round(time.monotonic() - t0, 1),
            **info)
        say(phase="server_start", argv=srv.cmd[1:],
            ready_s=round(srv.wait_ready(300), 1))
        serve_and_check(gguf, plan, srv)
        rc = srv.stop()
        need(rc == 0, f"the server exited with code {rc} on SIGTERM")
        mem = srv.shutdown_event("device_memory")
        need(mem is not None, "the server's shutdown dump has no "
                              "device_memory event")
        say(phase="server_exit", rc=rc, devices=mem["devices"])
    except Exception:
        srv.kill()
        sys.stderr.write(f"--- server log tail ---\n{srv.tail()}\n")
        raise
    finally:
        srv.kill()

    # the chip is free again: one holder at a time
    return run_child(work, gguf, plan, tp=args.chips)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-seq-len", type=int, default=4096,
                    help="the server's --max-seq-len (one-chip run)")
    ap.add_argument("--load-timeout", type=float, default=1500.0,
                    help="seconds the load (cold warm plan included) may "
                         "take")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy widths, interpret-mode kernels; proves "
                         "nothing about the chip and prints no ok line")
    # the compare child (started by this script, not by hand)
    ap.add_argument("--child-compare", action="store_true",
                    help=argparse.SUPPRESS)
    for name, typ in (("--work", str), ("--gguf", str), ("--tp", int),
                      ("--platform", str), ("--prompt-tokens", int)):
        ap.add_argument(name, type=typ, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child_compare:
        try:
            return child_compare(args)
        except SmokeFailure as e:
            print(f"chip_smoke compare child: {e}", file=sys.stderr)
            return 1

    # outside the checkout, and gone at exit: a 1 GB checkpoint left in
    # the repo would make the tree too large to copy to the chip
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.monotonic()
    try:
        device = run(args, work)
        say(phase="done", seconds=round(time.monotonic() - t0, 1))
        if not args.rehearse:
            need(device["platform"] == "tpu"
                 and device["count"] == args.chips, f"ran on {device}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    except ImportError as e:
        print(f"chip_smoke: FAILED: {e} (the script drives the repo it "
              f"lies in; alone it has nothing to run)", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    if args.rehearse:
        print("chip_smoke: rehearsal passed (no chip was involved)",
              file=sys.stderr)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
