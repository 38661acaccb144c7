"""Model manager + Ollama-compatible HTTP server (stdlib, threaded).

This is the API surface the reference's probes and clients rely on
(/root/reference/pkg/model/pod.go:41-64 probes /api/tags;
docs/pages/en/guide/getting-started.md:129-150 uses /api/generate and
/v1/chat/completions) — served by a JAX/TPU engine instead of llama.cpp:

  GET  /                      liveness banner
  GET  /api/version
  GET  /api/tags              local model list
  POST /api/pull              streaming pull progress (NDJSON)
  POST /api/generate          streaming generation (NDJSON)
  POST /api/chat              chat-templated generation (NDJSON)
  POST /api/show              modelfile/template/params/details
  POST /api/create            build a model from a Modelfile
  POST /api/copy, /api/delete, GET /api/ps
  POST /api/embeddings, /api/embed
  POST /v1/chat/completions, /v1/completions, GET /v1/models   (OpenAI)
  GET  /metrics               Prometheus (tok/s, TTFT — SURVEY.md §5 gap)
  GET  /healthz, /readyz

One model is resident at a time (each Model CR gets its own Deployment in
the operator design, mirroring the reference's per-model pods); naming a
different model swaps it in under a lock.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
import os
import queue
import threading
import time
from datetime import datetime, timezone
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from .. import __version__
from ..gguf.reader import GGUFFile
from ..gguf.transcode import load_model as transcode_load
from ..runtime.engine import EngineConfig, resolve_serving_defaults
from ..runtime.admission import TenantRateLimited, tenant_from_key
from ..runtime.errors import BadRequest, DeadlineExceeded, FollowerLost
from ..runtime.scheduler import SchedulerBroken, SchedulerBusy
from ..runtime.service import LoadedModel
from ..runtime.trace import FLIGHT, TRACER, fold_stages, span
from ..tokenizer import Tokenizer
from .metrics import GLOBAL as METRICS
from .modelfile import Modelfile, parse_modelfile, params_json
from .names import ModelName
from .registry import (MT_ADAPTER, MT_LICENSE, MT_MODEL, MT_PARAMS,
                       MT_PROJECTOR,
                       MT_SYSTEM, MT_TEMPLATE, ModelStore, RegistryClient,
                       RegistryError)


def _decode_images(images):
    """Ollama API images: list of base64 strings → uint8 [H, W, 3] arrays
    (PIL handles the container format). None/[] → None."""
    if not images:
        return None
    import base64
    import io
    from PIL import Image
    out = []
    for b64 in images:
        try:
            raw = base64.b64decode(b64) if isinstance(b64, str) else bytes(b64)
            im = Image.open(io.BytesIO(raw)).convert("RGB")
        except Exception as e:
            raise BadRequest(f"invalid image: {e}") from e
        out.append(np.asarray(im, np.uint8))
    return out


def _now_iso() -> str:
    return datetime.now(timezone.utc).isoformat()


# streaming-coalescing defaults: flush a frame every N tokens or T ms,
# whichever comes first (the first piece always flushes immediately — it
# carries TTFT). N=16 halves frame count at decode_chunk=8 and is a no-op
# relative to chunking at decode_chunk=32; 25 ms keeps perceived latency
# below a display refresh even when tokens trickle.
STREAM_FLUSH_TOKENS = 16
STREAM_FLUSH_MS = 25.0


def resolve_stream_flush(options: Optional[Dict]) -> Tuple[int, float]:
    """(tokens-per-frame, seconds-between-frames) for stream coalescing.

    Request options (`stream_flush_tokens`, `stream_flush_ms`) override
    the env (TPU_STREAM_FLUSH_TOKENS / TPU_STREAM_FLUSH_MS), which
    overrides the defaults. `stream_flush_tokens: 1` restores per-piece
    frames."""
    o = options or {}
    try:
        n = int(o.get("stream_flush_tokens",
                      os.environ.get("TPU_STREAM_FLUSH_TOKENS",
                                     STREAM_FLUSH_TOKENS)))
    except (TypeError, ValueError):
        n = STREAM_FLUSH_TOKENS
    try:
        ms = float(o.get("stream_flush_ms",
                         os.environ.get("TPU_STREAM_FLUSH_MS",
                                        STREAM_FLUSH_MS)))
    except (TypeError, ValueError):
        ms = STREAM_FLUSH_MS
    return max(1, n), max(0.0, ms) / 1000.0


class _StreamCoalescer:
    """Batches streamed text pieces into wire frames.

    The first piece flushes immediately (it is the TTFT token); after
    that a frame goes out every `max_tokens` tokens or `max_s` seconds,
    whichever comes first. Frames are assembled from pre-serialised
    invariant byte fragments into one reused per-request buffer, so the
    steady-state cost per frame is one strftime-free timestamp, one
    json.dumps of the text, and one socket write."""

    def __init__(self, chunk_fn, make_frame, max_tokens: int, max_s: float,
                 trace=None):
        self._chunk = chunk_fn
        self._make = make_frame
        self.max_tokens = max_tokens
        self.max_s = max_s
        self._parts = []
        self._ntok = 0
        self._t_last = None     # None → flush the first piece immediately
        self.frames = 0
        # request span timeline (runtime/trace.py) — the HTTP flush is
        # the last hop of the request's path, stamped per frame
        self._trace = trace

    def add(self, piece: str):
        self._parts.append(piece)
        self._ntok += getattr(piece, "n_tokens", 1)
        now = time.monotonic()
        if (self._t_last is None or self._ntok >= self.max_tokens
                or now - self._t_last >= self.max_s):
            self.flush(now)

    def flush(self, now: Optional[float] = None):
        if not self._parts:
            return
        text = "".join(self._parts)
        n_tok = self._ntok
        self._parts.clear()
        self._ntok = 0
        self._t_last = time.monotonic() if now is None else now
        # stamps the request's timeline too ("http_flush", as ever)
        with span("http.flush", self._trace, n_tokens=n_tok,
                  chars=len(text)):
            self._chunk(self._make(text))
        self.frames += 1
        METRICS.inc("tpu_model_stream_frames_total")


def _fmt_params(n: int) -> str:
    if n >= 1e9:
        return f"{n / 1e9:.1f}B"
    return f"{n / 1e6:.0f}M"


class ApiError(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status


def parse_keep_alive(v) -> Optional[float]:
    """Ollama keep_alive → seconds (None = keep forever).

    Accepts numbers (seconds; negative = forever) and Go-style duration
    strings ("5m", "1h30m", "300ms", "-1"). 0 means "unload as soon as
    idle"."""
    if v is None:
        raise BadRequest("keep_alive is None")
    if isinstance(v, bool):
        raise BadRequest(f"bad keep_alive {v!r}")
    if isinstance(v, (int, float)):
        if not math.isfinite(v):
            raise BadRequest(f"bad keep_alive {v!r}")
        return None if v < 0 else float(v)
    s = str(v).strip()
    if not s:
        raise BadRequest("empty keep_alive")
    try:
        n = float(s)
        if not math.isfinite(n):
            raise ValueError
        return None if n < 0 else n
    except ValueError:
        pass
    import re
    m = re.fullmatch(r"(-?)((?:\d+(?:\.\d+)?(?:ns|us|µs|ms|s|m|h))+)", s)
    if not m:
        raise BadRequest(f"bad keep_alive {v!r}")
    if m.group(1):
        return None
    unit_s = {"ns": 1e-9, "us": 1e-6, "µs": 1e-6, "ms": 1e-3,
              "s": 1.0, "m": 60.0, "h": 3600.0}
    total = 0.0
    for num, unit in re.findall(r"(\d+(?:\.\d+)?)(ns|us|µs|ms|s|m|h)", s):
        total += float(num) * unit_s[unit]
    return total


class ModelManager:
    """Owns the blob store, registry client, and the resident model."""

    def __init__(self, store_root: str, cache_dir: Optional[str] = None,
                 mesh=None, ecfg: Optional[EngineConfig] = None,
                 engine_dtype="bfloat16", serve_models: bool = True,
                 default_keep_alive=None, control_plane=None,
                 follower: bool = False):
        self.store = ModelStore(store_root)
        self.client = RegistryClient(self.store)
        self.mesh = mesh
        self.ecfg = ecfg
        self.cache_dir = cache_dir
        self.engine_dtype = engine_dtype
        self.serve_models = serve_models  # store-only mode serves pulls only
        # multi-host slice roles (runtime/follower.py): the leader's
        # control plane broadcasts load/unload + engine calls; a follower
        # manager builds bare engines (no scheduler/HTTP) and replays
        self.control_plane = control_plane
        self.follower = follower
        self.loaded: Optional[LoadedModel] = None
        self._lock = threading.Lock()
        self.start_time = time.time()
        # keep_alive: model idle-unload timer (the reference's engine keeps
        # this inside `ollama serve`; OLLAMA_KEEP_ALIVE is its env knob)
        import os
        raw_ka = (default_keep_alive if default_keep_alive is not None
                  else (os.environ.get("OLLAMA_KEEP_ALIVE") or "5m"))
        try:
            self.default_keep_alive = parse_keep_alive(raw_ka)
        except ValueError:
            # a malformed env var must not keep the pod from booting
            import sys
            print(f"warning: invalid OLLAMA_KEEP_ALIVE {raw_ka!r}; "
                  f"using 5m", file=sys.stderr)
            self.default_keep_alive = 300.0
        self.expires_at: Optional[float] = None
        self._last_ka: Optional[float] = self.default_keep_alive
        self._reaper_stop = threading.Event()
        # graceful drain (SIGTERM / preStop): /readyz flips 503 so the
        # Service pulls this endpoint, new submits shed 503+Retry-After,
        # running streams finish within TPU_DRAIN_TIMEOUT_S
        self.draining = False
        # followers unload on the leader's ("unload",) broadcast, never on
        # their own clock
        if serve_models and not follower:
            self._reaper = threading.Thread(
                target=self._reap_idle, daemon=True, name="keepalive-reaper")
            self._reaper.start()

    # ------------------------------------------------------------------
    def touch(self, keep_alive=None):
        """Reset the loaded model's idle-unload deadline (called per
        request; an explicit request keep_alive overrides the default)."""
        ka = self.default_keep_alive
        if keep_alive is not None:
            try:
                ka = parse_keep_alive(keep_alive)
            except ValueError:
                raise ApiError(400, f"invalid keep_alive "
                                    f"{keep_alive!r}") from None
        with self._lock:
            self._last_ka = ka
            self.expires_at = None if ka is None else time.monotonic() + ka

    def _reap_idle(self):
        while not self._reaper_stop.wait(1.0):
            with self._lock:
                lm = self.loaded
                exp = self.expires_at
                if (lm is None or exp is None or time.monotonic() < exp):
                    continue
                # only unload a quiet model: active slots / queued requests
                # push the actual unload past the deadline
                if lm.scheduler.has_pending:
                    continue
                # deadline is armed at request START; a generation longer
                # than keep_alive must still get its full idle window after
                # it finishes (stock server re-arms at completion)
                if self._last_ka is not None and lm.scheduler.finished:
                    last_done = lm.scheduler.finished[-1].t_done
                    if time.monotonic() < last_done + self._last_ka:
                        continue
                self.loaded = None
                self.expires_at = None
            lm.unload()  # outside the lock: shutdown joins the decode loop

    def stop(self, ref: str) -> bool:
        """keep_alive: 0 with an empty prompt — the `ollama stop` path.
        Unloads now when idle; with requests in flight it only expires the
        deadline so the reaper unloads after they drain (stock server never
        truncates other clients' generations). Returns True if ``ref`` is
        the resident model."""
        name = ModelName.parse(ref)
        with self._lock:
            lm = self.loaded
            if lm is None or lm.name != name.short:
                return False
            if lm.scheduler.has_pending:
                self._last_ka = 0.0
                self.expires_at = time.monotonic()  # reap once drained
                return True
            self.loaded = None
            self.expires_at = None
        lm.unload()
        return True

    def unload_now(self):
        """Immediate unload (follower replay of the leader's unload)."""
        with self._lock:
            lm, self.loaded = self.loaded, None
            self.expires_at = None
        if lm is not None:
            lm.unload()

    def shutdown(self):
        self._reaper_stop.set()

    def begin_drain(self):
        """Enter draining: readiness goes 503 (the operator's Service
        stops routing here), the scheduler sheds new submits, running
        streams keep generating. Idempotent. A draining replica is a
        scale-down or scale-to-zero candidate, so the AOT warm state is
        snapshotted to the shared cache volume here — the next wake
        restores it instead of recompiling the warm plan."""
        with self._lock:
            already, self.draining = self.draining, True
            lm = self.loaded
        if not already:
            FLIGHT.record("drain", phase="manager",
                          model=lm.name if lm is not None else None)
        if lm is not None:
            lm.scheduler.begin_drain()
            if not already and hasattr(lm, "save_warm_snapshot"):
                lm.save_warm_snapshot()
            # hottest KV prefixes ride along to the shared volume: the
            # next wake (any replica of this digest) imports them and
            # serves shared-prefix traffic as warm tier-2 hits
            if not already and hasattr(lm, "save_prefix_snapshot"):
                lm.save_prefix_snapshot()

    def drain(self, timeout_s: Optional[float] = None) -> int:
        """Graceful drain for SIGTERM: begin_drain(), then let the
        resident model's streams finish within ``timeout_s`` (default
        TPU_DRAIN_TIMEOUT_S) before stragglers are shed. Returns the
        straggler count."""
        self.begin_drain()
        with self._lock:
            lm = self.loaded
        if lm is None:
            return 0
        return lm.scheduler.drain(timeout_s)

    # ------------------------------------------------------------------
    def model_details(self, name: ModelName) -> Dict:
        out = {"format": "gguf", "family": "", "families": None,
               "parameter_size": "", "quantization_level": ""}
        try:
            layers = self.store.model_layers(name)
            path = layers.get(MT_MODEL)
            if path:
                with GGUFFile(path) as f:
                    out["family"] = f.arch
                    out["families"] = [f.arch]
                    cnt = f.metadata.get("general.parameter_count")
                    if cnt:
                        out["parameter_size"] = _fmt_params(int(cnt))
                    ft = f.metadata.get("general.file_type")
                    ftypes = {0: "F32", 1: "F16", 2: "Q4_0", 3: "Q4_1",
                              7: "Q8_0", 8: "Q5_0", 9: "Q5_1", 10: "Q2_K",
                              11: "Q3_K_S", 12: "Q3_K_M", 13: "Q3_K_L",
                              14: "Q4_K_S", 15: "Q4_K_M", 16: "Q5_K_S",
                              17: "Q5_K_M", 18: "Q6_K"}
                    if ft is not None:
                        out["quantization_level"] = ftypes.get(ft, str(ft))
        except (RegistryError, OSError, ValueError):
            pass
        return out

    def list_models(self):
        models = []
        for m in self.store.list_models():
            name: ModelName = m["name"]
            digest = (m["manifest"].get("config", {}) or {}).get("digest", "")
            models.append({
                "name": name.short, "model": name.short,
                "modified_at": datetime.fromtimestamp(
                    m["modified_at"], timezone.utc).isoformat(),
                "size": m["size"],
                "digest": digest.replace("sha256:", ""),
                "details": self.model_details(name),
            })
        return models

    def _read_layer_text(self, layers: Dict[str, str], mt: str
                         ) -> Optional[str]:
        path = layers.get(mt)
        if not path:
            return None
        try:
            with open(path, "r", errors="replace") as f:
                return f.read()
        except OSError:
            return None

    def load(self, ref: str) -> LoadedModel:
        if not self.serve_models:
            raise ApiError(503, "this instance is a model store; it serves "
                                "pulls, not inference")
        name = ModelName.parse(ref)
        with self._lock:
            if self.loaded is not None and self.loaded.name == name.short:
                if not self.loaded.scheduler.broken:
                    return self.loaded
                # broken scheduler (decode-loop gave up after repeated
                # failures): tear down and fall through to a fresh load so
                # a transient TPU/XLA fault doesn't wedge the pod forever
                self.loaded.unload()
                self.loaded = None
            layers = self.store.model_layers(name)  # raises if absent
            gguf_path = layers.get(MT_MODEL)
            if not gguf_path:
                raise ApiError(500, f"model {name.short} has no model layer")
            digest = self.store.model_digest(name) or ""
            import jax
            import ml_dtypes
            # ONE header open serves the arch probe, the encoder load, and
            # the auto-dtype config read (re-parsing multi-MB tokenizer
            # metadata per question would tax every model switch)
            from ..gguf.reader import GGUFFile as _GF
            from ..gguf.transcode import (config_from_gguf,
                                          encoder_config_from_gguf,
                                          is_encoder_arch,
                                          load_encoder_params)
            _enc = None
            _hcfg = None
            with _GF(gguf_path) as _hdr:
                if is_encoder_arch(_hdr.arch):
                    # embedding-only images (all-minilm & friends):
                    # BERT-family encoders load WITHOUT an Engine —
                    # tokenizer + one jitted bidirectional forward
                    # (runtime/service.EmbeddingModel); the reference
                    # serves these through llama.cpp's BERT path
                    ecfg2 = encoder_config_from_gguf(_hdr)
                    _enc = (ecfg2, load_encoder_params(_hdr, ecfg2),
                            {k: v for k, v in _hdr.metadata.items()
                             if k.startswith("tokenizer.")})
                elif self.engine_dtype is None:
                    _hcfg = config_from_gguf(_hdr)
            if _enc is not None:
                from ..runtime.service import EmbeddingModel
                ecfg2, eparams, tok_md = _enc
                if self.loaded is not None:
                    self.loaded.unload()
                    self.loaded = None
                if self.control_plane is not None:
                    self.control_plane.broadcast(("load", ref))
                self.loaded = EmbeddingModel(
                    name.short, ecfg2, eparams,
                    Tokenizer.from_gguf_metadata(tok_md), digest=digest)
                self.loaded.serving_dtype = "float32"
                self._last_ka = self.default_keep_alive
                self.expires_at = (None if self.default_keep_alive is None
                                   else time.monotonic()
                                   + self.default_keep_alive)
                return self.loaded
            engine_dtype = self.engine_dtype
            if engine_dtype is None:
                # no CR quantization / --dtype: resolve the measured
                # serving dtype PER MODEL from the GGUF header (int8 ≤4B,
                # int4 7B+, bf16 MoE on TPU; f32 on CPU) so `kubectl
                # apply` of a bare Model CR serves the config the bench
                # proves, not an unmeasured bf16 one (VERDICT r4 #3)
                from ..runtime.engine import resolve_engine_dtype
                engine_dtype = resolve_engine_dtype(
                    _hcfg, jax.default_backend())
                import sys
                print(f"serving dtype for {name.short}: {engine_dtype} "
                      f"({_hcfg.n_params/1e9:.2f}B params, auto)",
                      file=sys.stderr)
            dt = {"bfloat16": ml_dtypes.bfloat16, "int8": ml_dtypes.bfloat16,
                  "int4": ml_dtypes.bfloat16,
                  "float32": np.float32}[engine_dtype]
            if (jax.default_backend() == "cpu"
                    and dt is ml_dtypes.bfloat16):
                # this XLA CPU build cannot execute bf16 dots
                # (DotThunk UNIMPLEMENTED) — CPU serving runs f32
                dt = np.float32
            # parse/transcode the new model (host memory) BEFORE tearing the
            # old one down: a corrupt pull must not leave the server empty
            t_load = [time.perf_counter()]   # phase boundaries, in order
            cfg, params, tok_md = transcode_load(
                gguf_path, cache_dir=self.cache_dir, dtype=dt,
                digest=digest.replace("sha256:", "")[:24] or None)
            t_load.append(time.perf_counter())
            adapter_path = layers.get(MT_ADAPTER)
            if adapter_path:
                # Modelfile ADAPTER: merge W += (alpha/r)·BA host-side so
                # serving runs unmodified fused matmuls (gguf/lora.py);
                # must happen before int8 weight quantization below
                from ..gguf.lora import apply_lora
                try:
                    params = apply_lora(params, cfg, adapter_path)
                except ValueError as e:
                    raise ApiError(400, f"adapter: {e}") from e
            tokenizer = Tokenizer.from_gguf_metadata(tok_md)
            template = self._read_layer_text(layers, MT_TEMPLATE)
            system = self._read_layer_text(layers, MT_SYSTEM)
            params_raw = self._read_layer_text(layers, MT_PARAMS)
            default_params = json.loads(params_raw) if params_raw else {}
            if self.loaded is not None:
                self.loaded.unload()
                self.loaded = None
            import jax.numpy as jnp
            # (auto resolution never picks int8/int4 for MoE — explicit
            # spec.quantization on an MoE model keeps its old behavior)
            if engine_dtype in ("int8", "int4"):
                # weight-only quantization: int8/packed-int4 weights stay
                # quantized in HBM; dequant fuses into the matmuls
                # (ops/quant.py)
                from ..ops.quant import quantize_params
                params = quantize_params(
                    params, bits=4 if engine_dtype == "int4" else 8)
            t_load.append(time.perf_counter())
            if self.mesh is None:
                params = jax.tree_util.tree_map(jnp.asarray, params)
                jax.block_until_ready(params)
            # (on a mesh the engine places every leaf straight into its
            # sharding from host memory: staging the tree here first
            # would park a whole copy of the weights on device 0)
            t_load.append(time.perf_counter())
            vision = None
            proj_path = layers.get(MT_PROJECTOR)
            if proj_path:
                # llava-family mmproj layer: CLIP tower + MLP projector
                from ..gguf.reader import GGUFFile
                from ..gguf.transcode import (load_vision_params,
                                              vision_config_from_gguf)
                with GGUFFile(proj_path) as vf:
                    vcfg = vision_config_from_gguf(vf)
                    vparams = load_vision_params(vf, vcfg, dtype=dt)
                vision = (vcfg, jax.tree_util.tree_map(jnp.asarray, vparams))
            ecfg = self.ecfg or EngineConfig(
                max_seq_len=min(cfg.max_seq_len,
                                int(default_params.get("num_ctx", 4096))))
            # tri-state serving defaults, resolved per model: paged for
            # GQA on TPU (measured 2x the dense aggregate), dense for
            # MHA/MoE/CPU, pool capped at the old dense-8 HBM ceiling
            ecfg = resolve_serving_defaults(ecfg, cfg, self.mesh)
            if self.control_plane is not None:
                # followers pull the same layers from their own store and
                # replay this load; their first mirrored engine call
                # queues behind it on the FIFO control stream
                self.control_plane.broadcast(("load", ref))
            self.loaded = LoadedModel(
                name.short, cfg, params, tokenizer, template=template,
                system=system, default_params=default_params,
                mesh=self.mesh, ecfg=ecfg, digest=digest, vision=vision,
                control_plane=self.control_plane, follower=self.follower,
                warm_cache_dir=self.cache_dir)
            # effective serving config, for /api/ps observability (the
            # auto-resolved dtype is otherwise invisible to clients)
            self.loaded.serving_dtype = engine_dtype
            t_load.append(time.perf_counter())
            self._record_load(name.short, engine_dtype, ecfg, t_load)
            # fresh deadline under this same lock: a stale expiry from the
            # previous model must never reap the one we just installed
            self._last_ka = self.default_keep_alive
            self.expires_at = (None if self.default_keep_alive is None
                               else time.monotonic() + self.default_keep_alive)
            return self.loaded

    @staticmethod
    def _record_load(model: str, serving_dtype: str, ecfg: EngineConfig,
                     t_load) -> None:
        """One flight-recorder event per model load: the serving config
        the tri-state defaults resolved to, the seconds each phase took,
        which dequant path ran, and the bytes every local device holds
        now (GET /debug/events?kind=model_load). quantize_s spans all
        host work between transcode and upload (adapter merge and
        tokenizer build too). The warm plan's own event (kind=warm_plan)
        sits just before it."""
        from ..gguf import native
        cache_dt = ecfg.cache_dtype
        phases = [round(b - a, 3) for a, b in zip(t_load, t_load[1:])]
        FLIGHT.record(
            "model_load", model=model, serving_dtype=serving_dtype,
            kv_dtype=(cache_dt if isinstance(cache_dt, str)
                      else np.dtype(cache_dt).name),
            paged=bool(ecfg.paged), max_slots=ecfg.max_slots,
            page_size=ecfg.page_size, n_pages=ecfg.n_pages,
            decode_chunk=ecfg.decode_chunk, max_seq_len=ecfg.max_seq_len,
            transcode_s=phases[0], quantize_s=phases[1],
            upload_s=phases[2], engine_warm_s=phases[3],
            dequant=native.path_used(), devices=device_memory())

    def require_loaded(self, ref: str, keep_alive=None) -> LoadedModel:
        ka = self.default_keep_alive
        if keep_alive is not None:
            try:
                ka = parse_keep_alive(keep_alive)
            except ValueError:
                raise ApiError(400, f"invalid keep_alive "
                                    f"{keep_alive!r}") from None
        for _ in range(3):
            try:
                lm = self.load(ref)
            except RegistryError as e:
                raise ApiError(404, str(e)) from e
            # arm the deadline under the same lock the reaper tests — if
            # the reaper unloaded between load() returning and here, retry
            # instead of handing out a shut-down scheduler
            with self._lock:
                if self.loaded is lm:
                    self._last_ka = ka
                    self.expires_at = (None if ka is None
                                       else time.monotonic() + ka)
                    return lm
        raise ApiError(503, f"model {ref!r} kept unloading during load "
                            f"(keep_alive too short?)")

    def ps(self):
        out = []
        with self._lock:
            lm = self.loaded
        if lm is not None:
            with self._lock:
                exp = self.expires_at
            if exp is None:
                expires = "0001-01-01T00:00:00Z"  # keep_alive < 0: forever
            else:
                wall = time.time() + (exp - time.monotonic())
                expires = datetime.fromtimestamp(
                    wall, timezone.utc).isoformat()
            out.append({
                "name": lm.name, "model": lm.name,
                "size": int(lm.cfg.n_params * 2),
                "digest": lm.digest.replace("sha256:", ""),
                "details": {"format": "gguf", "family": lm.cfg.arch,
                            "parameter_size": _fmt_params(lm.cfg.n_params),
                            "serving_dtype": getattr(lm, "serving_dtype",
                                                     None),
                            # embedding models carry no engine
                            "decode_chunk": (lm.engine.ecfg.decode_chunk
                                             if getattr(lm, "engine", None)
                                             is not None else None),
                            "paged": (bool(lm.engine.paged)
                                      if getattr(lm, "engine", None)
                                      is not None else False),
                            # routed experts: all the router scores, and
                            # those this chip holds (0/0 = a dense MLP)
                            # (an embedding model's config has neither)
                            "experts": {
                                "all": getattr(lm.cfg, "n_experts", 0),
                                "held": getattr(lm.cfg, "experts_held", 0)},
                            # hybrid stacks: bytes of the slots' recurrent
                            # state (0 = keys and values only)
                            "recurrent_state_bytes": (
                                int(getattr(lm.engine, "state_bytes", 0))
                                if getattr(lm, "engine", None)
                                is not None else 0)},
                "expires_at": expires,
                "size_vram": 0,
                # crash-only serving status: supervised restarts on THIS
                # scheduler object plus process-lifetime failure counters
                # (the same series /metrics exports)
                "failures": {
                    "broken": bool(lm.scheduler.broken),
                    "engine_restarts": lm.scheduler.n_restarts,
                    "request_timeouts": int(METRICS.get(
                        "tpu_model_request_timeouts_total")),
                    "requests_shed": int(METRICS.get(
                        "tpu_model_requests_shed_total")),
                    "followers_lost": int(METRICS.get(
                        "tpu_model_followers_lost_total")),
                },
                # stall-free batching telemetry: last launch-to-host ms
                # per device program kind, plus process-lifetime admission
                # counters (same series /metrics exports)
                "dispatch": {
                    # whether decode double-buffers (false = forced sync:
                    # TPU_ASYNC_DISPATCH=0 or paged dp>1; the per-dispatch
                    # grammar fallback counts in
                    # tpu_model_async_fallback_total, not here)
                    "async": bool(lm.scheduler.async_dispatch),
                    "dispatch_ms": (dict(lm.engine.dispatch_ms)
                                    if getattr(lm, "engine", None)
                                    is not None else {}),
                    "prefill_chunks": int(METRICS.get(
                        "tpu_model_prefill_chunks_total")),
                    "admission_stall_ms": METRICS.get(
                        "tpu_model_admission_stall_ms_total"),
                },
                # radix prefix cache: process-lifetime hit/miss token
                # counters + live tree residency (same series /metrics
                # exports; nodes/pages are 0 when the cache is off)
                "prefix_cache": {
                    "enabled": bool(getattr(lm, "engine", None) is not None
                                    and getattr(lm.engine, "radix_enabled",
                                                False)),
                    "hit_tokens": int(METRICS.get(
                        "tpu_model_prefix_hit_tokens_total")),
                    "miss_tokens": int(METRICS.get(
                        "tpu_model_prefix_miss_tokens_total")),
                    "radix_nodes": (int(lm.engine.radix_nodes)
                                    if getattr(lm, "engine", None)
                                    is not None else 0),
                    "radix_pages": (int(lm.engine.radix_pages)
                                    if getattr(lm, "engine", None)
                                    is not None else 0),
                    # tiered residency: HBM pages (tier 0) vs spilled
                    # pages pinned in the host arena (tier 1/2), plus the
                    # arena byte occupancy against its capacity — all 0
                    # when TPU_HOST_CACHE_GB is unset
                    "tiers": {
                        "hbm_pages": (int(lm.engine.radix_pages)
                                      if getattr(lm, "engine", None)
                                      is not None else 0),
                        "host_pages": (int(lm.engine.host_cache_pages)
                                       if getattr(lm, "engine", None)
                                       is not None else 0),
                        "host_bytes": (int(lm.engine.host_cache_used_bytes)
                                       if getattr(lm, "engine", None)
                                       is not None else 0),
                        "host_capacity_bytes": (
                            int(lm.engine.host_cache_capacity_bytes)
                            if getattr(lm, "engine", None)
                            is not None else 0),
                    },
                },
                # overload discipline: live admission-policy snapshot —
                # per-class queue depth / token backlog, WDRR tenant
                # state, throttles, and the knobs in force (empty for
                # encoder models, which have no waiting line)
                "admission": lm.scheduler.admission_stats(),
                # lifecycle: serving/draining/broken state, the restart-
                # replay budget in force, and hung-dispatch watchdog
                # posture (empty for encoder models)
                "lifecycle": lm.scheduler.lifecycle_stats(),
                # utilization accounting (runtime/accounting.py): 60s
                # MFU/goodput/occupancy window, dispatch-wait/host/idle
                # breakdown, and mid-serving recompile counts — the
                # block the operator mirrors into the Model CR status
                # (empty for encoder models)
                "utilization": lm.scheduler.utilization_stats(),
            })
        return out

    # -- model management ----------------------------------------------
    def show(self, ref: str) -> Dict:
        name = ModelName.parse(ref)
        manifest = self.store.read_manifest(name)
        if manifest is None:
            raise ApiError(404, f"model {name.short!r} not found")
        layers = self.store.model_layers(name)
        template = self._read_layer_text(layers, MT_TEMPLATE) or ""
        system = self._read_layer_text(layers, MT_SYSTEM) or ""
        params_raw = self._read_layer_text(layers, MT_PARAMS)
        lic = self._read_layer_text(layers, MT_LICENSE) or ""
        mf = Modelfile(from_=name.short, template=template or None,
                       system=system or None,
                       adapter=layers.get(MT_ADAPTER))
        parameters = ""
        if params_raw:
            try:
                pj = json.loads(params_raw)
                mf.parameters = pj
                parameters = "\n".join(
                    f"{k:30s} {item}" for k, v in sorted(pj.items())
                    for item in (v if isinstance(v, list) else [v]))
            except json.JSONDecodeError:
                pass
        info = {}
        path = layers.get(MT_MODEL)
        if path:
            try:
                with GGUFFile(path) as f:
                    info = {k: v for k, v in f.metadata.items()
                            if not isinstance(v, list) or len(v) < 64}
            except (OSError, ValueError):
                pass
        capabilities = ["completion"]
        if MT_PROJECTOR in layers:
            capabilities.append("vision")   # llava-family (mmproj layer)
        return {"modelfile": mf.render(), "parameters": parameters,
                "template": template, "system": system, "license": lic,
                "details": self.model_details(name), "model_info": info,
                "capabilities": capabilities}

    def copy(self, src: str, dst: str):
        sname, dname = ModelName.parse(src), ModelName.parse(dst)
        manifest = self.store.read_manifest(sname)
        if manifest is None:
            raise ApiError(404, f"model {sname.short!r} not found")
        self.store.write_manifest(dname, manifest)

    def delete(self, ref: str):
        name = ModelName.parse(ref)
        if not self.store.delete_model(name):
            raise ApiError(404, f"model {name.short!r} not found")
        with self._lock:
            if self.loaded is not None and self.loaded.name == name.short:
                self.loaded.unload()
                self.loaded = None

    def create(self, ref: str, modelfile_text: str,
               progress=None) -> None:
        mf = parse_modelfile(modelfile_text)
        if not mf.from_:
            raise ApiError(400, "Modelfile needs a FROM line")
        name = ModelName.parse(ref)
        layers = []
        base_params: Dict = {}
        if mf.from_.startswith("@"):
            # pre-uploaded blob reference: `ollama create` rewrites a
            # local-file FROM into POST /api/blobs/<digest> + FROM @digest
            import os
            digest = mf.from_[1:]
            if not self.store.has_blob(digest):
                raise ApiError(400, f"FROM {mf.from_!r}: blob not "
                                    "uploaded (POST /api/blobs/<digest>)")
            layers.append({"mediaType": MT_MODEL, "digest": digest,
                           "size": os.path.getsize(
                               self.store.blob_path(digest))})
        elif (base_manifest := self.store.read_manifest(
                ModelName.parse(mf.from_))) is not None:
            # FROM a local model name: inherit every base layer the
            # Modelfile doesn't override (ollama keeps base template/
            # system/params on create); params merge
            overridden = set()
            if mf.template:
                overridden.add(MT_TEMPLATE)
            if mf.system:
                overridden.add(MT_SYSTEM)
            if mf.license:
                overridden.add(MT_LICENSE)
            if mf.adapter:
                overridden.add(MT_ADAPTER)
            for layer in base_manifest.get("layers", []):
                mt = layer["mediaType"]
                if mt == MT_PARAMS:
                    try:
                        with open(self.store.blob_path(layer["digest"])) as f:
                            base_params = json.load(f)
                    except (OSError, json.JSONDecodeError):
                        pass
                    continue  # re-emitted (possibly merged) below
                if mt not in overridden:
                    layers.append(layer)
        else:
            # FROM a GGUF file path on the server's filesystem
            import os
            if not os.path.exists(mf.from_):
                raise ApiError(400, f"FROM {mf.from_!r}: not a local model "
                                    "or file")
            if progress:
                progress("importing model blob", 0, 0)
            entry = self.store.add_blob_file(mf.from_)
            layers.append({"mediaType": MT_MODEL, **entry})
        if mf.template:
            layers.append({"mediaType": MT_TEMPLATE,
                           **self.store.add_blob(mf.template.encode())})
        if mf.system:
            layers.append({"mediaType": MT_SYSTEM,
                           **self.store.add_blob(mf.system.encode())})
        if mf.parameters or base_params:
            merged = dict(base_params)
            merged.update(mf.parameters or {})
            mf_merged = dataclasses.replace(mf, parameters=merged)
            layers.append({"mediaType": MT_PARAMS,
                           **self.store.add_blob(
                               params_json(mf_merged).encode())})
        if mf.license:
            layers.append({"mediaType": MT_LICENSE,
                           **self.store.add_blob(mf.license.encode())})
        if mf.adapter:
            import os
            if not os.path.exists(mf.adapter):
                raise ApiError(400, f"ADAPTER {mf.adapter!r}: no such file")
            if progress:
                progress("importing adapter", 0, 0)
            layers.append({"mediaType": MT_ADAPTER,
                           **self.store.add_blob_file(mf.adapter)})
        config = self.store.add_blob(json.dumps(
            {"model_format": "gguf"}).encode())
        manifest = {
            "schemaVersion": 2,
            "mediaType": "application/vnd.docker.distribution.manifest.v2+json",
            "config": {"mediaType": "application/vnd.docker.container.image.v1+json",
                       **config},
            "layers": layers,
        }
        self.store.write_manifest(name, manifest)
        if progress:
            progress("success", 0, 0)


# ---------------------------------------------------------------------------
# HTTP layer
# ---------------------------------------------------------------------------

class Handler(BaseHTTPRequestHandler):
    manager: ModelManager = None  # set by serve()
    protocol_version = "HTTP/1.1"
    server_version = "tpu-ollama/" + __version__
    # with a BOUNDED worker pool (_DeepStackHTTPServer), an idle
    # keep-alive connection parked on readline() must not hold a worker
    # forever — time it out and let the client reconnect
    timeout = 75

    # -- helpers --------------------------------------------------------
    def log_message(self, fmt, *args):  # quiet by default
        pass

    def _json_body(self) -> Dict:
        n = int(self.headers.get("Content-Length") or 0)
        if n == 0:
            return {}
        try:
            return json.loads(self.rfile.read(n) or b"{}")
        except json.JSONDecodeError as e:
            raise ApiError(400, f"invalid json: {e}") from e

    def _send_json(self, obj, status=200,
                   headers: Optional[Dict[str, str]] = None):
        body = json.dumps(obj).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _send_text(self, text: str, status=200,
                   ctype="text/plain; charset=utf-8"):
        body = text.encode()
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _start_stream(self, ctype="application/x-ndjson"):
        self.send_response(200)
        self.send_header("Content-Type", ctype)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        self._streaming = True
        self._stream_ctype = ctype

    def _chunk(self, data: bytes):
        self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
        self.wfile.flush()

    def _end_stream(self):
        self.wfile.write(b"0\r\n\r\n")
        self.wfile.flush()
        self._streaming = False

    def _send_error(self, message: str, status: int,
                    headers: Optional[Dict[str, str]] = None):
        """Error that is safe both before and after a stream started: once
        chunked headers are out, a second status line would corrupt the
        framing — emit the error as a final chunk instead."""
        if getattr(self, "_streaming", False):
            try:
                if getattr(self, "_stream_ctype", "") == "text/event-stream":
                    # keep SSE framing: a bare JSON line mid-stream is
                    # dropped by OpenAI SDKs and the missing [DONE] hangs them
                    self._chunk(self._sse({"error": {
                        "message": message, "type": "server_error"}}))
                    self._chunk(b"data: [DONE]\n\n")
                else:
                    self._stream_json({"error": message})
                self._end_stream()
            except (BrokenPipeError, ConnectionResetError):
                pass
        else:
            self._send_json({"error": message}, status, headers=headers)

    def _stream_json(self, obj):
        self._chunk(json.dumps(obj).encode() + b"\n")

    @staticmethod
    def _pull_first(gen):
        """Pull the FIRST (piece, final) pair before the caller commits
        200 + chunked headers. Failures that precede the first token —
        deadline shed while queued (503 + Retry-After), admission errors
        — can then surface as real HTTP status codes; once the first
        item exists the stream is committed and later failures become
        terminal frames. Returns an iterator replaying that first item."""
        it = iter(gen)
        try:
            first = next(it)
        except StopIteration:
            return iter(())
        return itertools.chain([first], it)

    def _coalescer(self, pre: bytes, mid: Optional[bytes], suf: bytes,
                   options: Optional[Dict], trace=None) -> _StreamCoalescer:
        """Frame coalescer over this response's chunked stream. A frame is
        `pre + now_iso + mid + json(text) + suf` (NDJSON; the timestamp
        is the only other varying part) or `pre + json(text) + suf` when
        ``mid`` is None (SSE chunks carry no per-frame timestamp). The
        fragments must reproduce json.dumps' default rendering of the
        full frame dict byte-for-byte — the wire format is unchanged,
        only how many tokens each frame carries."""
        n, s = resolve_stream_flush(options)
        buf = bytearray()

        def make(text: str) -> bytearray:
            buf.clear()
            buf.extend(pre)
            if mid is not None:
                # an ISO-8601 UTC timestamp is plain ASCII with no JSON
                # escapes, so splicing it raw equals json.dumps output
                buf.extend(_now_iso().encode())
                buf.extend(mid)
            buf.extend(json.dumps(text).encode())
            buf.extend(suf)
            return buf

        return _StreamCoalescer(self._chunk, make, n, s, trace=trace)

    # -- debug introspection -------------------------------------------
    def _query(self) -> Dict[str, str]:
        """Last value per key of the request's query string."""
        from urllib.parse import parse_qs
        qs = parse_qs(self.path.partition("?")[2])
        return {k: v[-1] for k, v in qs.items()}

    def _debug_trace(self):
        """Span timeline of one recent request (runtime/trace.py). With
        no id, lists the ids the tracer still holds (newest last)."""
        q = self._query()
        rid = q.get("id")
        if rid is None:
            self._send_json({"ids": TRACER.ids()})
            return
        tr = TRACER.get(rid)
        if tr is None:
            self._send_json({"error": f"no trace for id {rid!r} "
                             "(evicted, or TPU_TRACE=0)"}, 404)
            return
        self._send_json(tr.to_dict())

    def _debug_events(self):
        """The flight-recorder ring: last TPU_FLIGHT_EVENTS structured
        scheduler/engine events, oldest first. ?kind=K keeps only one
        event type (applied BEFORE the trim, so ?kind=shed&last=10 is
        the newest 10 sheds); ?last=N trims to the newest N."""
        events = FLIGHT.snapshot()
        kind = self._query().get("kind", "")
        if kind:
            events = [e for e in events if e.get("kind") == kind]
        try:
            last = int(self._query().get("last", "0"))
        except ValueError:
            last = 0
        if last > 0:
            events = events[-last:]
        self._send_json({"events": events, "dumps": FLIGHT.dumps})

    def _debug_utilization(self):
        """Per-second utilization aggregates from the loaded model's
        accounting ring (?last=N seconds, default 60) plus the windowed
        snapshot — the payload behind the /api/ps utilization block."""
        lm = self.manager.loaded
        if lm is None or getattr(lm, "scheduler", None) is None:
            self._send_json({"error": "no generative model loaded"}, 404)
            return
        acct = getattr(lm.scheduler, "acct", None)
        if acct is None or not acct.enabled:
            self._send_json(
                {"enabled": False,
                 "error": "accounting disabled (TPU_ACCOUNTING=0)"}, 200)
            return
        try:
            last = int(self._query().get("last", "60"))
        except ValueError:
            last = 60
        self._send_json({
            "model": lm.name,
            "snapshot": lm.scheduler.utilization_stats(),
            "ring": acct.ring(last=max(1, min(last, 600))),
        })

    def _debug_profile(self):
        """Capture a jax.profiler trace for ?seconds= (default 2, max
        30) into a temp dir and report its path. Opt-in via
        TPU_DEBUG_PROFILE=1 — profiling stalls the device queue, so it
        must never be reachable on an unguarded production port."""
        if os.environ.get("TPU_DEBUG_PROFILE") != "1":
            self._send_json(
                {"error": "profiling disabled (set TPU_DEBUG_PROFILE=1)"},
                403)
            return
        try:
            seconds = float(self._query().get("seconds", "2"))
        except ValueError:
            seconds = 2.0
        seconds = min(max(seconds, 0.1), 30.0)
        import tempfile

        import jax
        out_dir = tempfile.mkdtemp(prefix="tpu-profile-")
        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        self._send_json({"seconds": seconds, "trace_dir": out_dir})

    # -- routing --------------------------------------------------------
    def do_GET(self):
        try:
            path = self.path.split("?")[0]
            if path == "/":
                self._send_text("Ollama is running")
            elif path == "/api/version":
                self._send_json({"version": __version__})
            elif path == "/api/tags":
                self._send_json({"models": self.manager.list_models()})
            elif path == "/api/ps":
                self._send_json({"models": self.manager.ps()})
            elif path == "/v1/models":
                models = [{"id": m["name"], "object": "model",
                           "created": 0, "owned_by": "library"}
                          for m in self.manager.list_models()]
                self._send_json({"object": "list", "data": models})
            elif path == "/metrics":
                self._send_text(METRICS.render(),
                                ctype="text/plain; version=0.0.4")
            elif path == "/healthz":
                self._send_text("ok")
            elif path == "/debug/trace":
                self._debug_trace()
            elif path == "/debug/events":
                self._debug_events()
            elif path == "/debug/utilization":
                self._debug_utilization()
            elif path == "/debug/profile":
                self._debug_profile()
            elif path in ("/readyz", "/livez"):
                # livez fails too: a broken scheduler self-heals on the next
                # load(), but an idle pod would otherwise stay wedged with
                # no probe ever restarting it
                lm = self.manager.loaded
                if lm is not None and lm.scheduler.broken:
                    self._send_text("engine failed", status=503)
                elif path == "/readyz" and self.manager.draining:
                    # draining: readiness fails so the Service stops
                    # routing here, but liveness stays ok — the kubelet
                    # must NOT restart a pod mid-drain (that would cut
                    # the very streams the drain is protecting)
                    self._send_text("draining", status=503)
                else:
                    self._send_text("ok")
            else:
                self._send_json({"error": "not found"}, 404)
        except ApiError as e:
            self._send_json({"error": str(e)}, e.status)
        except BrokenPipeError:
            pass
        except Exception as e:  # noqa: BLE001
            self._send_json({"error": f"internal: {e}"}, 500)

    def do_DELETE(self):
        try:
            if self.path.split("?")[0] == "/api/delete":
                body = self._json_body()
                self.manager.delete(body.get("name") or body.get("model", ""))
                self._send_json({})
            else:
                self._send_json({"error": "not found"}, 404)
        except ApiError as e:
            self._send_json({"error": str(e)}, e.status)
        except Exception as e:  # noqa: BLE001
            self._send_json({"error": f"internal: {e}"}, 500)

    def do_HEAD(self):
        path = self.path.split("?")[0]
        if path.startswith("/api/blobs/"):
            # `ollama create` probes blobs before uploading (HEAD 200 =
            # skip the POST). Reject non-hex digests before touching the
            # filesystem — blob_path() joins the digest into a path, so an
            # unvalidated one is an arbitrary-path existence oracle.
            from .registry import valid_blob_digest
            digest = path[len("/api/blobs/"):]
            ok = (valid_blob_digest(digest)
                  and self.manager.store.has_blob(digest))
            self.send_response(200 if ok else 404)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        if path == "/":
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()
        else:
            self.send_response(404)
            self.send_header("Content-Length", "0")
            self.end_headers()

    def do_POST(self):
        path = self.path.split("?")[0]
        if path.startswith("/api/blobs/"):
            self._api_blob_upload(path[len("/api/blobs/"):])
            return
        # open until _submit hands the request to the scheduler; a request
        # that never gets there (another route, an error) records nothing
        self._ingress = span("http.ingress").begin()
        self._gen_trace = None
        try:
            body = self._json_body()
            route = {
                "/api/generate": self._api_generate,
                "/api/chat": self._api_chat,
                "/api/pull": self._api_pull,
                "/api/push": self._api_push,
                "/api/create": self._api_create,
                "/api/show": self._api_show,
                "/api/copy": self._api_copy,
                "/api/delete": self._api_delete,
                "/api/embeddings": self._api_embeddings,
                "/api/embed": self._api_embed,
                "/api/drain": self._api_drain,
                "/api/prefix_probe": self._api_prefix_probe,
                "/api/kv_export": self._api_kv_export,
                "/api/kv_import": self._api_kv_import,
                "/v1/chat/completions": self._oai_chat,
                "/v1/completions": self._oai_completions,
                "/v1/embeddings": self._oai_embeddings,
            }.get(path)
            if route is None:
                self._send_json({"error": "not found"}, 404)
                return
            route(body)
        except ApiError as e:
            self._send_error(str(e), e.status)
        except BadRequest as e:
            # typed request-validation failures from the service layer (bad
            # format value, prompt too long, images on a text model, …).
            # Plain ValueError deliberately falls through to the 500 branch:
            # an internal jax/numpy ValueError is a server bug, not a 400.
            self._send_error(str(e), 400)
        except DeadlineExceeded as e:
            # shed while queued: the caller got nothing and should retry
            # (503 is what load balancers key backpressure on); a
            # mid-generation expiry normally ends as a terminal stream
            # frame, so a pre-stream surface here maps to 504
            if e.while_queued:
                self._send_error(str(e), 503, headers={
                    "Retry-After": str(int(e.retry_after_s))})
            else:
                self._send_error(str(e), 504)
        except TenantRateLimited as e:
            # THIS tenant is over its share; everyone else is fine —
            # 429, so client-side backoff stays per-tenant
            self._send_error(str(e), 429, headers={
                "Retry-After": str(int(getattr(e, "retry_after_s", 1)))})
        except SchedulerBusy as e:
            # queue-full and SLO early rejects both carry a computed
            # Retry-After (queue-model drain estimate), not a flat 1s
            self._send_error(str(e), 503, headers={
                "Retry-After": str(int(getattr(e, "retry_after_s", 1)))})
        except SchedulerBroken as e:
            self._send_error(str(e), 500)
        except FollowerLost as e:
            self._send_error(f"multi-host world degraded: {e}", 500)
        except RegistryError as e:
            self._send_error(str(e), 500)
        except (BrokenPipeError, ConnectionResetError):
            pass
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            self._send_error(f"internal: {e}", 500)
        finally:
            self._ingress.cancel()
            if self._gen_trace is not None:
                # the response has ended: its last http_flush is stamped
                fold_stages(self._gen_trace)

    def _submit(self, lm, prompt: str, **kw):
        """``lm.generate_stream``: parse, template and tokenize are done
        and the scheduler has the request, so `http.ingress` ends here; the
        request's trace is kept for the stage fold when the response ends."""
        gen = lm.generate_stream(prompt, **kw)
        self._ingress.end()
        self._gen_trace = getattr(gen, "trace", None)
        return gen

    # -- Ollama endpoints ----------------------------------------------
    def _model_arg(self, body) -> str:
        model = body.get("model") or body.get("name")
        if not model:
            raise ApiError(400, "missing 'model'")
        return model

    def _inject_tenant(self, options: Optional[Dict]) -> Optional[Dict]:
        """Fair-queuing tenant from transport headers when the body
        didn't name one: ``X-Tenant`` verbatim, else a stable hash of
        the API key (``X-API-Key`` / ``Authorization``) — keyed clients
        get per-key fairness without any body change. Returns the
        options dict (possibly unchanged) for generate_stream."""
        o = dict(options or {})
        if not o.get("tenant"):
            t = self.headers.get("X-Tenant")
            if not t:
                key = (self.headers.get("X-API-Key")
                       or self.headers.get("Authorization"))
                t = tenant_from_key(key) if key else None
            if t:
                o["tenant"] = t
        return o or None

    def _api_generate(self, body: Dict):
        model = self._model_arg(body)
        prompt = body.get("prompt", "")
        ka = body.get("keep_alive")
        if not prompt and not body.get("context"):
            if ka is not None and parse_keep_alive(ka) == 0.0:
                # empty prompt + keep_alive 0 = `ollama stop`
                self.manager.stop(model)
                self._send_json({"model": model, "created_at": _now_iso(),
                                 "response": "", "done": True,
                                 "done_reason": "unload"})
                return
            # empty generate is ollama's "load the model" ping
            self.manager.require_loaded(model, keep_alive=ka)
            self._send_json({"model": model, "created_at": _now_iso(),
                             "response": "", "done": True,
                             "done_reason": "load"})
            return
        lm = self.manager.require_loaded(model, keep_alive=ka)
        stream = body.get("stream", True)
        raw = bool(body.get("raw", False))
        text_prompt = prompt if raw else lm.render_prompt(
            prompt, system=body.get("system"),
            template=body.get("template"), suffix=body.get("suffix"))
        gen = self._submit(lm, text_prompt,
                           options=self._inject_tenant(body.get("options")),
                           context=body.get("context"), raw=raw,
                           images=_decode_images(body.get("images")),
                           format=body.get("format"))
        if stream:
            trace = getattr(gen, "trace", None)
            gen = self._pull_first(gen)
            self._start_stream()
            co = self._coalescer(
                b'{"model": ' + json.dumps(model).encode()
                + b', "created_at": "',
                b'", "response": ', b', "done": false}\n',
                body.get("options"), trace=trace)
            for piece, final in gen:
                if final is None:
                    co.add(piece)
                else:
                    co.flush()
                    self._stream_json(self._final_chunk(model, final, body))
            self._end_stream()
        else:
            final = None
            for _piece, f in gen:
                if f is not None:
                    final = f
            out = self._final_chunk(model, final, body)
            out["response"] = final.text
            self._send_json(out)

    def _final_chunk(self, model: str, res, body: Dict) -> Dict:
        out = {
            "model": model, "created_at": _now_iso(), "response": "",
            "done": True, "done_reason": res.done_reason,
            "total_duration": int(res.total_s * 1e9),
            "load_duration": 0,
            "prompt_eval_count": res.prompt_tokens,
            "prompt_eval_duration": int(res.ttft_s * 1e9),
            "eval_count": res.generated_tokens,
            "eval_duration": int(max(res.total_s - res.ttft_s, 0.0) * 1e9),
        }
        if body.get("context") is not None or not body.get("raw"):
            out["context"] = res.context
        if getattr(res, "timings", None) is not None:
            # opt-in (options.trace=true): per-span first/last/count
            # summary of the request's trace, plus the id to fetch the
            # full timeline from /debug/trace
            out["timings"] = dict(res.timings,
                                  request_id=getattr(res, "request_id", 0))
        return out

    def _api_chat(self, body: Dict):
        model = self._model_arg(body)
        messages = body.get("messages", [])
        ka = body.get("keep_alive")
        if not messages and ka is not None and parse_keep_alive(ka) == 0.0:
            self.manager.stop(model)
            self._send_json({"model": model, "created_at": _now_iso(),
                             "message": {"role": "assistant", "content": ""},
                             "done": True, "done_reason": "unload"})
            return
        lm = self.manager.require_loaded(model, keep_alive=ka)
        stream = body.get("stream", True)
        tools = body.get("tools")
        prompt = lm.render_chat(messages, template=body.get("template"),
                                tools=tools)
        images = []
        for m in messages:
            images.extend(m.get("images") or [])
        gen = self._submit(lm, prompt,
                           options=self._inject_tenant(body.get("options")),
                           images=_decode_images(images),
                           format=body.get("format"))

        def chat_message(final) -> Dict:
            """Assistant message for the completed generation: JSON tool
            invocations become structured tool_calls (server/tools.py);
            prose around them stays as content."""
            msg = {"role": "assistant", "content": final.text}
            if tools:
                from .tools import split_tool_calls
                calls, prose = split_tool_calls(final.text)
                if calls:
                    msg = {"role": "assistant", "content": prose,
                           "tool_calls": calls}
            return msg

        if stream and not tools:
            trace = getattr(gen, "trace", None)
            gen = self._pull_first(gen)
            self._start_stream()
            co = self._coalescer(
                b'{"model": ' + json.dumps(model).encode()
                + b', "created_at": "',
                b'", "message": {"role": "assistant", "content": ',
                b'}, "done": false}\n',
                body.get("options"), trace=trace)
            for piece, final in gen:
                if final is None:
                    co.add(piece)
                else:
                    co.flush()
                    out = self._final_chunk(model, final, body)
                    out.pop("response", None)
                    out.pop("context", None)
                    out["message"] = {"role": "assistant", "content": ""}
                    self._stream_json(out)
            self._end_stream()
        else:
            final = None
            for _p, f in gen:
                if f is not None:
                    final = f
            out = self._final_chunk(model, final, body)
            out.pop("response", None)
            out.pop("context", None)
            out["message"] = chat_message(final)
            if stream:
                # tool responses stream as ONE message chunk + final (the
                # invocation can't be parsed until the output completes)
                self._start_stream()
                self._stream_json({"model": model, "created_at": _now_iso(),
                                   "message": out["message"],
                                   "done": False})
                out["message"] = {"role": "assistant", "content": ""}
                self._stream_json(out)
                self._end_stream()
            else:
                self._send_json(out)

    def _api_pull(self, body: Dict):
        model = self._model_arg(body)
        stream = body.get("stream", True)
        if stream:
            self._start_stream()

            def progress(status, completed, total, digest=None):
                msg = {"status": status}
                if total:
                    msg["total"] = total
                    msg["completed"] = completed
                if digest:
                    msg["digest"] = digest
                self._stream_json(msg)

            try:
                self.manager.client.pull(model, progress)
            except RegistryError as e:
                self._stream_json({"error": str(e)})
            self._end_stream()
        else:
            self.manager.client.pull(model)
            self._send_json({"status": "success"})

    def _api_push(self, body: Dict):
        model = self._model_arg(body)
        stream = body.get("stream", True)
        if stream:
            self._start_stream()

            def progress(status, completed=0, total=0, digest=None):
                msg = {"status": status}
                if total:
                    msg["total"] = total
                    msg["completed"] = completed
                if digest:
                    msg["digest"] = digest
                self._stream_json(msg)

            try:
                self.manager.client.push(model, progress)
            except RegistryError as e:
                self._stream_json({"error": str(e)})
            self._end_stream()
        else:
            self.manager.client.push(model)
            self._send_json({"status": "success"})

    def _api_blob_upload(self, digest: str):
        """POST /api/blobs/sha256:<hex> — raw body is the blob; the CLI
        uploads local GGUFs here before /api/create references them."""
        from .registry import RegistryError, valid_blob_digest
        # Any error response sent without consuming the declared body would
        # leave blob bytes on the HTTP/1.1 keep-alive socket to be parsed as
        # the next request line — close the connection on every error path.
        try:
            length = int(self.headers.get("Content-Length", "0"))
            if length <= 0:
                self.close_connection = True
                self._send_error("missing blob body", 400)
                return
            if not valid_blob_digest(digest):
                self.close_connection = True
                self._send_error(f"unsupported digest {digest!r}", 400)
                return
            self.manager.store.put_blob_stream(digest, self.rfile, length)
            self.send_response(201)
            self.send_header("Content-Length", "0")
            self.end_headers()
        except RegistryError as e:
            self.close_connection = True
            self._send_error(str(e), 400)
        except BrokenPipeError:
            pass
        except Exception as e:  # noqa: BLE001
            self.close_connection = True
            self._send_error(f"internal: {e}", 500)

    def _api_create(self, body: Dict):
        model = self._model_arg(body)
        modelfile_text = body.get("modelfile", "")
        if not modelfile_text and body.get("files"):
            # newer create API: {"files": {"x.gguf": "sha256:..."}} of
            # pre-uploaded blobs (see _api_blob_upload)
            files = body["files"]
            if len(files) != 1:
                raise ApiError(400, "multi-file create is not supported "
                                    "(one GGUF per model)")
            digest = next(iter(files.values()))
            lines = [f"FROM @{digest}"]
            if body.get("template"):
                lines.append("TEMPLATE \"\"\"" + body["template"] + "\"\"\"")
            if body.get("system"):
                lines.append("SYSTEM \"\"\"" + body["system"] + "\"\"\"")
            for k, v in (body.get("parameters") or {}).items():
                items = v if isinstance(v, list) else [v]
                lines.extend(f"PARAMETER {k} {item}" for item in items)
            modelfile_text = "\n".join(lines)
        if not modelfile_text and body.get("from"):
            modelfile_text = f"FROM {body['from']}"
        stream = body.get("stream", True)
        if stream:
            self._start_stream()

            def progress(status, *_):
                self._stream_json({"status": status})

            try:
                self.manager.create(model, modelfile_text, progress)
            except ApiError as e:
                self._stream_json({"error": str(e)})
            self._end_stream()
        else:
            self.manager.create(model, modelfile_text)
            self._send_json({"status": "success"})

    def _api_show(self, body: Dict):
        self._send_json(self.manager.show(self._model_arg(body)))

    def _api_copy(self, body: Dict):
        src, dst = body.get("source"), body.get("destination")
        if not src or not dst:
            raise ApiError(400, "need 'source' and 'destination'")
        self.manager.copy(src, dst)
        self._send_json({})

    def _api_delete(self, body: Dict):
        self.manager.delete(self._model_arg(body))
        self._send_json({})

    def _api_drain(self, body: Dict):
        """Operator-initiated graceful drain (the drain-first scale-down
        protocol): readyz flips 503, new submits shed, running streams
        finish, and the AOT warm state is snapshotted for the next wake.
        Idempotent — the operator re-POSTs on every poll until the
        replica reports zero active work via /api/ps."""
        self.manager.begin_drain()
        lm = self.manager.loaded
        sched = lm.scheduler if lm is not None else None
        self._send_json({
            "status": "draining",
            "active_streams": int(getattr(sched, "n_active", 0) or 0),
            "queued": int(getattr(sched, "qsize", 0) or 0),
        })

    def _api_prefix_probe(self, body: Dict):
        """Non-mutating radix-cache probe for the fleet gateway's
        cache-aware routing: how many leading tokens of this request's
        rendered prompt THIS replica could serve from its prefix cache
        right now. The gateway scatters the probe to healthy replicas on
        an affinity-table miss and routes to the longest match. Renders
        the prompt exactly like /api/generate so the probed ids equal
        the ids the real request would admit with."""
        model = self._model_arg(body)
        prompt = body.get("prompt", "")
        lm = self.manager.require_loaded(model,
                                         keep_alive=body.get("keep_alive"))
        raw = bool(body.get("raw", False))
        text = prompt if raw else lm.render_prompt(
            prompt, system=body.get("system"),
            template=body.get("template"), suffix=body.get("suffix"))
        tok = getattr(lm, "tokenizer", None)
        engine = getattr(lm, "engine", None)
        matched = 0
        n_ids = 0
        tier = 0
        if tok is not None and engine is not None:
            ids = tok.encode(text, add_bos=tok.add_bos)
            n_ids = len(ids)
            if n_ids > 1:
                if hasattr(engine, "prefix_probe_tier"):
                    # worst tier on the matched path: 0 = all-HBM
                    # (restitch-free), 1 = host restitch needed, 2 = the
                    # match includes imported fleet-snapshot pages — the
                    # gateway prefers lower tiers on matched-length ties
                    matched, tier = engine.prefix_probe_tier(ids)
                    matched, tier = int(matched), int(tier)
                else:
                    matched = int(engine.prefix_probe(ids))
        self._send_json({"model": model, "matched_tokens": matched,
                         "matched_tier": tier, "prompt_tokens": n_ids})

    # -- disaggregated prefill→decode KV transfer (ISSUE 20) -----------
    def _request_ids(self, lm, body: Dict):
        """Token ids exactly as /api/generate (or /api/chat, when the
        body carries ``messages``) would admit them — the KV transfer is
        keyed by the request's real admitted ids, so rendering must not
        drift from the serving paths."""
        if body.get("messages") is not None:
            text = lm.render_chat(body.get("messages") or [],
                                  template=body.get("template"),
                                  tools=body.get("tools"))
            ids = []
        else:
            prompt = body.get("prompt", "")
            text = prompt if body.get("raw") else lm.render_prompt(
                prompt, system=body.get("system"),
                template=body.get("template"), suffix=body.get("suffix"))
            ids = list(body.get("context") or [])
        tok = lm.tokenizer
        return ids + tok.encode(text, add_bos=(not ids) and tok.add_bos)

    def _api_kv_export(self, body: Dict):
        """Serve the KV pages covering this request's prompt prefix as
        one octet-stream blob (runtime/kv_wire.py format). 404 = nothing
        exportable here (dense engine, prefix not parked, multi-host) —
        the puller treats any non-200 as "re-prefill instead", so this
        endpoint never invents an error frame. Writes are paced to
        TPU_DISAGG_TRANSFER_MB_S (0 = unthrottled) so a big transfer
        cannot starve co-resident decode traffic of NIC bandwidth."""
        model = self._model_arg(body)
        lm = self.manager.require_loaded(model,
                                         keep_alive=body.get("keep_alive"))
        if not hasattr(lm, "kv_export"):
            self._send_json({"error": "kv export unsupported"}, 404)
            return
        ids = self._request_ids(lm, body)
        max_bytes = int(body.get("max_bytes") or (64 << 20))
        try:
            blob = lm.kv_export(ids, max_bytes)
        except Exception as e:  # noqa: BLE001 — incl. injected pages.export
            # faults: a failed export is a soft downgrade for the caller
            # (journal replay / cold prefill), so answer 503, not 500
            self._send_json({"error": f"kv export failed: {e}"}, 503)
            return
        if not blob:
            self._send_json({"error": "no exportable prefix"}, 404)
            return
        rate = float(os.environ.get("TPU_DISAGG_TRANSFER_MB_S", "0") or 0)
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(blob)))
        self.end_headers()
        step = 256 << 10
        t0 = time.monotonic()
        for off in range(0, len(blob), step):
            self.wfile.write(blob[off:off + step])
            if rate > 0:
                # sleep until the bytes sent so far fit under the cap
                ahead = ((off + step) / (rate * (1 << 20))
                         - (time.monotonic() - t0))
                if ahead > 0:
                    time.sleep(min(ahead, 1.0))
        self.wfile.flush()

    def _api_kv_import(self, body: Dict):
        """Pull a request's KV blob straight from the prefill replica
        named by ``source`` and graft it into this replica's radix tree
        (direct replica-to-replica transfer; the gateway only
        orchestrates). Always answers JSON with ``imported_pages`` —
        0 with a 2xx still means "go ahead and serve, you'll just
        re-prefill", which is why import failures are 5xx only when the
        pull itself broke."""
        model = self._model_arg(body)
        lm = self.manager.require_loaded(model,
                                         keep_alive=body.get("keep_alive"))
        source = body.get("source")
        if not source:
            raise ApiError(400, "missing 'source'")
        fwd = {k: body[k] for k in
               ("model", "prompt", "system", "template", "suffix", "raw",
                "context", "messages", "tools", "keep_alive", "max_bytes")
               if body.get(k) is not None}
        timeout = float(os.environ.get("TPU_DISAGG_HANDOFF_TIMEOUT_S",
                                       "30") or 30)
        import urllib.request
        req = urllib.request.Request(
            source.rstrip("/") + "/api/kv_export",
            data=json.dumps(fwd).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.monotonic()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                blob = resp.read()
        except Exception as e:  # noqa: BLE001 — network/HTTP/timeout
            self._send_json({"error": f"kv pull failed: {e}",
                             "imported_pages": 0}, 502)
            return
        try:
            pages = lm.kv_import(blob)
        except Exception as e:  # noqa: BLE001 — incl. injected
            # pages.import faults: page table untouched, caller serves
            # the request cold
            self._send_json({"error": f"kv import failed: {e}",
                             "imported_pages": 0}, 503)
            return
        dt = time.monotonic() - t0
        if pages:
            METRICS.inc("tpu_model_kv_transfer_pages_total", float(pages))
            METRICS.inc("tpu_model_kv_transfer_bytes_total",
                        float(len(blob)))
            METRICS.observe("tpu_model_kv_transfer_seconds", dt)
        self._send_json({"imported_pages": pages, "bytes": len(blob),
                         "seconds": dt})

    def _api_embeddings(self, body: Dict):
        lm = self.manager.require_loaded(self._model_arg(body),
                                         keep_alive=body.get("keep_alive"))
        prompt = body.get("prompt", "")
        emb = lm.embed([prompt])[0]
        self._send_json({"embedding": [float(x) for x in emb]})

    def _embed_input(self, body: Dict):
        """Shared input handling for /api/embed and /v1/embeddings."""
        lm = self.manager.require_loaded(self._model_arg(body),
                                         keep_alive=body.get("keep_alive"))
        inp = body.get("input", "")
        texts = [inp] if isinstance(inp, str) else list(inp)
        return lm.embed(texts)

    def _api_embed(self, body: Dict):
        embs = self._embed_input(body)
        self._send_json({
            "model": body.get("model"), "object": "list",
            "embeddings": [[float(x) for x in e] for e in embs]})

    # -- OpenAI compatibility ------------------------------------------
    def _oai_chat(self, body: Dict):
        model = self._model_arg(body)
        lm = self.manager.require_loaded(model)
        messages = body.get("messages", [])
        options = {}
        for src, dst in (("temperature", "temperature"), ("top_p", "top_p"),
                         ("seed", "seed"),
                         ("frequency_penalty", "frequency_penalty"),
                         ("presence_penalty", "presence_penalty")):
            if body.get(src) is not None:
                options[dst] = body[src]
        if body.get("max_tokens") is not None:
            options["num_predict"] = body["max_tokens"]
        if body.get("stop"):
            options["stop"] = body["stop"]
        tools = body.get("tools")
        prompt = lm.render_chat(messages, tools=tools)
        rid = f"chatcmpl-{int(time.time() * 1000)}"
        created = int(time.time())
        # OpenAI response_format → grammar/schema-constrained decoding:
        # json_schema carries its schema dict through to the skeleton
        # machine (ops/schema.py); json_object = generic JSON grammar
        rf = body.get("response_format") or {}
        fmt = None
        if isinstance(rf, dict):
            if rf.get("type") == "json_schema":
                js = rf.get("json_schema")
                fmt = (js.get("schema") if isinstance(js, dict)
                       else None) or "json"
            elif rf.get("type") == "json_object":
                fmt = "json"
        gen = self._submit(lm, prompt,
                           options=self._inject_tenant(options), format=fmt)
        if tools:
            # buffer and answer as one completion: tool invocations are
            # parsed from the full output
            final = None
            for _p, f in gen:
                if f is not None:
                    final = f
            from .tools import split_tool_calls
            calls, prose = split_tool_calls(final.text)
            if calls:
                msg = {"role": "assistant", "content": prose or None,
                       "tool_calls": [
                           {"id": f"call_{rid}_{i}", "type": "function",
                            "function": {
                                "name": c["function"]["name"],
                                "arguments": json.dumps(
                                    c["function"]["arguments"])}}
                           for i, c in enumerate(calls)]}
                finish = "tool_calls"
            else:
                msg = {"role": "assistant", "content": final.text}
                finish = final.done_reason
            if body.get("stream"):
                # tool invocations parse only once the output completes:
                # stream the finished message as one SSE delta + finish
                self._start_stream(ctype="text/event-stream")
                delta = dict(msg)
                if delta.get("tool_calls"):
                    # SSE deltas carry a per-entry index
                    delta["tool_calls"] = [dict(tc, index=i) for i, tc in
                                           enumerate(delta["tool_calls"])]
                self._chunk(self._sse({
                    "id": rid, "object": "chat.completion.chunk",
                    "created": created, "model": model,
                    "choices": [{"index": 0, "delta": delta,
                                 "finish_reason": None}]}))
                self._chunk(self._sse({
                    "id": rid, "object": "chat.completion.chunk",
                    "created": created, "model": model,
                    "choices": [{"index": 0, "delta": {},
                                 "finish_reason": finish}]}))
                self._chunk(b"data: [DONE]\n\n")
                self._end_stream()
                return
            self._send_json({
                "id": rid, "object": "chat.completion", "created": created,
                "model": model,
                "choices": [{"index": 0, "message": msg,
                             "finish_reason": finish}],
                "usage": {"prompt_tokens": final.prompt_tokens,
                          "completion_tokens": final.generated_tokens,
                          "total_tokens": final.prompt_tokens +
                          final.generated_tokens}})
            return
        if body.get("stream"):
            trace = getattr(gen, "trace", None)
            gen = self._pull_first(gen)
            self._start_stream(ctype="text/event-stream")
            self._chunk(self._sse({
                "id": rid, "object": "chat.completion.chunk",
                "created": created, "model": model,
                "choices": [{"index": 0,
                             "delta": {"role": "assistant", "content": ""},
                             "finish_reason": None}]}))
            co = self._coalescer(
                b'data: {"id": ' + json.dumps(rid).encode()
                + b', "object": "chat.completion.chunk", "created": '
                + str(created).encode() + b', "model": '
                + json.dumps(model).encode()
                + b', "choices": [{"index": 0, "delta": {"content": ',
                None, b'}, "finish_reason": null}]}\n\n', options,
                trace=trace)
            final = None
            for piece, f in gen:
                if f is None:
                    co.add(piece)
                else:
                    final = f
            co.flush()
            self._chunk(self._sse({
                "id": rid, "object": "chat.completion.chunk",
                "created": created, "model": model,
                "choices": [{"index": 0, "delta": {},
                             "finish_reason": final.done_reason}]}))
            self._chunk(b"data: [DONE]\n\n")
            self._end_stream()
        else:
            final = None
            for _p, f in gen:
                if f is not None:
                    final = f
            self._send_json({
                "id": rid, "object": "chat.completion", "created": created,
                "model": model,
                "choices": [{"index": 0,
                             "message": {"role": "assistant",
                                         "content": final.text},
                             "finish_reason": final.done_reason}],
                "usage": {"prompt_tokens": final.prompt_tokens,
                          "completion_tokens": final.generated_tokens,
                          "total_tokens": final.prompt_tokens +
                          final.generated_tokens}})

    def _oai_embeddings(self, body: Dict):
        """OpenAI-compatible embeddings (maps onto LoadedModel.embed)."""
        embs = self._embed_input(body)
        self._send_json({
            "object": "list",
            "model": body.get("model"),
            "data": [{"object": "embedding", "index": i,
                      "embedding": [float(x) for x in e]}
                     for i, e in enumerate(embs)],
            "usage": {"prompt_tokens": 0, "total_tokens": 0},
        })

    def _oai_completions(self, body: Dict):
        model = self._model_arg(body)
        lm = self.manager.require_loaded(model)
        options = {}
        if body.get("max_tokens") is not None:
            options["num_predict"] = body["max_tokens"]
        if body.get("temperature") is not None:
            options["temperature"] = body["temperature"]
        if body.get("stop"):
            options["stop"] = body["stop"]
        final = None
        for _piece, f in self._submit(lm, body.get("prompt", ""),
                                      options=self._inject_tenant(options)):
            if f is not None:
                final = f
        self._send_json({
            "id": f"cmpl-{int(time.time() * 1000)}",
            "object": "text_completion", "created": int(time.time()),
            "model": model,
            "choices": [{"index": 0, "text": final.text,
                         "finish_reason": final.done_reason}],
            "usage": {"prompt_tokens": final.prompt_tokens,
                      "completion_tokens": final.generated_tokens,
                      "total_tokens": final.prompt_tokens +
                      final.generated_tokens}})

    @staticmethod
    def _sse(obj) -> bytes:
        return b"data: " + json.dumps(obj).encode() + b"\n\n"


class _DeepStackHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a bounded deep-stack worker pool.

    Two departures from stock ThreadingHTTPServer:

    - Worker threads are POOLED and capped (TPU_HTTP_WORKERS, default
      64): stock spawns one thread per connection, so a load-balancer
      health-check storm or slow-reading client fleet grows threads
      without bound, and every spawn pays thread start-up on the request
      path. Workers here are spawned lazily up to the cap and then
      reused; excess connections queue until a worker frees.
    - Workers get a deep (64 MiB) stack: handler threads can run XLA
      compiles (a /api/chat that loads a model warms its buckets on the
      request thread), and LLVM recursion overflows a default stack.
      `threading.stack_size` is process-global, so the bump is scoped to
      the spawn and restored right after. (A thread spawned elsewhere in
      this narrow window also gets the deep stack; that is a virtual
      reservation, not committed memory.)"""

    daemon_threads = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._pool_q: queue.Queue = queue.Queue()
        self._pool_lock = threading.Lock()
        self._workers = 0
        self._idle = 0
        self._max_workers = max(
            1, int(os.environ.get("TPU_HTTP_WORKERS", "64") or "64"))

    def _worker(self):
        while True:
            item = self._pool_q.get()
            if item is None:
                return
            with self._pool_lock:
                self._idle -= 1
            request, client_address = item
            try:
                self.finish_request(request, client_address)
            except Exception:  # noqa: BLE001 — mirror ThreadingMixIn
                self.handle_error(request, client_address)
            finally:
                self.shutdown_request(request)
                with self._pool_lock:
                    self._idle += 1

    def process_request(self, request, client_address):
        with self._pool_lock:
            # spawn only when no worker will be free to take this item
            # once the backlog drains, and only below the cap
            if (self._idle - self._pool_q.qsize() <= 0
                    and self._workers < self._max_workers):
                self._workers += 1
                self._idle += 1   # counted idle until it picks up work
                try:
                    old = threading.stack_size(64 << 20)
                except (ValueError, RuntimeError):
                    old = None
                try:
                    threading.Thread(
                        target=self._worker, daemon=True,
                        name=f"http-worker-{self._workers}").start()
                finally:
                    if old is not None:
                        threading.stack_size(old)
        self._pool_q.put((request, client_address))

    def server_close(self):
        super().server_close()
        with self._pool_lock:
            n = self._workers
        for _ in range(n):
            self._pool_q.put(None)


def _hbm_bytes_in_use() -> float:
    """Live accelerator memory on local device 0, via whichever of the
    backend's memory_stats keys exists (TPU reports bytes_in_use; some
    backends report none at all — then this reads 0, and the gauge-error
    counter stays untouched because we return rather than raise)."""
    import jax
    devs = jax.local_devices()
    if not devs:
        return 0.0
    stats = devs[0].memory_stats()
    if not stats:
        return 0.0
    return float(stats.get("bytes_in_use", 0.0))


def device_memory() -> list:
    """What every local device is and holds, from its memory_stats (all
    zeros where the backend reports none, as the CPU does): the
    per-device view the device-0 gauge above cannot give of a mesh."""
    import jax
    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append({"id": d.id, "platform": d.platform,
                    "kind": d.device_kind,
                    "bytes_in_use": int(st.get("bytes_in_use", 0)),
                    "peak_bytes_in_use": int(
                        st.get("peak_bytes_in_use", 0)),
                    "bytes_limit": int(st.get("bytes_limit", 0))})
    return out


def serve(manager: ModelManager, host: str = "0.0.0.0", port: int = 11434
          ) -> ThreadingHTTPServer:
    handler = type("BoundHandler", (Handler,), {"manager": manager})
    httpd = _DeepStackHTTPServer((host, port), handler)
    METRICS.gauge_fn("tpu_model_hbm_bytes_in_use", _hbm_bytes_in_use)
    METRICS.gauge_fn("tpu_model_flight_recorder_events",
                     lambda: float(FLIGHT.seq))
    t = threading.Thread(target=httpd.serve_forever, daemon=True,
                         name="http-server")
    t.start()
    return httpd
