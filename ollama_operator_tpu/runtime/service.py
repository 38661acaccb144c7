"""LoadedModel: one resident model = engine + scheduler + tokenizer +
prompt template + default options.

This is the text-level API the HTTP layer (server/app.py) calls — the
equivalent of the model-serving half of `ollama serve` in the container the
reference launches per model Deployment (/root/reference/pkg/model/model.go:39,
pod.go:14). Handles prompt templating, stop-sequence holdback, and
per-request option merging; everything below it is token-level.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time
import weakref
from collections import OrderedDict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig
from ..server.metrics import GLOBAL as METRICS
from ..server.template import DEFAULT_TEMPLATE, Template
from ..tokenizer import StreamDecoder, Tokenizer
from .admission import (resolve_priority, resolve_tenant,
                        resolve_ttft_slo_s)
from .engine import Engine, EngineConfig, SlotOptions
from .errors import BadRequest
from .faults import FAULTS
from .scheduler import Scheduler


def resolve_deadline_s(defaults: Optional[Dict],
                       options: Optional[Dict]) -> Optional[float]:
    """Per-request wall-clock budget in seconds, or None for unlimited.

    Precedence: request ``deadline_ms`` option > modelfile default >
    ``TPU_REQUEST_DEADLINE_MS`` env. 0 (or absent everywhere) disables.
    """
    o = dict(defaults or {})
    o.update(options or {})
    raw = o.get("deadline_ms")
    if raw is None:
        raw = os.environ.get("TPU_REQUEST_DEADLINE_MS") or None
    if raw is None:
        return None
    try:
        ms = float(raw)
    except (TypeError, ValueError) as e:
        raise BadRequest(f"invalid deadline_ms: {raw!r}") from e
    if ms < 0:
        raise BadRequest("deadline_ms must be >= 0")
    return ms / 1000.0 if ms > 0 else None


@dataclasses.dataclass
class GenerateResult:
    text: str = ""
    prompt_tokens: int = 0
    generated_tokens: int = 0
    ttft_s: float = 0.0
    total_s: float = 0.0
    done_reason: str = "stop"
    context: List[int] = dataclasses.field(default_factory=list)
    # scheduler request id — the handle for GET /debug/trace?id=
    request_id: int = 0
    # per-stage span summary (runtime/trace.py), filled only when the
    # request asked for it (options.trace=true) — rides into the final
    # NDJSON frame as the "timings" block
    timings: Optional[Dict] = None


class _OwnedStream:
    """Iterator that owns its scheduler slot: with eager submission, the
    request exists before the caller ever iterates, so a drop before the
    first next() (e.g. client socket died while writing response headers)
    must still cancel the request — a generator's finally can't cover that
    window because an unstarted generator never entered its try block."""

    def __init__(self, it, req):
        self._it, self._req = it, req
        self._started = False
        # span timeline handle, so the HTTP layer can stamp its flush
        # events onto the same timeline the scheduler writes to
        self.trace = req.trace

    def __iter__(self):
        return self

    def __next__(self):
        self._started = True
        return next(self._it)

    def close(self):
        if not self._started:
            self._req.cancel()  # idempotent event-set
        self._it.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # lint: allow(exception-hygiene): never raise from GC
            pass


_schema_warned = [False]   # once-per-process format-schema downgrade notice


class _Piece(str):
    """A detokenised text piece that remembers how many scheduler tokens
    produced it. The stream protocol stays (str, final) tuples — existing
    consumers see a plain str — but the HTTP frame coalescer and bench
    need token counts per piece, not character counts."""

    n_tokens = 1

    @staticmethod
    def of(text: str, n: int) -> "_Piece":
        p = _Piece(text)
        p.n_tokens = n
        return p


def merge_options(defaults: Dict, request: Optional[Dict]
                  ) -> Tuple[SlotOptions, int, List[str]]:
    """(modelfile params, request options) → (SlotOptions, num_predict, stop)."""
    o = dict(defaults or {})
    o.update(request or {})
    stop = o.get("stop") or []  # tolerate explicit null
    if isinstance(stop, str):
        stop = [stop]
    try:
        so = SlotOptions(
            temperature=float(o.get("temperature", 0.8)),
            top_k=int(o.get("top_k", 40)),
            top_p=float(o.get("top_p", 0.9)),
            min_p=float(o.get("min_p", 0.0)),
            typical_p=float(o.get("typical_p", 1.0)),
            repeat_penalty=float(o.get("repeat_penalty", 1.1)),
            presence_penalty=float(o.get("presence_penalty", 0.0)),
            frequency_penalty=float(o.get("frequency_penalty", 0.0)),
            # llama.cpp treats any value other than 1/2 as off
            mirostat=(int(o.get("mirostat", 0))
                      if int(o.get("mirostat", 0)) in (1, 2) else 0),
            mirostat_tau=float(o.get("mirostat_tau", 5.0)),
            mirostat_eta=float(o.get("mirostat_eta", 0.1)),
            seed=int(o.get("seed", -1)),
            repeat_last_n=int(o.get("repeat_last_n", 64)))
        num_predict = int(o.get("num_predict", 128))
    except (TypeError, ValueError) as e:
        raise BadRequest(f"invalid options: {e}") from e
    if num_predict < 0:
        num_predict = 1 << 30  # -1 = unlimited (bounded by context)
    return so, num_predict, list(stop)


class StopMatcher:
    """Streaming stop-sequence matcher with holdback of partial matches."""

    def __init__(self, stops: Sequence[str]):
        self.stops = [s for s in stops if s]
        self.buf = ""
        self.hit = False

    def feed(self, piece: str) -> str:
        if self.hit:
            return ""
        if not self.stops:
            return piece
        self.buf += piece
        # full match?
        cut = None
        for s in self.stops:
            idx = self.buf.find(s)
            if idx >= 0 and (cut is None or idx < cut):
                cut = idx
        if cut is not None:
            out, self.buf = self.buf[:cut], ""
            self.hit = True
            return out
        # hold back the longest tail that could begin a stop string
        hold = 0
        for s in self.stops:
            for k in range(min(len(s) - 1, len(self.buf)), 0, -1):
                if self.buf.endswith(s[:k]):
                    hold = max(hold, k)
                    break
        if hold:
            out, self.buf = self.buf[:-hold], self.buf[-hold:]
            return out
        out, self.buf = self.buf, ""
        return out

    def flush(self) -> str:
        out, self.buf = self.buf, ""
        return "" if self.hit else out


class LoadedModel:
    def __init__(self, name: str, cfg: ModelConfig, params, tokenizer: Tokenizer,
                 template: Optional[str] = None,
                 system: Optional[str] = None,
                 default_params: Optional[Dict] = None,
                 mesh=None, ecfg: Optional[EngineConfig] = None,
                 digest: str = "", vision: Optional[Tuple] = None,
                 control_plane=None, follower: bool = False,
                 warm_cache_dir: Optional[str] = None):
        self.name = name
        self.cfg = cfg
        # (VisionConfig, vision params) for multimodal models (llava) —
        # the mmproj layer the reference delegates to llama.cpp's clip
        self.vision = vision
        self._vision_fns = {}
        self.digest = digest
        self.tokenizer = tokenizer
        self.template = Template(template or DEFAULT_TEMPLATE)
        self.system = system
        self.default_params = default_params or {}
        self.loaded_at = time.time()
        self.ecfg = ecfg or EngineConfig()
        self.control_plane = control_plane
        self.follower = follower
        self._unloaded = False   # set under dispatch_lock on multi-host
        # an engine's compiled closures refer back to it, so an engine
        # nobody holds any more (a model just unloaded) keeps its caches on
        # the device until the cycle collector runs: run it before asking
        # for this one's
        import gc
        gc.collect()
        self.engine = Engine(cfg, params, mesh=mesh, ecfg=self.ecfg)
        if control_plane is not None:
            # multi-host leader: every device-dispatching engine call is
            # broadcast to the follower processes BEFORE running locally,
            # so the whole slice executes identical SPMD programs
            # (runtime/follower.py)
            from .follower import MirroredEngine
            self.engine = MirroredEngine(self.engine, control_plane)
        # AOT-compile every attention-bucket decode program up front —
        # serving must never pay an XLA compile at a bucket crossing (the
        # persistent compilation cache makes this near-free on restarts).
        # Followers warm via the leader's replayed warm_buckets call.
        # When a warm snapshot exists on the weight-cache volume (saved
        # by a drain before scale-to-zero), restore it instead: the
        # woken replica re-enters serving with the full warm plan and
        # tpu_model_recompiles_total untouched.
        import os as _os
        self._warm_cache_dir = warm_cache_dir if not follower else None
        if not follower and _os.environ.get("TPU_WARM_BUCKETS", "1") != "0":
            if not self._restore_warm_snapshot():
                self.engine.warm_buckets()
        # tier-2 prefix snapshot: seed the host arena with the fleet's
        # shared hot prefixes so this replica's first shared-prefix
        # request is a warm tier-2 hit instead of a cold prefill
        # (import_prefixes is MIRRORED — followers replay the same
        # import and the trees stay bit-identical)
        if not follower:
            self._restore_prefix_snapshot()
        # followers replay engine calls from the control stream — they
        # never schedule on their own
        self.scheduler = None if follower else Scheduler(self.engine)
        self._embed_fn = None
        self._embed_lock = threading.Lock()
        # canonical schema JSON → compiled machine, LRU-evicted one at a
        # time (each entry amortises full-vocab mask sweeps)
        self._schemas: OrderedDict[str, object] = OrderedDict()
        # weakrefs: a registered gauge must not keep the engine (and its
        # multi-GB params) alive after unload()
        wself = weakref.ref(self)
        METRICS.gauge_fn("tpu_model_active_slots",
                         lambda: (lm := wself()) is not None
                         and lm.scheduler is not None
                         and lm.scheduler.n_active or 0)
        METRICS.gauge_fn("tpu_model_queue_depth",
                         lambda: (lm := wself()) is not None
                         and lm.scheduler is not None
                         and lm.scheduler.qsize or 0)
        if self.engine.paged:
            # paged-pool pressure signal for autoscaling/alerting (the
            # preemption COUNTER lives in the scheduler — counters survive
            # unload, keeping Prometheus rate() semantics intact)
            METRICS.gauge_fn("tpu_model_kv_free_pages",
                             lambda: (lm := wself()) is not None
                             and lm.engine.free_pages or 0)
        if getattr(self.engine, "radix_enabled", False):
            # radix prefix-cache residency: nodes == chunks, pages ==
            # pool pages the tree pins (hit/miss counters live in the
            # scheduler path and survive unload)
            METRICS.gauge_fn("tpu_model_radix_nodes",
                             lambda: (lm := wself()) is not None
                             and lm.engine.radix_nodes or 0)
            METRICS.gauge_fn("tpu_model_radix_pages",
                             lambda: (lm := wself()) is not None
                             and lm.engine.radix_pages or 0)
        for kind in getattr(self.engine, "cache_bytes", ()):
            METRICS.gauge_fn("tpu_model_cache_bytes",
                             lambda kind=kind: (lm := wself()) is not None
                             and lm.engine.cache_bytes[kind] or 0,
                             f'{{kind="{kind}"}}')
        for what in getattr(self.engine, "ring_positions", ()):
            METRICS.gauge_fn("tpu_model_ring_positions",
                             lambda what=what: (lm := wself()) is not None
                             and lm.engine.ring_positions[what] or 0,
                             f'{{what="{what}"}}')
        for what in getattr(self.engine, "latent_positions", ()):
            METRICS.gauge_fn("tpu_model_latent_positions",
                             lambda what=what: (lm := wself()) is not None
                             and lm.engine.latent_positions[what] or 0,
                             f'{{what="{what}"}}')
        if getattr(self.engine, "host_cache_enabled", False):
            # tier-1 host-arena occupancy: bytes and whole KV pages the
            # spilled radix subtrees hold in pinned host RAM (the spill /
            # tier-hit counters live in the scheduler path and survive
            # unload, keeping Prometheus rate() semantics intact)
            METRICS.gauge_fn("tpu_model_host_cache_bytes",
                             lambda: (lm := wself()) is not None
                             and lm.engine.host_cache_used_bytes or 0)
            METRICS.gauge_fn("tpu_model_host_cache_pages",
                             lambda: (lm := wself()) is not None
                             and lm.engine.host_cache_pages or 0)
        # utilization gauge (runtime/accounting.py): 60s-window MFU read
        # from the scheduler's accounting snapshot; None (no peak known /
        # idle) renders 0. Occupancy, goodput and waste are rates over
        # tpu_model_useful_tokens_total / tpu_model_padded_tokens_total
        # (and fields of /debug/utilization), not gauges
        def _mfu():
            lm = wself()
            if lm is None or lm.scheduler is None:
                return 0.0
            acct = getattr(lm.scheduler, "acct", None)
            if acct is None or not acct.enabled:
                return 0.0
            return float(acct.snapshot().get("mfu") or 0.0)
        METRICS.gauge_fn("tpu_model_mfu", _mfu)

    # ------------------------------------------------------------------
    # warm-snapshot (scale-to-zero fast cold-start): the AOT warm-bucket
    # executable cache persists on the weight-cache volume across pod
    # generations — saved at drain time, restored at load
    # ------------------------------------------------------------------
    def warm_snapshot_key(self) -> str:
        """Serving-identity hash the snapshot is keyed by: a snapshot is
        only valid for the exact digest + engine geometry + jax backend
        that produced it."""
        import hashlib
        import jax
        payload = "|".join([
            self.digest or self.name, repr(self.ecfg), jax.__version__,
            jax.default_backend()])
        return hashlib.sha256(payload.encode()).hexdigest()[:24]

    def _restore_warm_snapshot(self) -> bool:
        """Try to warm from a persisted snapshot; False falls back to a
        normal warm_buckets pass (never an error — the snapshot is an
        optimisation, not a dependency)."""
        import os as _os
        if (self._warm_cache_dir is None
                or _os.environ.get("TPU_WARM_SNAPSHOT", "1") == "0"
                or not hasattr(self.engine, "restore_warm")):
            return False
        from ..gguf.store import load_warm_snapshot
        try:
            blob = load_warm_snapshot(self._warm_cache_dir,
                                      self.warm_snapshot_key())
            if blob is None:
                return False
            self.engine.restore_warm(blob)
        except Exception:  # noqa: BLE001 — corrupt/incompatible snapshot
            return False
        METRICS.inc("tpu_model_warm_snapshot_restores_total", 1.0)
        return True

    def save_warm_snapshot(self) -> bool:
        """Persist the warm state (drain path: the operator snapshots
        before a scale-to-zero so the wake is warm). Best-effort."""
        import os as _os
        if (self.follower or self._warm_cache_dir is None
                or _os.environ.get("TPU_WARM_SNAPSHOT", "1") == "0"
                or not hasattr(self.engine, "warm_snapshot")):
            return False
        from ..gguf.store import save_warm_snapshot
        try:
            blob = self.engine.warm_snapshot()
            save_warm_snapshot(self._warm_cache_dir,
                               self.warm_snapshot_key(), blob)
        except Exception:  # noqa: BLE001 — never let a snapshot fail a drain
            return False
        METRICS.inc("tpu_model_warm_snapshot_saves_total", 1.0)
        return True

    # ------------------------------------------------------------------
    # tier-2 prefix snapshots (fleet-shared hot KV prefixes): the hottest
    # radix subtrees persist on the shared weight-cache volume across pod
    # generations — saved at drain time, imported into the host arena at
    # load so a just-woken replica answers shared-prefix traffic warm
    # ------------------------------------------------------------------
    def prefix_snapshot_key(self) -> str:
        """Serving-identity hash the prefix snapshot is keyed by: KV
        pages are only valid for the exact digest + engine geometry
        (page size, kv dtype, head layout all live in ecfg) + jax
        backend that produced them."""
        import hashlib
        import jax
        payload = "|".join([
            self.digest or self.name, repr(self.ecfg), jax.__version__,
            jax.default_backend(), "prefix-v1"])
        return hashlib.sha256(payload.encode()).hexdigest()[:24]

    def _restore_prefix_snapshot(self) -> bool:
        """Try to seed the host arena from a persisted prefix snapshot;
        False means cold (never an error — the snapshot is an
        optimisation, not a dependency)."""
        import os as _os
        if (self._warm_cache_dir is None
                or _os.environ.get("TPU_HOST_CACHE_SNAPSHOT", "1") == "0"
                or not getattr(self.engine, "host_cache_enabled", False)):
            return False
        from ..gguf.store import load_prefix_snapshot
        try:
            blob = load_prefix_snapshot(self._warm_cache_dir,
                                        self.prefix_snapshot_key())
            if blob is None:
                return False
            n = self.engine.import_prefixes(blob)
        except Exception:  # noqa: BLE001 — corrupt/incompatible snapshot
            return False
        return n > 0

    def save_prefix_snapshot(self) -> bool:
        """Persist the hottest prefixes (drain path, beside the warm
        snapshot). Best-effort — never lets a snapshot fail a drain."""
        import os as _os
        if (self.follower or self._warm_cache_dir is None
                or _os.environ.get("TPU_HOST_CACHE_SNAPSHOT", "1") == "0"
                or not getattr(self.engine, "radix_enabled", False)):
            return False
        from ..gguf.store import save_prefix_snapshot
        try:
            budget = int(_os.environ.get("TPU_HOST_CACHE_SNAPSHOT_MB",
                                         "64") or "64") << 20
            blob = self.engine.export_prefixes(budget)
            if blob is None:
                return False
            save_prefix_snapshot(self._warm_cache_dir,
                                 self.prefix_snapshot_key(), blob)
        except Exception:  # noqa: BLE001 — never let a snapshot fail a drain
            return False
        return True

    # ------------------------------------------------------------------
    # disaggregated prefill→decode handoff (ISSUE 20): the prefill
    # replica exports the request's quiescent KV pages; the decode
    # replica imports them as a radix warm start. Both run on the
    # scheduler thread (run_exclusive) so the page gathers / grafts
    # never race a dispatch. Multi-host slices are gated out the same
    # way multimodal is: the paged radix pool is leader-local.
    # ------------------------------------------------------------------
    def kv_export(self, ids: List[int],
                  max_bytes: int = 64 << 20) -> Optional[bytes]:
        """Serialize the KV pages covering ``ids``'s radix prefix.
        None means nothing exportable (dense engine, no prefix parked,
        multi-host) — the gateway downgrades to journal replay, so this
        is a soft answer, never an error."""
        if self.control_plane is not None or self.follower:
            return None
        if not getattr(self.engine, "radix_enabled", False):
            return None
        return self.scheduler.run_exclusive(
            lambda: self.engine.export_request_kv(ids, max_bytes))

    def kv_import(self, blob: bytes) -> int:
        """Graft a transferred KV blob into this replica's radix tree;
        returns pages imported (0 = nothing usable: the decode side
        simply re-prefills — a transfer is a warm start, never a
        correctness dependency)."""
        if self.control_plane is not None or self.follower:
            return 0
        if not getattr(self.engine, "radix_enabled", False):
            return 0
        return self.scheduler.run_exclusive(
            lambda: self.engine.import_request_kv(blob))

    # ------------------------------------------------------------------
    # multimodal (llava): image bytes → projected embeddings → spliced
    # prompt embedding sequence handed to the engine's embeds admission
    # ------------------------------------------------------------------
    def encode_images(self, images_u8) -> "np.ndarray":
        """List of uint8 [H, W, 3] arrays → [n_img, n_patches, D]."""
        if self.control_plane is not None:
            raise RuntimeError(
                "multimodal requests are not supported on multi-host "
                "slices yet (the vision tower jit is leader-only)")
        from ..models import vision as V
        import jax
        vcfg, vparams = self.vision
        batch = np.stack([V.preprocess(im, vcfg) for im in images_u8])
        fn = self._vision_fns.get("encode")
        if fn is None:
            fn = jax.jit(lambda p, x: V.encode(vcfg, p, x))
            self._vision_fns["encode"] = fn
        return np.asarray(fn(vparams, jnp.asarray(batch)))

    def splice_images(self, ids, images_u8):
        """Text ids + decoded images → (padded_ids, embeds [n, D]).

        Image tokens are inserted after the BOS token (llava convention:
        image context precedes the instruction); padded_ids carry a pad id
        at image positions (only the repeat-penalty counts see them).
        """
        import jax
        img = self.encode_images(images_u8)          # [n_img, N, D]
        n_img, N, D = img.shape
        fn = self._vision_fns.get("embed_ids")
        if fn is None:
            from ..models.decoder import _embed
            fn = jax.jit(lambda p, t: _embed(self.cfg, p, t))
            self._vision_fns["embed_ids"] = fn
        text = np.asarray(fn(self.engine.params,
                             jnp.asarray(np.asarray(ids, np.int32)[None]))
                          )[0].astype(np.float32)    # [n_text, D]
        cut = 1 if (ids and self.tokenizer.add_bos
                    and ids[0] == self.tokenizer.bos_id) else 0
        embeds = np.concatenate(
            [text[:cut]] + [img.reshape(n_img * N, D)] + [text[cut:]], axis=0)
        # pad id == vocab_size: definitively not a real token, and the
        # engine's penalty-count scatter drops it as out-of-bounds
        pad = [self.cfg.vocab_size] * (n_img * N)
        padded_ids = list(ids[:cut]) + pad + list(ids[cut:])
        return padded_ids, embeds

    # ------------------------------------------------------------------
    def _make_constraint(self, format):
        """format:"json" → generic grammar; a schema dict → the compiled
        skeleton machine (ops/schema.py) when the schema is in the
        supported subset, else generic JSON with a once-per-process
        downgrade warning (never a silently wrong constraint)."""
        from ..ops.constrain import JsonConstraint
        if isinstance(format, dict):
            import json as _json
            from ..ops.schema import SchemaConstraint, compile_schema
            key = _json.dumps(format, sort_keys=True)
            if key in self._schemas:
                sch = self._schemas[key]
                self._schemas.move_to_end(key)
            else:
                sch = compile_schema(format)
                if len(self._schemas) > 64:
                    # evict ONE stale entry — wholesale clears would
                    # re-pay every compiled machine's per-state mask
                    # cache on schema-rotating workloads (ADVICE r2)
                    self._schemas.popitem(last=False)
                self._schemas[key] = sch   # None cached too (unsupported)
            if sch is not None:
                c = SchemaConstraint.for_tokenizer(sch, self.tokenizer)
                c.mask_row()   # prime the initial mask on the HTTP
                # thread (later novel hole states still fill in the
                # scheduler loop — amortised by the abstract-state cache)
                return c
            if not _schema_warned[0]:
                _schema_warned[0] = True
                print("warning: JSON schema outside the supported subset; "
                      "constraining to generic JSON only",
                      file=sys.stderr, flush=True)
        return JsonConstraint.for_tokenizer(self.tokenizer)

    def render_prompt(self, prompt: str, system: Optional[str] = None,
                      template: Optional[str] = None,
                      suffix: Optional[str] = None) -> str:
        """``suffix`` enables fill-in-middle (code models): it renders
        through the template's ``.Suffix``; a model whose template has no
        suffix section cannot insert — that's a client error (upstream
        ollama answers the same way)."""
        tpl = Template(template) if template else self.template
        if suffix:
            if ".Suffix" not in tpl.src:
                raise BadRequest(
                    f"model {self.name} does not support insert (its "
                    f"template has no .Suffix section)")
            return tpl.render(prompt=prompt, suffix=suffix,
                              system=system if system is not None else
                              (self.system or ""))
        return tpl.render(prompt=prompt,
                          system=system if system is not None else
                          (self.system or ""))

    def render_chat(self, messages: List[Dict],
                    template: Optional[str] = None,
                    tools: Optional[List[Dict]] = None) -> str:
        """Render a messages list. Templates that iterate .Messages get them
        directly; legacy system/prompt templates get a flattened view.

        ``tools`` (OpenAI wire shape) render through the template's
        ``.Tools`` (Go-shaped, server/tools.py); a model whose template has
        no tools section cannot honour them — that's a client error."""
        from ..server.tools import to_template_tool_calls, to_template_tools
        tpl = Template(template) if template else self.template
        if tools and ".Tools" not in tpl.src:
            raise BadRequest(
                f"model {self.name} does not support tools (its template "
                f"has no .Tools section)")
        system = self.system or ""
        sys_parts = [m["content"] for m in messages
                     if m.get("role") == "system"]
        if sys_parts:
            system = "\n".join(([system] if system else []) + sys_parts)
        msgs = []
        for m in messages:
            if m.get("role") == "system":
                continue
            entry = {"Role": m.get("role", "user"),
                     "Content": m.get("content", "") or ""}
            if m.get("tool_calls"):
                entry["ToolCalls"] = to_template_tool_calls(m["tool_calls"])
            msgs.append(entry)
        tpl_tools = to_template_tools(tools) if tools else []
        if ".Messages" in tpl.src:
            if system:
                msgs = [{"Role": "system", "Content": system}] + msgs
            return tpl.render(messages=msgs, system=system, prompt="",
                              tools=tpl_tools)
        prompt = msgs[-1]["Content"] if msgs else ""
        return tpl.render(system=system, prompt=prompt, tools=tpl_tools)

    # ------------------------------------------------------------------
    def generate_stream(self, prompt_text: str,
                        options: Optional[Dict] = None,
                        context: Optional[List[int]] = None,
                        raw: bool = False,
                        cancel_event: Optional[threading.Event] = None,
                        images: Optional[List] = None,
                        format: Optional[object] = None
                        ) -> Iterator[Tuple[str, Optional[GenerateResult]]]:
        """Yields (text_piece, None)… then ("", final GenerateResult).

        ``format``: Ollama structured-output field — ``"json"`` (or any
        JSON-schema dict, honoured as generic JSON mode) turns on
        grammar-constrained decoding (ops/constrain.py): the output is
        guaranteed to be a syntactically complete JSON value.

        Option parsing, tokenization, and scheduler admission run eagerly
        at call time — NOT on first next() — so SchedulerBusy/Broken and
        bad-request errors surface before the HTTP layer commits a 200 +
        chunked headers (a mid-stream error chunk can't carry the 503 that
        load balancers key backpressure on)."""
        so, num_predict, stops = merge_options(self.default_params, options)
        t0 = time.monotonic()
        ids = list(context or [])
        # BOS only at the start of a fresh sequence (continuations carry it)
        ids += self.tokenizer.encode(
            prompt_text, add_bos=(not ids) and self.tokenizer.add_bos)
        embeds = None
        context_ids = ids
        if images:
            if self.vision is None:
                raise BadRequest(
                    f"model {self.name} has no vision projector; it cannot "
                    f"accept images")
            ids, embeds = self.splice_images(ids, images)
        # disagg prefill-only mode (gateway-injected option, ISSUE 20):
        # the prefill replica runs prefill + ONE decoded token — enough
        # to commit the first frame — then finishes; the scheduler's
        # finish path parks the prompt's KV in the radix tree, which is
        # exactly what /api/kv_export ships to the decode pool.
        # merge_options ignores unknown keys, so the flag never reaches
        # SlotOptions (same contract as options.trace).
        prefill_only = bool((options or {}).get("disagg_prefill"))
        max_new = min(num_predict, self.engine.max_seq - len(ids) - 1)
        if prefill_only:
            max_new = min(max_new, 1)
        if max_new < 1:
            raise BadRequest(
                f"prompt of {len(ids)} tokens leaves no room to generate "
                f"within the {self.engine.max_seq}-token context")
        constraint = None
        if format is not None and format != "":
            if format == "json" or isinstance(format, dict):
                constraint = self._make_constraint(format)
            else:
                raise BadRequest(
                    f"unsupported format {format!r}; expected \"json\" or "
                    f"a JSON schema object")
        req = self.scheduler.submit(ids, so, max_new,
                                    eog_ids=frozenset(self.tokenizer.eog_ids),
                                    embeds=embeds, constraint=constraint,
                                    deadline_s=resolve_deadline_s(
                                        self.default_params, options),
                                    priority=resolve_priority(
                                        self.default_params, options),
                                    tenant=resolve_tenant(options),
                                    ttft_slo_s=resolve_ttft_slo_s(
                                        self.default_params, options))
        # opt-in span summary in the final frame: options.trace=true
        # (merge_options ignores unknown keys, so "trace" never reaches
        # SlotOptions)
        want_timings = bool((options or {}).get("trace"))
        # returned context carries only REAL token ids: a continuation
        # re-prefills from context without the image, so image pad ids
        # must not leak into it (they would re-enter as garbage tokens)
        return _OwnedStream(
            self._stream(req, stops, context_ids, max_new, t0, cancel_event,
                         want_timings, prefill_only),
            req)

    def _stream(self, req, stops, ids, max_new, t0, cancel_event,
                want_timings: bool = False, prefill_only: bool = False
                ) -> Iterator[Tuple[str, Optional[GenerateResult]]]:
        sd = StreamDecoder(self.tokenizer)
        sm = StopMatcher(stops)
        # prompt_eval_count includes image tokens (llava counts them);
        # ``ids`` here is the context view, which excludes the image pads
        result = GenerateResult(prompt_tokens=req.stats.n_prompt)
        all_ids: List[int] = []
        finished = False
        try:
            # chunk-granular consumption: one queue item, one batched
            # detokenise, and one StopMatcher pass per decode dispatch
            for chunk in req.chunks():
                if cancel_event is not None and cancel_event.is_set():
                    req.cancel()
                all_ids.extend(chunk)
                FAULTS.check("detok.feed")
                req.trace.event("detok", n=len(chunk))
                piece = sm.feed(sd.feed_many(chunk))
                if piece:
                    result.text += piece
                    yield _Piece.of(piece, len(chunk)), None
                if sm.hit:
                    req.cancel()
                    break
            finished = True
        finally:
            # generator closed early (client disconnect → GeneratorExit):
            # free the decode slot instead of burning it to max_tokens
            if not finished:
                req.cancel()
        tail = sm.feed(sd.flush()) + sm.flush()
        if tail:
            result.text += tail
            yield _Piece.of(tail, 0), None   # tokens already counted above
        st = req.stats
        result.generated_tokens = st.n_generated
        result.ttft_s = st.ttft_s
        result.total_s = time.monotonic() - t0
        if getattr(req, "done_reason", None) in ("timeout", "drain"):
            # deadline_ms expired mid-generation ("timeout"), or the
            # graceful-drain window closed around a running stream
            # ("drain"): the scheduler released the slot and sent a
            # clean terminal frame — surface the real reason instead of
            # misreporting "stop" (a client seeing "drain" knows its
            # partial output was cut by a rollout and can resume via
            # context)
            result.done_reason = req.done_reason
        else:
            result.done_reason = ("stop"
                                  if sm.hit or st.n_generated < max_new
                                  else "length")
            if prefill_only and result.done_reason == "length":
                # cut at the injected 1-token cap, not a real completion:
                # the gateway keys its handoff on this reason. A genuine
                # "stop" (first token was EOG / a stop sequence) stays
                # "stop" — the stream is actually done, no handoff needed.
                result.done_reason = "handoff"
        result.context = ids + all_ids
        METRICS.inc("tpu_model_requests_total")
        METRICS.inc("tpu_model_generated_tokens_total", st.n_generated)
        METRICS.inc("tpu_model_prompt_tokens_total", len(ids))
        if st.n_reused:
            # prompt tokens whose K/V came from a parked prefix (no prefill)
            METRICS.inc("tpu_model_prefix_reused_tokens_total", st.n_reused)
        METRICS.observe("tpu_model_ttft_seconds", st.ttft_s)
        if st.decode_tok_s > 0:
            METRICS.observe("tpu_model_decode_tokens_per_second",
                            st.decode_tok_s)
        result.request_id = req.id
        if want_timings:
            result.timings = req.trace.timings()
        yield "", result

    def generate(self, prompt_text: str, options: Optional[Dict] = None,
                 raw: bool = False) -> GenerateResult:
        final = None
        for _piece, res in self.generate_stream(prompt_text, options,
                                                raw=raw):
            if res is not None:
                final = res
        return final

    # ------------------------------------------------------------------
    def embed(self, texts: List[str]) -> np.ndarray:
        """Mean-pooled final hidden states (ollama /api/embeddings)."""
        from ..models import decoder as D

        with self._embed_lock:
            if self._embed_fn is None:
                cfg = self.cfg

                def _embed(params, tokens, n_valid):
                    x = D._embed(cfg, params, tokens)
                    import jax.numpy as jnp
                    from jax import lax
                    from ..ops.attention import causal_mask
                    import math
                    B, T = tokens.shape
                    # the model's real score scale (granite's exact
                    # multiplier, gemma's query_pre_attn_scalar) — a
                    # hand-rolled 1/sqrt(head_dim) silently mis-scales
                    # those families' embeddings
                    scale = D._attn_scale(cfg)
                    from ..ops.rope import rope_angles_cfg
                    positions = jnp.broadcast_to(
                        jnp.arange(T, dtype=jnp.int32), (B, T))
                    cos, sin = rope_angles_cfg(positions, cfg)
                    mask = causal_mask(T, T, 0,
                                       sliding_window=cfg.sliding_window)
                    mask = jnp.broadcast_to(mask, (B, 1, T, T))

                    mesh = self.engine.mesh

                    def body(x, lp):
                        # mesh keeps pallas inside the shard_map dispatch
                        # on >1-device meshes (GSPMD can't see pallas_call)
                        x, kv = D._block_chunk(cfg, lp, x, cos, sin, mask,
                                               scale, mesh=mesh)
                        return x, None

                    x, _ = lax.scan(body, x, params["layers"])
                    x = D._norm(cfg, x, params["out_norm_w"],
                                params.get("out_norm_b"))
                    valid = (jnp.arange(T)[None, :] < n_valid[:, None]
                             ).astype(x.dtype)
                    pooled = (x * valid[:, :, None]).sum(1) / jnp.maximum(
                        valid.sum(1, keepdims=True), 1)
                    return pooled.astype(jnp.float32)

                # replicated output: multi-controller processes can
                # only read fully-addressable (or replicated) arrays
                self._embed_fn = jax.jit(
                    _embed, out_shardings=self.engine._repl_sh)
        # one device dispatch per LENGTH BUCKET, not per text (round-1
        # weak #9: serial per-text dispatches — fine for probes, weak for
        # real embedding traffic): texts bucket by padded length, each
        # bucket embeds as one [n, T] batch, results return in input order
        all_ids = [self.tokenizer.encode(t) for t in texts]
        buckets: Dict[int, List[int]] = {}
        for i, ids in enumerate(all_ids):
            T = max(16, 1 << (max(len(ids), 1) - 1).bit_length())
            buckets.setdefault(T, []).append(i)
        outs: List[Optional[np.ndarray]] = [None] * len(texts)

        def dispatch():
            for T, idxs in sorted(buckets.items()):
                # batch dim padded to a power of two as well, so compiled
                # program count stays O(log² (texts, len)), not O(requests)
                n_pad = 1 << (len(idxs) - 1).bit_length()
                toks = np.zeros((n_pad, T), np.int32)
                lens = np.zeros((n_pad,), np.int32)
                for row, i in enumerate(idxs):
                    ids = all_ids[i]
                    toks[row, :len(ids)] = ids
                    lens[row] = len(ids)
                out = self.engine._fetch(self._embed_fn(
                    self.engine.params, self.engine._gr(toks),
                    self.engine._gr(lens)))
                for row, i in enumerate(idxs):
                    outs[i] = out[row]

        cp = self.control_plane
        if cp is None:
            dispatch()
        else:
            # followers replay embed() with the same texts — bucketing and
            # the jit body are deterministic, so the SPMD programs line
            # up. The dispatch lock keeps the broadcast AND the local
            # device dispatches atomic against the decode loop's mirrored
            # calls (and against unload), preserving the follower's FIFO
            # replay order on the leader's device queue.
            with cp.dispatch_lock:
                if self._unloaded:
                    raise RuntimeError("model unloaded")
                # lint: allow(lock-order): broadcast under dispatch_lock keeps FIFO replay order
                cp.broadcast(("lm_call", "embed", (list(texts),)))
                dispatch()
        return np.stack(outs)

    def unload(self):
        if self.scheduler is not None:
            self.scheduler.shutdown()   # may still mirror engine calls
            if self.control_plane is not None:
                # the ("unload",) broadcast must be FIFO-AFTER the loop's
                # last mirrored call: shutdown()'s bounded join can time
                # out mid-compile, and a call broadcast after unload
                # would hit followers with no engine while the leader
                # enters the collective alone
                t = getattr(self.scheduler, "_thread", None)
                if t is not None and t.is_alive():
                    t.join()
        if self.control_plane is not None:
            # under the dispatch lock: an embed holding it finishes its
            # dispatches first; embeds arriving after see _unloaded and
            # refuse instead of dispatching into a dead world
            with self.control_plane.dispatch_lock:
                self._unloaded = True
                # lint: allow(lock-order): unload must be FIFO-after the last mirrored call
                self.control_plane.broadcast(("unload",))
        METRICS.remove_gauge("tpu_model_active_slots")
        METRICS.remove_gauge("tpu_model_queue_depth")
        if self.engine.paged:
            METRICS.remove_gauge("tpu_model_kv_free_pages")
        if getattr(self.engine, "radix_enabled", False):
            METRICS.remove_gauge("tpu_model_radix_nodes")
            METRICS.remove_gauge("tpu_model_radix_pages")
        for kind in getattr(self.engine, "cache_bytes", ()):
            METRICS.remove_gauge("tpu_model_cache_bytes",
                                 f'{{kind="{kind}"}}')
        for what in getattr(self.engine, "ring_positions", ()):
            METRICS.remove_gauge("tpu_model_ring_positions",
                                 f'{{what="{what}"}}')
        for what in getattr(self.engine, "latent_positions", ()):
            METRICS.remove_gauge("tpu_model_latent_positions",
                                 f'{{what="{what}"}}')
        if getattr(self.engine, "host_cache_enabled", False):
            METRICS.remove_gauge("tpu_model_host_cache_bytes")
            METRICS.remove_gauge("tpu_model_host_cache_pages")
        METRICS.remove_gauge("tpu_model_mfu")


class _IdleScheduler:
    """Scheduler facade for embedding-only models: always quiet, never
    broken — the manager's keep-alive reaper and load-health checks read
    these fields (n_active, has_pending, qsize, finished, broken) on
    every resident model."""
    n_active = 0
    qsize = 0
    has_pending = False
    broken = False
    n_preemptions = 0
    n_restarts = 0
    finished = ()      # reaper: no completed generations to re-arm from
    # /api/ps reads these off every resident model's scheduler; an
    # encoder has no decode loop, so they are permanently "off"
    async_dispatch = False
    n_throttles = 0
    draining = False
    n_replays = 0
    n_watchdog_fires = 0

    def admission_stats(self) -> dict:
        return {}   # encoders have no waiting line to police

    def lifecycle_stats(self) -> dict:
        return {}   # no decode loop: nothing to replay, drain, or watch

    def utilization_stats(self, window_s: float = 60.0) -> dict:
        return {}   # no dispatches: nothing to account

    def begin_drain(self):
        pass        # encoders hold no streams; drain is instant

    def drain(self, timeout_s=None) -> int:
        return 0

    def shutdown(self):
        pass


class EmbeddingModel:
    """A resident encoder (BERT-family) model: tokenizer + ONE jitted
    bidirectional forward, no Engine/KV-cache/decode loop. Serves
    /api/embed, /api/embeddings, and /v1/embeddings; generation routes
    reject with a clear 400 (matching how the reference's embedding
    images behave — llama.cpp refuses generation on encoder archs)."""

    def __init__(self, name: str, cfg, params, tokenizer,
                 digest: str = ""):
        import jax.numpy as jnp
        self.name = name
        self.cfg = cfg
        self.digest = digest
        self.tokenizer = tokenizer
        self.loaded_at = time.time()
        self.params = jax.tree_util.tree_map(jnp.asarray, params)
        self.scheduler = _IdleScheduler()
        self.is_encoder = True
        self._lock = threading.Lock()

    def embed(self, texts) -> np.ndarray:
        from ..models import encoder as E
        ids = [self.tokenizer.encode(t) for t in texts]
        with self._lock:   # jit cache + single-chip dispatch serialization
            return E.embed_batch(self.params, self.cfg, ids)

    # -- generation surface: honest rejection --------------------------
    def _reject(self, *_a, **_kw):
        from ..server.app import ApiError
        raise ApiError(400, f"{self.name!r} is an embedding model "
                            f"(arch {self.cfg.arch}); it does not support "
                            f"generation — use /api/embed")

    generate = generate_stream = render_chat = render_prompt = _reject

    def unload(self):
        self.params = None
