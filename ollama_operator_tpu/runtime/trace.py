"""Request-lifecycle tracing and the crash flight recorder.

Zero-dependency observability core (stdlib only — no opentelemetry, no
prometheus_client; ROADMAP forbids new deps; jax, which the serving process
has loaded anyway, is imported by the first span and never by the operator's
control plane).  Three instruments:

1. **Per-request span timelines** (`RequestTrace`): every request carries a
   lock-cheap append-only event list stamping its path through the stack —
   queued → admit/stitch → each chunked-prefill piece → each decode dispatch
   (with epoch and launch-vs-materialize split) →
   detok → HTTP flush.  Appends are a single `list.append` of a tuple (
   GIL-atomic, no lock), so tracing rides the hot decode path at well under
   the 2% tok/s budget `bench.py measure_mixed` enforces.

2. **Flight recorder** (`FlightRecorder`): a global fixed-size ring buffer of
   structured scheduler/engine events (admissions, preemptions, restarts,
   quarantine transitions, async fallbacks, fault injections).  On a
   supervised restart or a chaos-drill fault the last N events are dumped as
   JSON lines to stderr, so every CI chaos job prints what happened *before*
   the injected failure — the crash-only analogue of a black box.

3. **Spans** (`span`, `device_scope`): ONE closed vocabulary of names from
   the socket to the kernel. A host span is a `jax.profiler.TraceAnnotation`
   (so it lands in the profiler's host plane, on the device planes' clock,
   whenever a profiler session runs) whose duration also feeds
   `tpu_model_span_seconds{span=...}`; a device scope is a
   `jax.named_scope` around a fixed part of the model step, which the
   profiler carries to every device operation's `tf_op` stat. `fold_stages`
   turns a finished request's timeline into the stage histograms
   `tpu_model_request_stage_seconds{stage=...}`.

Multi-host note: recording is strictly host-side.  Nothing here enqueues
mirrored engine calls, so followers replay the exact same device program
stream whether the leader traces or not (`runtime/follower.py` invariant).
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from ..server.metrics import GLOBAL as METRICS

# Kill switch: TPU_TRACE=0 swaps every begin() for the shared no-op trace.
# The flight recorder stays on regardless — it is the crash debugger, its
# cost is one deque append per *scheduler-level* event, not per token.
TRACE_ENABLED = os.environ.get("TPU_TRACE", "1") not in ("0", "false", "")

# How many finished request timelines the registry keeps for /debug/trace.
TRACE_KEEP = int(os.environ.get("TPU_TRACE_KEEP", "256"))

# Ring size of the flight recorder (structured events, not tokens).
FLIGHT_EVENTS = int(os.environ.get("TPU_FLIGHT_EVENTS", "512"))


class RequestTrace:
    """Span timeline for one request.

    Events are `(t_rel_s, name, fields)` tuples appended without a lock;
    `t_rel_s` is seconds since the trace began (perf_counter deltas, so
    spans subtract cleanly).  `fields` is a small dict or None.
    """

    __slots__ = ("rid", "t_wall", "_t0", "events", "cls", "tenant",
                 "t_http", "folded")

    def __init__(self, rid: str):
        self.rid = rid
        self.t_wall = time.time()
        self._t0 = time.perf_counter()
        self.events: List[tuple] = []
        # perf_counter() at which the HTTP handler began reading this
        # request (the `http.ingress` span open on the submitting thread);
        # None for a request no handler owns. fold_stages() reads both.
        self.t_http: Optional[float] = None
        self.folded = False
        # admission identity (PR 8 priority class / tenant), set by the
        # scheduler at submit() so a slow span is attributable to a class
        self.cls: Optional[str] = None
        self.tenant: Optional[str] = None

    def set_identity(self, cls: Optional[str] = None,
                     tenant: Optional[str] = None) -> None:
        if cls:
            self.cls = cls
        if tenant:
            self.tenant = tenant

    def event(self, name: str, **fields: Any) -> None:
        self.events.append(
            (time.perf_counter() - self._t0, name, fields or None))

    def event_at(self, t_abs: float, name: str, **fields: Any) -> None:
        """Record an event stamped at an earlier perf_counter() reading
        (e.g. a dispatch *launch* observed only when the handle is waited)."""
        self.events.append((t_abs - self._t0, name, fields or None))

    def to_dict(self) -> Dict[str, Any]:
        evs = []
        for t, name, fields in list(self.events):
            e = {"t_ms": round(t * 1e3, 3), "ev": name}
            if fields:
                e.update(fields)
            evs.append(e)
        out = {"id": self.rid, "t_start_unix": self.t_wall, "events": evs}
        if self.cls:
            out["class"] = self.cls
        if self.tenant:
            out["tenant"] = self.tenant
        return out

    def timings(self) -> Dict[str, Any]:
        """Condensed per-stage summary for the opt-in `timings` block in the
        final NDJSON frame (options.trace=true)."""
        first: Dict[str, float] = {}
        last: Dict[str, float] = {}
        counts: Dict[str, int] = {}
        for t, name, _ in list(self.events):
            first.setdefault(name, t)
            last[name] = t
            counts[name] = counts.get(name, 0) + 1
        out: Dict[str, Any] = {
            "spans": [{"ev": k, "first_ms": round(first[k] * 1e3, 3),
                       "last_ms": round(last[k] * 1e3, 3), "n": counts[k]}
                      for k in first],
        }
        if "admitted" in first and "queued" in first:
            out["queue_wait_ms"] = round(
                (first["admitted"] - first["queued"]) * 1e3, 3)
        return out


class _NullTrace:
    """Shared no-op stand-in when TPU_TRACE=0: call sites never branch."""

    __slots__ = ()
    rid = ""
    events: List[tuple] = []
    cls: Optional[str] = None
    tenant: Optional[str] = None
    t_http: Optional[float] = None
    folded = True

    def set_identity(self, cls: Optional[str] = None,
                     tenant: Optional[str] = None) -> None:
        pass

    def event(self, name: str, **fields: Any) -> None:
        pass

    def event_at(self, t_abs: float, name: str, **fields: Any) -> None:
        pass

    def to_dict(self) -> Dict[str, Any]:
        return {"id": "", "events": []}

    def timings(self) -> Dict[str, Any]:
        return {"spans": []}


NULL_TRACE = _NullTrace()


class Tracer:
    """Bounded registry of recent request timelines, keyed by request id.

    begin() is called by the scheduler at submit(); the trace stays
    addressable through GET /debug/trace?id= until TRACE_KEEP newer
    requests push it out."""

    def __init__(self, keep: int = TRACE_KEEP):
        self._lock = threading.Lock()
        self._keep = max(1, keep)
        self._traces: "collections.OrderedDict[str, RequestTrace]" = \
            collections.OrderedDict()

    def begin(self, rid) -> RequestTrace:
        if not TRACE_ENABLED:
            return NULL_TRACE  # type: ignore[return-value]
        tr = RequestTrace(str(rid))
        top = getattr(_tls, "top", None)
        if top is not None and top.name == "http.ingress":
            tr.t_http = top.t0
        with self._lock:
            self._traces[tr.rid] = tr
            while len(self._traces) > self._keep:
                self._traces.popitem(last=False)
        return tr

    def get(self, rid) -> Optional[RequestTrace]:
        with self._lock:
            return self._traces.get(str(rid))

    def ids(self) -> List[str]:
        with self._lock:
            return list(self._traces)


class FlightRecorder:
    """Fixed-size ring buffer of structured events; survives until dumped.

    Events are plain dicts `{"seq": n, "t_unix": ..., "kind": ..., **fields}`.
    record() takes one short lock (deque.append is atomic but the seq
    counter is not); dump() snapshots under the same lock then writes JSON
    lines outside it."""

    def __init__(self, maxlen: int = FLIGHT_EVENTS):
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(
            maxlen=max(16, maxlen))
        self._seq = 0
        self._dumps = 0

    def record(self, kind: str, **fields: Any) -> None:
        ev = {"seq": 0, "t_unix": round(time.time(), 6), "kind": kind}
        ev.update(fields)
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            self._events.append(ev)

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    @property
    def seq(self) -> int:
        """Total events ever recorded (the ring keeps only the tail)."""
        return self._seq

    @property
    def dumps(self) -> int:
        return self._dumps

    def dump(self, reason: str, stream=None, last: int = 0) -> int:
        """Print the last `last` events (0 = all buffered) as JSON lines.

        Called from the supervisor restart path and chaos drills; writes to
        stderr by default so CI job logs capture it even when the process is
        about to be torn down.  Returns the number of events printed."""
        evs = self.snapshot()
        if last > 0:
            evs = evs[-last:]
        out = stream if stream is not None else sys.stderr
        with self._lock:
            self._dumps += 1
        try:
            out.write(f"--- flight recorder dump: {reason} "
                      f"({len(evs)} events) ---\n")
            for ev in evs:
                out.write(json.dumps(ev, default=str) + "\n")
            out.write(f"--- end flight recorder dump: {reason} ---\n")
            out.flush()
        except Exception:  # lint: allow(exception-hygiene): a broken stderr must never mask the original failure
            pass
        return len(evs)

# --------------------------------------------------------------------------
# the span vocabulary: host spans, device scopes, request stages
# --------------------------------------------------------------------------

# Host spans. One row each: (name, layer as PERF.md §3 names it, what the
# span covers). The table is closed: span() of another name is a KeyError.
SPAN_TABLE = (
    ("http.ingress", "HTTP server",
     "request read to scheduler.submit: parse, template, tokenize"),
    ("http.flush", "HTTP server",
     "_StreamCoalescer.flush: frame assembled to socket write returned"),
    ("sched.hold", "scheduler",
     "_hold_pass: asleep on _wake before the pass, a chunk in flight and "
     "free slots with no request waiting yet (field end: filled|deadline)"),
    ("sched.housekeep", "scheduler",
     "shed expired, throttle, priority preemption, pool pressure relief"),
    ("sched.prefill", "scheduler", "_advance_prefill: one chunked piece"),
    ("sched.admit", "admission", "_admit_waiting (field n: admitted)"),
    ("sched.stall", "admission",
     "a pass or a decode step found the pool dry with pages fenced: the "
     "chunk in flight is landed and fanned out and the fence quiesced "
     "before anything else is dispatched (field cause)"),
    ("sched.launch", "engine dispatch",
     "engine.decode_n_launch as called from _step"),
    ("sched.wait", "engine dispatch",
     "blocked on a dispatch: DecodeHandle.wait or the synchronous decode_n"),
    ("sched.collect", "engine dispatch",
     "blocked on a launched admission's first token(s): AdmitHandle.wait "
     "(field m: requests of the dispatch)"),
    ("sched.fanout", "scheduler",
     "_fanout: grammar walk, per-request queue put"),
    ("sched.idle", "scheduler", "the _wake.wait of an idle scheduler"),
    ("engine.decode_n", "engine dispatch",
     "host time to launch one decode chunk"),
    ("engine.admit", "engine dispatch",
     "one-shot prefill + insert, to the program's dispatch (the wait for "
     "the first token lies outside: sched.collect where it is launched)"),
    ("engine.admit_many", "engine dispatch",
     "batched prefill of same-bucket prompts, to the program's dispatch"),
    ("engine.extend", "engine dispatch",
     "prefix-reusing or chunked-prefill piece, to the program's dispatch"),
    ("engine.release", "engine dispatch", "slot release / park program"),
    ("engine.install_key", "engine dispatch", "per-slot PRNG key install"),
    ("engine.upload", "engine dispatch",
     "host-to-device staging of slot state (the unnamed jit_convert_"
     "element_type / jit__lambda programs): sampling rows, active mask, "
     "block tables, stacked keys"),
    ("engine.enqueue", "engine dispatch",
     "the call that hands one compiled program to the runtime, from the "
     "call to its return: the runtime's own time, and all of the wait "
     "where it holds the launch because its queue is full (field "
     "program; a scalar's upload on one device is such a program too, "
     "jit_convert_element_type: program=scalar_upload)"),
)
SPANS: Dict[str, str] = {name: layer for name, layer, _ in SPAN_TABLE}
_SPAN_LABELS = {name: f'{{span="{name}"}}' for name in SPANS}
# the RequestTrace event a span given `rid` stamps (back-dated to its
# start, with dur_ms): the event names /debug/trace has always shown
_SPAN_EVENT = {"http.flush": "http_flush"}

# Device scopes: jax.named_scope names around the fixed parts of a model
# step, the same in the dense, cached and paged paths and in prefill.
# Compile-time only; the profiler shows them in each device operation's
# `tf_op` stat (benchmark/trace_spans.py reads that).
DEVICE_SCOPES = ("embed", "attn.qkv", "attn.kv_write", "attn.core",
                 "attn.window", "attn.index", "attn.out", "mlp", "moe.route",
                 "moe.experts", "ssm.in_proj",
                 "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out",
                 "conv.in_proj", "conv.conv", "conv.out", "delta.in_proj",
                 "delta.conv", "delta.update", "delta.gate_norm", "delta.out",
                 "lm_head", "sample")

# Kernels: the `name=` of every `pl.pallas_call` site under ops/pallas/ (eight
# sites; the fused matmul's one site serves two names), which is what a device
# trace calls the kernel's operation, under whichever scope above it ran
# (`delta_update` under delta.update, PR 45; `latent_decode` under attn.core,
# PR 51; `ring_decode` under attn.window, PR 52). tests/test_spans.py holds
# the sites to this list.
KERNEL_NAMES = ("flash_prefill", "decode_attention", "paged_v3",
                "paged_kv_write", "qmm_pallas", "qmm4_pallas", "delta_update",
                "latent_decode", "ring_decode")

# Gauges of the loaded model's cache, registered by LoadedModel from the
# engine's property of the same stem (cache_bytes, ring_positions,
# latent_positions: host arithmetic, no device work) and read by a per-layer
# metric of the benchmark: label key and the values it may take. A gauge is
# there only where the model has the thing it counts.
CACHE_GAUGES = {
    "tpu_model_cache_bytes": ("kind", ("full", "window", "state", "index")),
    "tpu_model_ring_positions": ("what", ("live", "allocated")),
    "tpu_model_latent_positions": ("what", ("live", "allocated")),
}

# Request stages folded into tpu_model_request_stage_seconds{stage=...}
STAGES = ("ingress", "queue", "prefill", "first_flush", "decode")
_STAGE_LABELS = {st: f'{{stage="{st}"}}' for st in STAGES}

for _lab in _SPAN_LABELS.values():
    METRICS.seed_histogram("tpu_model_span_seconds", _lab)
for _lab in _STAGE_LABELS.values():
    METRICS.seed_histogram("tpu_model_request_stage_seconds", _lab)

_tls = threading.local()     # .top: the innermost span open on this thread
_annotation = None           # jax.profiler.TraceAnnotation, on first use


def _annotation_cls():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def device_scope(name: str):
    """`jax.named_scope(name)` for a name of DEVICE_SCOPES."""
    if name not in DEVICE_SCOPES:
        raise KeyError(f"{name!r} is not a device scope of runtime/trace.py")
    import jax
    return jax.named_scope(name)


class span:
    """One host span: `with span("sched.admit") as sp: ...`.

    While a profiler session runs it is a TraceAnnotation (fields become
    the event's stats); always, its duration is observed into
    tpu_model_span_seconds{span=name} on exit and kept on the object
    (`t0`, `dur`, `t1`, and `self_s` = dur minus the spans nested inside it on
    this thread), so the caller needs no clock read of its own. With
    `rid` (a request id or its RequestTrace) the span is also stamped on
    that request's timeline, back-dated to its start. begin()/end() serve
    a span that opens and closes in different functions; end() is
    idempotent and cancel() closes without recording."""

    __slots__ = ("name", "t0", "dur", "self_s", "_label", "_rid", "_fields",
                 "_ann", "_parent", "_child_s")

    def __init__(self, name: str, rid=None, **fields: Any):
        self._label = _SPAN_LABELS[name]     # KeyError: the table is closed
        self.name = name
        self._rid = rid
        self._fields = fields
        self.t0 = 0.0
        self.dur: Optional[float] = None
        self.self_s = 0.0
        self._ann = None
        self._child_s = 0.0

    @property
    def t1(self) -> float:
        """perf_counter() at which the span ended."""
        return self.t0 + self.dur

    def set(self, **fields: Any) -> None:
        """Fields known only inside the span (e.g. how many it admitted)."""
        self._fields.update(fields)
        if self._ann is not None:
            self._ann.set_metadata(**fields)

    def begin(self) -> "span":
        ann = _annotation_cls()
        if ann.is_enabled():
            self._ann = ann(self.name, **self._fields)
            self._ann.__enter__()
        self._parent = getattr(_tls, "top", None)
        _tls.top = self
        self.t0 = time.perf_counter()
        return self

    def _close(self) -> float:
        dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if getattr(_tls, "top", None) is self:
            _tls.top = self._parent
        return dur

    def end(self) -> None:
        if self.dur is not None:
            return
        self.dur = dur = self._close()
        self.self_s = max(dur - self._child_s, 0.0)
        if self._parent is not None:
            self._parent._child_s += dur
        METRICS.observe("tpu_model_span_seconds", dur, self._label)
        rid = self._rid
        if rid is not None:
            tr = rid if hasattr(rid, "event_at") else TRACER.get(rid)
            if tr is not None:
                tr.event_at(self.t0, _SPAN_EVENT.get(self.name, self.name),
                            dur_ms=round(dur * 1e3, 3), **self._fields)

    def cancel(self) -> None:
        if self.dur is None:
            self.dur = self._close()

    __enter__ = begin

    def __exit__(self, *exc) -> None:
        self.end()


def fold_stages(tr) -> Dict[str, float]:
    """Fold a finished request's timeline into the stage histograms, once.

    ingress: handler start to `queued`; queue: `queued` to the start of the
    request's first prefill dispatch (its slot was granted; `admitted` is
    stamped after that dispatch returned); prefill: from there to
    `first_token` on the host; first_flush: `first_token` to the first
    `http_flush`; decode: `first_token` to `finish`. A stage whose ends the
    timeline lacks (shed, error, no handler) is not observed. The handler
    calls this when its response has ended; a request no handler owns
    (t_http None) is folded by the scheduler at `finish`. Returns the
    stages observed, in seconds."""
    if tr.folded:
        return {}
    tr.folded = True
    at: Dict[str, float] = {}
    for t, name, fields in list(tr.events):
        if name in ("prefill", "prefill_piece"):
            name, t = "prefill_start", t - fields["dur_ms"] / 1e3
        elif name == "http_flush" and fields and "dur_ms" in fields:
            t += fields["dur_ms"] / 1e3      # to the socket write's return
        at.setdefault(name, t)
    if tr.t_http is not None:
        at["http"] = tr.t_http - tr._t0
    out: Dict[str, float] = {}
    for stage, a, b in (("ingress", "http", "queued"),
                        ("queue", "queued", "prefill_start"),
                        ("prefill", "prefill_start", "first_token"),
                        ("first_flush", "first_token", "http_flush"),
                        ("decode", "first_token", "finish")):
        if a in at and b in at:
            out[stage] = max(at[b] - at[a], 0.0)
            METRICS.observe("tpu_model_request_stage_seconds", out[stage],
                            _STAGE_LABELS[stage])
    return out


TRACER = Tracer()
FLIGHT = FlightRecorder()
