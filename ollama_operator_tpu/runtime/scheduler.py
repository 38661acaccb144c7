"""Continuous-batching scheduler: the host-side loop around the engine.

This is the serving loop of the model server — the piece the reference gets
from `ollama serve` inside the delegated container
(/root/reference/pkg/model/pod.go:14-66). One daemon thread owns the engine:

  admit waiting requests into free slots (prefill) → one decode step for all
  active slots → fan tokens out to per-request queues → retire finished
  slots → repeat; park when idle.

Requests are token-in/token-out here; text concerns (detokenisation, stop
strings, templates) live a layer up in server/. Cancellation is cooperative:
the slot is released on the next loop iteration.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import queue
import sys
import threading
import time
import traceback
from typing import Iterator, List, Optional, Sequence

import numpy as np

from ..ops.constrain import GrammarTable
from ..server.metrics import GLOBAL as METRICS
from . import accounting
from .admission import (DEFAULT_TENANT, PRIORITY_RANK, AdmissionQueue,
                        TenantRateLimited, TenantRateLimiter,
                        observed_throughput_tps, predict_queue_wait_s,
                        retry_after_s, shed_labels)
from .engine import Engine, SlotOptions
from .errors import BadRequest, DeadlineExceeded
from .faults import FAULTS, InjectedFault
from .paged import PagesExhausted
from .trace import FLIGHT, TRACER, fold_stages, span


# tpu_model_admissions_total's label values (Scheduler._own)
_ADMIT_MODE = {m: f'{{mode="{m}"}}' for m in ("launched", "awaited")}
# tpu_model_pass_holds_total's and tpu_model_decode_launches_total's
# (Scheduler._hold_pass, _step)
_HOLD_END = {e: f'{{end="{e}"}}' for e in ("filled", "deadline", "none")}
_LAUNCH_TIMING = {t: f'{{timing="{t}"}}' for t in ("ahead", "late", "empty")}
# tpu_model_page_stalls_total's and tpu_model_admission_passes_total's
# (Scheduler._stall_for_pages, _admit_waiting)
_STALL_CAUSE = {c: f'{{cause="{c}"}}' for c in (
    "pool_dry_admit", "pool_dry_stitch", "pool_dry_decode")}
_PASS_STALLED = {False: '{stalled="no"}', True: '{stalled="yes"}'}


class SchedulerBusy(RuntimeError):
    """Raised by submit() when the waiting queue is full (backpressure).
    ``retry_after_s`` rides into the HTTP 503's Retry-After header —
    computed from the admission queue model when one is available."""

    def __init__(self, msg: str, *, retry_after_s: int = 1):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class SchedulerOverloaded(SchedulerBusy):
    """Raised by submit() when the admission queue model predicts the
    request would miss its TTFT SLO — rejected up front (503 + computed
    Retry-After) instead of burning a queue slot and prefill work on a
    doomed request."""


class SchedulerBroken(RuntimeError):
    """Raised by submit() after repeated engine failures wedged the loop."""


class WatchdogTimeout(RuntimeError):
    """Raised on the scheduler thread when a dispatch wait exceeds the
    hung-dispatch watchdog budget (TPU_DISPATCH_WATCHDOG_MS, or the
    auto-derived ceiling from the dispatch histograms). Treated exactly
    like an engine failure: supervised restart, then replay."""


# Lifecycle knobs are read per call, not cached at construction: a test
# (or an operator live-tuning a deployment) can flip them on a running
# scheduler and the next restart/drain honors the new value.

def replay_max_streams() -> int:
    """TPU_RESTART_REPLAY_MAX: streams replayed per restart (0 = replay
    disabled — every in-flight stream errors exactly once, PR 2
    semantics)."""
    return int(os.environ.get("TPU_RESTART_REPLAY_MAX", "64") or "0")


def replay_token_budget() -> int:
    """TPU_RESTART_REPLAY_TOKENS: aggregate prompt+generated tokens the
    replay prefill may re-process per restart — bounds the recovery
    stall a restart can add before fail-safe erroring kicks in."""
    return int(os.environ.get("TPU_RESTART_REPLAY_TOKENS", "65536")
               or "0")


def drain_timeout_s() -> float:
    """TPU_DRAIN_TIMEOUT_S: how long drain() lets running streams finish
    before shedding stragglers (the operator sizes the pod's
    terminationGracePeriodSeconds from this plus shutdown slack)."""
    return float(os.environ.get("TPU_DRAIN_TIMEOUT_S", "30") or "0")


@dataclasses.dataclass
class RequestStats:
    n_prompt: int = 0
    n_generated: int = 0
    n_reused: int = 0       # prompt tokens served from the prefix cache
    t_submit: float = 0.0
    t_admitted: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0

    @property
    def ttft_s(self) -> float:
        return max(self.t_first_token - self.t_submit, 0.0)

    @property
    def decode_tok_s(self) -> float:
        dur = self.t_done - self.t_first_token
        if dur <= 0 or self.n_generated <= 1:
            return 0.0
        return (self.n_generated - 1) / dur


class Request:
    _ids = iter(range(1, 1 << 62))
    _ids_lock = threading.Lock()

    def __init__(self, prompt_ids: Sequence[int], opts: SlotOptions,
                 max_tokens: int, eog_ids: frozenset,
                 embeds: Optional[np.ndarray] = None, constraint=None,
                 deadline: Optional[float] = None,
                 priority: str = "normal",
                 tenant: str = DEFAULT_TENANT):
        with Request._ids_lock:
            self.id = next(Request._ids)
        # admission-policy state (host-side only — never broadcast):
        # priority class, fairness tenant, and the WDRR token cost
        # (prompt + predicted decode tokens, refined by submit())
        self.priority = priority
        self.rank = PRIORITY_RANK.get(priority, 1)
        self.tenant = tenant
        self.cost = float(len(prompt_ids) + max_tokens)
        # throttle-preemption resume gate: _next_waiting must not hand
        # this request a slot again before this monotonic stamp
        self.resume_at = 0.0
        self.prompt_ids = np.asarray(prompt_ids, np.int32)
        self.embeds = embeds          # [n_prompt, D] multimodal embeddings
        self.constraint = constraint  # ops/constrain.py grammar state
        # device-grammar program for this request's token table: None =
        # not yet resolved, False = unavailable (capability off, build
        # failed, or another grammar owns the device tables), else the
        # installed GrammarTable (see Scheduler._grammar_table)
        self._gtable = None
        self.opts = opts
        self.max_tokens = max_tokens
        self.eog_ids = eog_ids
        self.out: queue.Queue = queue.Queue()
        self.cancelled = threading.Event()
        self.stats = RequestStats(n_prompt=len(self.prompt_ids),
                                  t_submit=time.monotonic())
        # span timeline (runtime/trace.py): queued → admit/stitch →
        # prefill pieces → decode dispatches → detok → HTTP flush.
        # begin() returns the shared no-op trace when TPU_TRACE=0.
        self.trace = TRACER.begin(self.id)
        # monotonic stamp of the last token chunk delivered, for the
        # chunk-normalized tpu_model_itl_seconds observation in _fanout
        self._t_last_emit = 0.0
        self.slot: Optional[int] = None
        self.error: Optional[str] = None
        # absolute time.monotonic() budget, or None for no deadline:
        # expired while queued → shed (503), expired mid-generation →
        # terminal frame with finish reason "timeout"
        self.deadline = deadline
        # terminal reason from the ("done", reason) frame, readable after
        # chunks()/tokens() returns — "stop", "length", "timeout", ...
        self.done_reason: Optional[str] = None
        # every sampled token (incl. EOG), for parking the slot's KV as a
        # reusable prefix after the request finishes
        self.all_tokens: List[int] = []
        # set when the request is preempted (paged pool pressure): the
        # full prompt + tokens generated so far; re-admission prefills
        # from here and generation continues seamlessly on the same
        # output queue
        self.resume_ids: Optional[np.ndarray] = None

    @property
    def admit_ids(self) -> np.ndarray:
        return (self.resume_ids if self.resume_ids is not None
                else self.prompt_ids)

    def cancel(self):
        self.cancelled.set()

    def tokens(self) -> Iterator[int]:
        """Blocking iterator over generated token ids."""
        for chunk in self.chunks():
            for tid in chunk:
                yield tid

    def chunks(self) -> Iterator[List[int]]:
        """Blocking iterator over per-dispatch batches of token ids.

        The scheduler queues ONE item per decode chunk (plus one for the
        prefill-sampled token), not one per token — consumers that can
        batch (detokenisation, HTTP frame assembly) should iterate here
        instead of tokens() to keep queue/lock traffic per request at
        O(generated / decode_chunk)."""
        while True:
            kind, payload = self.out.get()
            if kind == "tokens":
                yield payload
            elif kind == "done":
                self.done_reason = payload
                return
            elif kind == "shed":
                msg, retry_after_s = payload
                raise DeadlineExceeded(msg, while_queued=True,
                                       retry_after_s=retry_after_s)
            else:  # error
                raise RuntimeError(payload)


class _PrefillJob:
    """A request whose prompt is admitting piece by piece (chunked
    prefill): ``done`` tokens of ``req.admit_ids`` are already in the
    slot's KV cache. Between pieces the slot is parked (engine-inactive),
    so the scheduler — not the engine — must remember it is taken."""

    __slots__ = ("req", "done")

    def __init__(self, req: Request, done: int):
        self.req = req
        self.done = done


class Scheduler:
    # a parked prefix must beat this many cached tokens to be worth an
    # extend over a fresh admit (tiny reuses still pay a full slice+write)
    MIN_PREFIX_REUSE = 16
    # ceiling on the supervised-restart backoff (it doubles per
    # consecutive failure starting from restart_backoff)
    RESTART_BACKOFF_CAP = 2.0
    # the clock _hold_pass reads its deadline on: the engine's and the
    # spans' (a test puts its own here, and a _wake whose wait() moves it)
    _now = staticmethod(time.perf_counter)

    def __init__(self, engine: Engine, max_queue: int = 256,
                 max_restarts: Optional[int] = None,
                 restart_backoff: Optional[float] = None,
                 prefill_chunk: Optional[int] = None,
                 async_dispatch: Optional[bool] = None):
        self.engine = engine
        # reuse floor (TPU_MIN_PREFIX_REUSE): prefixes shorter than this
        # admit cold — a tiny reuse still pays a full extend dispatch, so
        # raising the floor trades cache hits for fewer small programs;
        # lowering it helps only when dispatch is near-free (colocated
        # host). Parked-slot reuse and radix stitches honor the same
        # floor.
        self.min_prefix_reuse = int(os.environ.get(
            "TPU_MIN_PREFIX_REUSE", "") or self.MIN_PREFIX_REUSE)
        # radix prefix cache (paged, single sub-pool): finished prefixes
        # are donated to a shared page-granular tree instead of parked in
        # one slot, so N concurrent requests can hit the same prefix
        self._use_radix = bool(getattr(engine, "radix_enabled", False))
        # crash-only supervision: after a decode-loop failure the engine
        # state is rebuilt in-process up to max_restarts consecutive
        # times before the scheduler goes terminally `broken` (which
        # needs a model reload / pod restart to clear)
        self.max_restarts = (
            max_restarts if max_restarts is not None
            else int(os.environ.get("TPU_ENGINE_MAX_RESTARTS", "3")))
        self.restart_backoff = (
            restart_backoff if restart_backoff is not None
            else float(os.environ.get("TPU_ENGINE_RESTART_BACKOFF_S",
                                      "0.05")))
        self.n_restarts = 0
        # stall-free chunked prefill (Sarathi-style): prompts longer than
        # one piece admit bucket-by-bucket through Engine.extend, a
        # budget of pieces per scheduler step (_piece_tokens), so the
        # worst-case stall a DECODING slot sees is that budget's
        # prefill, not a prompt's. 0 disables; unset derives from
        # decode_chunk (rounded up to a real bucket).
        if prefill_chunk is None:
            pc_env = os.environ.get("TPU_PREFILL_CHUNK", "")
            prefill_chunk = (int(pc_env) if pc_env
                             else engine.ecfg.decode_chunk * 8)
        self.prefill_chunk = (
            engine.bucket_for(min(int(prefill_chunk), engine.max_seq))
            if prefill_chunk and engine.supports_extend else 0)
        # the prompt tokens the pieces of one scheduler step may hold
        # together: what the chunk launched behind them decodes (every
        # slot, decode_chunk steps), and one piece at the least. So the
        # stall a decoding slot sees from chunked prompts stays about one
        # chunk's own time, and a mix whose prompts are no longer than
        # its answers never queues jobs behind one another. _step refills
        # it; an awaited piece spends all of it (_dispatch_piece).
        self._piece_tokens = max(
            self.prefill_chunk, engine.n_slots * engine.ecfg.decode_chunk)
        self._piece_budget = self._piece_tokens
        # double-buffered async dispatch: launch decode dispatch N+1
        # before materialising N's tokens, so host fan-out/detokenise
        # overlaps device compute (JAX async dispatch). The only
        # remaining sync fallback is HOST-masked grammar (a fresh host
        # PDA mask per token — device-table grammar slots ride async,
        # see _fanout). Paged mode double-buffers too, dp-sharded pools
        # included: the page table's epoch fence quarantines freed pages
        # until the dispatch that captured their block table
        # materialises (ShardedPageTable delegates the fence per shard),
        # so recycling can never corrupt an in-flight program's reads
        # (runtime/paged.py).
        if async_dispatch is None:
            async_dispatch = os.environ.get(
                "TPU_ASYNC_DISPATCH", "1").lower() not in ("0", "false")
        self.async_dispatch = bool(async_dispatch)
        # epoch of the newest decode handle already materialised — the
        # next launch passes it back as retire= so the engine unfences
        # pages quarantined at or before it (and so followers, which
        # never wait on handles, retire at the identical call position)
        self._fence_ack = 0
        # slot → _PrefillJob for requests mid-chunked-prefill (the slot
        # is engine-inactive between pieces; without this map
        # free_slots() would hand it to someone else)
        self._prefilling: dict = {}
        # (DecodeHandle, {slot: request-at-launch}) of the in-flight
        # decode dispatch, when double-buffering
        self._pending = None
        # the admissions this pass launched and has not collected, oldest
        # first: (AdmitHandle, [(slot, request, start, end, of)]): the
        # dispatch prefilled [start, end) of a prompt of ``of`` tokens,
        # and its token is the request's first iff end == of (anything
        # less is a chunked admission's piece). A finished admission's
        # slot has its owner already (_own), so the chunk launched next
        # carries it; _land collects the first tokens behind that
        # launch. Empty whenever _step returns.
        self._launched: List[tuple] = []
        # whether a stall for pages opened since the admission pass began
        # (_stall_for_pages sets it, _admit_waiting clears and reads it)
        self._pass_stalled = False
        # device-grammar escape bookkeeping: slot → request whose
        # ALREADY-LAUNCHED next dispatch ran with the slot frozen
        # (its automaton escaped the device table mid-chunk); that
        # dispatch's rows for the slot are garbage and its launch-time
        # length advance rolls back at fan-out (see _fanout)
        self._gdiscard: dict = {}
        # the waiting line: strict-priority classes + per-tenant WDRR
        # over token budgets + SLO-aware early rejection
        # (runtime/admission.py). Host-side policy state only — nothing
        # here is ever mirrored to multi-host followers.
        self._admission = AdmissionQueue(max_queue=max_queue)
        # per-tenant decode-token rate limiting (TPU_TENANT_TOKEN_RATE);
        # over-rate best-effort requests are throttle-preempted into
        # _throttled and resume on the same stream once their bucket
        # refills
        self._limiter = TenantRateLimiter.from_env()
        self._throttled: List[Request] = []
        self.n_throttles = 0
        # priority preemption: a queued high-class request may evict a
        # running strictly-lower-class one (resumable preempt) instead
        # of waiting a full generation for a slot — the mechanism that
        # keeps high-priority TTFT flat at 5× offered load
        self._priority_preempt = os.environ.get(
            "TPU_PRIORITY_PREEMPT", "1").lower() not in ("0", "false")
        # EWMA of generated tokens per finished request — the "predicted
        # decode tokens" half of a request's WDRR token cost (max_tokens
        # alone over-charges every short completion)
        self._avg_decode = 64.0
        # preempted requests (paged pool pressure) re-admit before the
        # waiting queue — they already hold a place in the line
        self._preempted: List[Request] = []
        self.n_preemptions = 0
        # what the host took, in seconds, from the start of a step's
        # housekeeping until the step's FIRST program was handed to the
        # runtime (t_queued of the pass's first prefill, else of the decode
        # launch), less the time inside the runtime's launch call
        # (Engine.enqueue_s: where a full queue holds a launch): the
        # largest of the recent steps that had a chunk in flight all
        # through (one that drained for pages measures the drain), each
        # older one counting a tenth less. _hold_pass ends its hold this
        # long before the chunk in flight lands: from then on the device's
        # queue is never empty, and the rest of the pass runs in the shadow
        # of what it has queued. Not the whole pass: the runtime blocks a
        # launch while 32 programs are in flight (an admission is nine),
        # so a pass of four admissions ends only after the chunk in flight
        # has (PERF.md, PR 40). None until a step has measured one.
        self._lead_s: Optional[float] = None
        # what the last two decode chunks took, in seconds, as
        # _wait_handle accounts them (Engine._landed's interval): the hold
        # takes the chunk in flight for the shorter of the two, so one
        # chunk that held a compile or a stall lengthens no hold
        self._chunk_s: collections.deque = collections.deque(maxlen=2)
        # slot vacancy: (perf_counter() of the previous _step, slots its
        # admission pass left free, whether a request still waited then) —
        # the state that holds until the next iteration's admission pass
        self._vacancy: Optional[tuple] = None
        # restart replay (stream-preserving recovery): _fail_running
        # moves replayable in-flight requests here instead of erroring
        # them; _supervised_restart re-admits them through the preempt
        # resume machinery once the engine is rebuilt. Scheduler-thread
        # owned between shutdown()/drain() joins.
        self._recovering: List[Request] = []
        self.n_replays = 0
        self.n_replay_fallbacks = 0
        # graceful drain: submit() sheds (503 + Retry-After) while set;
        # running streams keep going until drain()'s timeout
        self.draining = False
        # hung-dispatch watchdog: a persistent helper thread runs each
        # blocking dispatch wait so the scheduler thread can bound it;
        # on a fire the worker is abandoned (fresh queues next time — a
        # late result must never be delivered to the wrong generation)
        self._wd_thread: Optional[threading.Thread] = None
        self._wd_req: Optional[queue.Queue] = None
        self._wd_resp: Optional[queue.Queue] = None
        self.n_watchdog_fires = 0
        self._running: List[Optional[Request]] = [None] * engine.n_slots
        # slot → token ids (prompt + generated) still resident in its KV
        # cache; candidates for prefix-cache reuse (ollama keeps the same
        # conversation hot in a llama.cpp slot; here any shared prefix —
        # system prompt, earlier chat turns — is reusable)
        self._parked: dict = {}
        # exclusive tasks (disagg KV export/import): closures drained at
        # the top of _step, ON the scheduler thread, so page gathers and
        # radix grafts never race a dispatch (run_exclusive)
        self._tasks: List = []
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self.broken = False
        self._consecutive_failures = 0
        self.total_generated = 0
        self.total_prompt = 0
        # utilization & goodput accounting (runtime/accounting.py):
        # per-dispatch FLOPs/goodput splits + the dispatch-wait/host/idle
        # wall-clock breakdown. make_accounting honors TPU_ACCOUNTING=0
        # at construction (bench A/B flips the module flag between arms).
        self.acct = accounting.make_accounting(getattr(engine, "cfg", None))
        self.finished: List[RequestStats] = []  # ring of recent stats
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="tpu-scheduler")
        self._thread.start()

    # ------------------------------------------------------------------
    def run_exclusive(self, fn, timeout_s: float = 30.0):
        """Run ``fn()`` on the scheduler thread, between steps, and
        return its result (re-raising its exception).  The disagg KV
        export/import paths ride this: they touch the page table, the
        radix tree, and the KV pool, none of which may be mutated while
        a dispatch is being assembled.  The scheduler drains queued
        tasks at the top of every ``_step`` — under load that is after
        the in-flight dispatch lands; idle, the wake event pops the
        0.05s wait immediately.  Raises TimeoutError if the scheduler
        thread is wedged (or broken) past ``timeout_s``; the task is
        then abandoned (a late run finds its waiter gone and discards
        the result via the ``dead`` flag)."""
        done = threading.Event()
        cell: dict = {"dead": False}

        def task():
            try:
                r = fn()
                if not cell["dead"]:
                    cell["r"] = r
            except BaseException as e:  # noqa: BLE001 — relayed to caller
                if not cell["dead"]:
                    cell["e"] = e
            finally:
                done.set()

        with self._lock:
            if self.broken:
                raise SchedulerBroken(
                    "scheduler stopped after repeated engine failures")
            self._tasks.append(task)
        self._wake.set()
        if not done.wait(timeout_s):
            cell["dead"] = True
            raise TimeoutError(
                f"scheduler did not run exclusive task in {timeout_s}s")
        if "e" in cell:
            raise cell["e"]
        return cell.get("r")

    def _run_tasks(self):
        """Drain queued exclusive tasks (scheduler thread only).  A task
        raising is the task's problem — relayed to its waiter by the
        wrapper, never a scheduler failure."""
        if not self._tasks:
            return
        with self._lock:
            tasks, self._tasks = self._tasks, []
        for t in tasks:
            t()

    def _tokens_done(self) -> float:
        """Tokens the engine has pushed through so far (prompt +
        generated), live — the numerator of the queue model's observed
        throughput."""
        return float(self.total_prompt + self.total_generated)

    def submit(self, prompt_ids: Sequence[int],
               opts: SlotOptions = SlotOptions(),
               max_tokens: int = 128,
               eog_ids: frozenset = frozenset(),
               embeds: Optional[np.ndarray] = None,
               constraint=None,
               deadline_s: Optional[float] = None,
               priority: str = "normal",
               tenant: str = DEFAULT_TENANT,
               ttft_slo_s: Optional[float] = None) -> Request:
        if len(prompt_ids) >= self.engine.max_seq:
            raise BadRequest(
                f"prompt of {len(prompt_ids)} tokens exceeds context window "
                f"{self.engine.max_seq}")
        deadline = (time.monotonic() + deadline_s
                    if deadline_s is not None and deadline_s > 0 else None)
        req = Request(prompt_ids, opts, max_tokens, eog_ids, embeds=embeds,
                      constraint=constraint, deadline=deadline,
                      priority=priority, tenant=tenant)
        # WDRR token cost: prompt + predicted decode tokens (EWMA of
        # recent completions, capped by this request's own budget)
        req.cost = float(len(prompt_ids)
                         + min(max_tokens, max(16, int(self._avg_decode))))
        # broken-check + enqueue under the lock: the failure path flips
        # `broken` and drains under the same lock, so a request can never
        # slip into the queue after the final drain (its reader would hang)
        victim = None
        with self._lock:
            if self.broken:
                raise SchedulerBroken(
                    "scheduler stopped after repeated engine failures")
            if self.draining:
                # graceful drain: running streams finish, NEW work goes
                # to the next replica — 503 + Retry-After sized to the
                # drain window so the client's retry lands post-rollout
                retry = min(120, max(1, int(drain_timeout_s())))
                METRICS.inc("tpu_model_requests_shed_total")
                METRICS.inc("tpu_model_drain_shed_total")
                FLIGHT.record("shed", rid=req.id, cause="draining",
                              cls=priority, tenant=tenant,
                              retry_after_s=retry)
                raise SchedulerBusy("server draining",
                                    retry_after_s=retry)
            cap = int(os.environ.get("TPU_TENANT_MAX_QUEUED", "0") or 0)
            if cap > 0 and self._admission.queued_for(tenant) >= cap:
                # this tenant specifically is over its share: 429, not
                # 503 — global backpressure signals would be a lie
                METRICS.inc("tpu_model_requests_shed_total")
                METRICS.inc("tpu_model_shed_total",
                            labels=shed_labels(priority, "tenant_cap"))
                FLIGHT.record("shed", rid=req.id, cause="tenant_cap",
                              cls=priority, tenant=tenant, cap=cap)
                raise TenantRateLimited(
                    f"tenant {tenant!r} already has {cap} requests "
                    f"queued", retry_after_s=min(30, max(1, cap)))
            if ttft_slo_s is not None:
                # queue model: token backlog at equal-or-higher priority
                # ÷ observed throughput. A request predicted to miss its
                # TTFT SLO is rejected NOW, with a Retry-After computed
                # from how long that backlog needs to drain — not after
                # wasting a queue slot and prefill work on a timeout.
                backlog = self._admission.backlog_tokens(req.rank)
                try:
                    predicted = predict_queue_wait_s(backlog,
                                                     self._tokens_done())
                except Exception as e:  # noqa: BLE001 — incl. injected
                    # faults at admission.predict: the predictor is an
                    # optimisation, so it fails OPEN (admit; the
                    # deadline machinery still covers the request)
                    FLIGHT.record("admission_predict_failed",
                                  rid=req.id, error=str(e)[:120])
                    predicted = 0.0
                if predicted > ttft_slo_s:
                    tps = observed_throughput_tps(self._tokens_done())
                    retry = retry_after_s(predicted, ttft_slo_s, tps)
                    METRICS.inc("tpu_model_requests_shed_total")
                    METRICS.inc("tpu_model_shed_total",
                                labels=shed_labels(priority,
                                                   "slo_predict"))
                    FLIGHT.record(
                        "early_reject", rid=req.id, cls=priority,
                        tenant=tenant,
                        predicted_ms=int(predicted * 1e3),
                        slo_ms=int(ttft_slo_s * 1e3), retry_after_s=retry)
                    raise SchedulerOverloaded(
                        f"predicted queue wait {predicted:.2f}s exceeds "
                        f"ttft_slo {ttft_slo_s:.2f}s",
                        retry_after_s=retry)
            accepted, victim = self._admission.offer(req)
            if not accepted:
                # full and nothing lower-priority to displace: reject
                # the incoming request with a computed Retry-After and
                # record its (zero-length) queue wait — the same
                # accounting every other shed path gets
                retry = self._retry_after_estimate(req.rank)
                self._observe_wait(req)
                METRICS.inc("tpu_model_requests_shed_total")
                METRICS.inc("tpu_model_shed_total",
                            labels=shed_labels(priority, "queue_full"))
                FLIGHT.record("shed", rid=req.id, cause="queue_full",
                              cls=priority, tenant=tenant,
                              qsize=self._admission.max_queue,
                              retry_after_s=retry)
                raise SchedulerBusy(
                    f"request queue full ({self._admission.max_queue} "
                    f"waiting)", retry_after_s=retry) from None
        if victim is not None:
            # queue pressure displaced a strictly lower-priority queued
            # request (shed-lowest-first); outside the lock — _shed
            # takes it for the finished ring. The dedicated "displaced"
            # event (distinct from the victim's own "shed") puts the
            # *eviction* in the flight-recorder timeline with both sides'
            # identities.
            FLIGHT.record("displaced", rid=victim.id, cls=victim.priority,
                          tenant=victim.tenant, by=req.id,
                          by_cls=req.priority)
            self._shed(victim, cause="queue_full")
        req.trace.set_identity(priority, tenant)
        req.trace.event("queued", n_prompt=len(prompt_ids),
                        max_tokens=max_tokens, cls=priority,
                        tenant=tenant)
        self._wake.set()
        return req

    def _retry_after_estimate(self, rank: int) -> int:
        """Retry-After for a rejected request: queue-model drain time of
        the backlog at its priority, floored at 1s (falls back to a
        depth heuristic when the model has no throughput signal yet)."""
        backlog = self._admission.backlog_tokens(rank)
        tps = observed_throughput_tps(self._tokens_done())
        if tps > 0:
            return int(min(max(1, round(backlog / tps + 0.5)), 120))
        return min(30, max(1, self.qsize))

    def shutdown(self):
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=10)
        # idle watchdog worker exits on the sentinel; an ABANDONED one
        # (post-fire) is a daemon parked on a dead queue — harmless
        if self._wd_req is not None:
            self._wd_req.put(None)
            self._wd_thread = None
        # an in-flight dispatch's tokens die with the loop; its owners
        # are still in _running and drain below
        self._pending = None
        self._launched.clear()
        self._prefilling.clear()
        # unfence anything the dropped dispatch was holding: the engine
        # may outlive this scheduler (model swap builds a fresh one), and
        # a page parked in quarantine forever is a pool leak
        try:
            if self.engine.quarantined_pages:
                self.engine.fence_quiesce()
        except Exception:  # lint: allow(exception-hygiene): engine may already be torn down
            pass
        # drain everything still attached so no caller blocks forever on
        # req.tokens() after an unload (model swap, server shutdown)
        for slot, req in enumerate(self._running):
            if req is not None:
                self._running[slot] = None
                req.stats.t_done = time.monotonic()
                req.out.put(("done", "unloaded"))
        for req in self._preempted + self._throttled + self._recovering:
            req.out.put(("done", "unloaded"))
        self._preempted.clear()
        self._throttled.clear()
        self._recovering.clear()
        for req in self._admission.drain():
            req.out.put(("done", "unloaded"))

    def begin_drain(self):
        """Flip into draining (the SIGTERM path): new submits shed with
        503 + Retry-After, running streams keep generating. Idempotent;
        cleared only by tearing the scheduler down."""
        with self._lock:
            if self.draining or self.broken:
                return
            self.draining = True
        METRICS.inc("tpu_model_drain_started_total")
        FLIGHT.record("drain", phase="begin", running=self.n_active,
                      queued=self.qsize)

    def drain(self, timeout_s: Optional[float] = None) -> int:
        """Graceful drain: begin_drain(), wait up to ``timeout_s``
        (default TPU_DRAIN_TIMEOUT_S) for every attached stream to
        finish, then shed stragglers — running streams get a terminal
        ``("done", "drain")`` frame (partial output stands, finish
        reason tells the client it was a rollout, not a stop token),
        waiting ones shed 503. Returns the straggler count. The decode
        loop is stopped before straggler teardown (drain is always
        followed by shutdown), so the teardown can't race a dispatch."""
        self.begin_drain()
        if timeout_s is None:
            timeout_s = drain_timeout_s()
        deadline = time.monotonic() + max(0.0, timeout_s)
        while time.monotonic() < deadline:
            if not self.has_pending:
                break
            time.sleep(0.02)
        shed = 0
        if self.has_pending:
            self._stop.set()
            self._wake.set()
            self._thread.join(timeout=10)
            self._pending = None
            self._prefilling.clear()
            try:
                if self.engine.quarantined_pages:
                    self.engine.fence_quiesce()
            except Exception:  # lint: allow(exception-hygiene): engine may be torn down
                pass
            retry = min(120, max(1, int(timeout_s) or 1))
            for slot, req in enumerate(self._running):
                if req is None:
                    continue
                self._running[slot] = None
                req.stats.t_done = time.monotonic()
                req.out.put(("done", "drain"))
                try:
                    self.engine.release(slot)
                except Exception:  # lint: allow(exception-hygiene): best-effort teardown
                    pass
                shed += 1
            for req in (self._preempted + self._throttled
                        + self._recovering):
                req.out.put(("shed", ("server draining", retry)))
                shed += 1
            self._preempted.clear()
            self._throttled.clear()
            self._recovering.clear()
            for req in self._admission.drain():
                req.out.put(("shed", ("server draining", retry)))
                shed += 1
            if shed:
                METRICS.inc("tpu_model_drain_shed_total", float(shed))
        FLIGHT.record("drain", phase="complete", shed=shed)
        return shed

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self._running)

    @property
    def qsize(self) -> int:
        """Requests waiting for a slot (queued + preempted + throttled +
        recovering). Public API for metrics and the server's load probes
        — external code must not reach into the admission queue."""
        return (len(self._admission) + len(self._preempted)
                + len(self._throttled) + len(self._recovering))

    @property
    def has_pending(self) -> bool:
        """True while any request is running, queued, preempted,
        throttled, or awaiting restart replay — i.e. unloading the model
        now would strand a caller."""
        return (self.n_active > 0 or bool(self._preempted)
                or bool(self._throttled) or bool(self._recovering)
                or not self._admission.empty())

    def admission_stats(self) -> dict:
        """Live admission-policy snapshot for /api/ps: per-class queue
        depth/backlog, throttle state, and the policy knobs in force."""
        out = self._admission.stats()
        out.update({
            "default_priority": os.environ.get("TPU_DEFAULT_PRIORITY",
                                               "normal") or "normal",
            "ttft_slo_ms": float(os.environ.get("TPU_TTFT_SLO_MS", "0")
                                 or 0),
            "priority_preempt": self._priority_preempt,
            "rate_limited_tenants": self._limiter.enabled,
            "throttled": len(self._throttled),
            "throttles": self.n_throttles,
            "shed_by_class": {
                p: int(sum(METRICS.get("tpu_model_shed_total",
                                       shed_labels(p, c))
                           for c in ("queue_full", "deadline",
                                     "slo_predict", "tenant_cap")))
                for p in PRIORITY_RANK},
        })
        return out

    def lifecycle_stats(self) -> dict:
        """Lifecycle snapshot for /api/ps: serving/draining/broken state,
        the restart-replay budget in force, and watchdog posture."""
        return {
            "state": ("broken" if self.broken
                      else "draining" if self.draining else "serving"),
            # disagg pool role stamped by the operator on pool
            # Deployments; "" = unified replica (routing is the
            # gateway's job — this is the observable, not the switch)
            "pool": os.environ.get("TPU_DISAGG_ROLE", ""),
            # live work counters: the operator's drain-first scale-down
            # polls these to know when a victim replica is empty
            "active_streams": self.n_active,
            "queued": self.qsize,
            "restarts": self.n_restarts,
            "replay": {
                "enabled": replay_max_streams() > 0,
                "max_streams": replay_max_streams(),
                "token_budget": replay_token_budget(),
                "replayed_streams": self.n_replays,
                "fallbacks": self.n_replay_fallbacks,
                "recovering": len(self._recovering),
            },
            "watchdog": {
                "timeout_s": round(self._watchdog_timeout_s(), 3),
                "fires": self.n_watchdog_fires,
            },
        }

    def utilization_stats(self, window_s: float = 60.0) -> dict:
        """Utilization snapshot for /api/ps (and the operator's Model CR
        status mirror): MFU, goodput, occupancy/waste, wall-clock
        breakdown, and the engine's mid-serving recompile counts."""
        out = self.acct.snapshot(window_s)
        out["recompiles"] = dict(getattr(self.engine, "recompiles", {}))
        return out

    # ------------------------------------------------------------------
    def _finish(self, slot: int, req: Request, reason: str):
        # the LAST sampled token was never fed back through the model, so
        # its K/V is not (reliably) in the cache — park everything before it
        parkable = (list(req.prompt_ids) + req.all_tokens)[:-1]
        park = (self.engine.supports_extend and req.embeds is None
                and reason in ("stop", "length") and len(parkable) > 0)
        if self._use_radix:
            # radix mode: donate the full-page-aligned prefix to the
            # shared tree (pages pinned, slot freed) instead of parking
            # the whole thing in this one slot
            if park:
                self.engine.donate_prefix(slot, parkable)
            else:
                self.engine.release(slot)
        else:
            self.engine.release(slot, park=park)
            if park:
                self._parked[slot] = parkable
            else:
                self._parked.pop(slot, None)
        self._running[slot] = None
        req.stats.t_done = time.monotonic()
        # EWMA of decode lengths feeds the admission cost model (token
        # budget = prompt + predicted decode, not request counts)
        self._avg_decode += 0.2 * (req.stats.n_generated - self._avg_decode)
        req.trace.event("finish", reason=reason, slot=slot,
                        n_generated=req.stats.n_generated)
        if req.trace.t_http is None:
            # no HTTP handler owns this request: nobody else will fold it
            fold_stages(req.trace)
        with self._lock:
            self.finished.append(req.stats)
            if len(self.finished) > 512:
                self.finished = self.finished[-256:]
        req.out.put(("done", reason))

    def _emit_first(self, req: Request, tid: int) -> bool:
        """Queue the prefill-sampled token as its own chunk; returns False
        if the request just finished. This token flushes immediately —
        it IS the TTFT token, and holding it back to the first decode
        flush would add a whole chunk dispatch to first-token latency."""
        if req.stats.n_generated == 0:
            # guard: a preempted request re-admitting must keep its
            # original first-token stamp
            req.stats.t_first_token = time.monotonic()
        req.all_tokens.append(tid)  # EOG included: it sits in the KV cache
        if tid in req.eog_ids:
            return False
        req.stats.n_generated += 1
        self.total_generated += 1
        req._t_last_emit = time.monotonic()
        req.trace.event("first_token")
        self._limiter.debit(req.tenant, 1)
        METRICS.inc("tpu_model_tenant_decode_tokens_total", 1.0,
                    f'{{tenant="{req.tenant}"}}')
        req.out.put(("tokens", [tid]))
        return req.stats.n_generated < req.max_tokens

    def _best_prefix(self, req: Request):
        """(slot, reuse_len) of the parked slot sharing the longest token
        prefix with the request, or (None, 0). At least one tail token must
        remain to prefill (the parked last position has no cached logits),
        and the tail's bucket must fit above the reused prefix."""
        if (self._use_radix or req.embeds is not None
                or not self.engine.supports_extend):
            return None, 0
        ids = req.admit_ids
        best, best_m = None, 0
        # a recurrent state stands where its sequence ended and cannot be
        # cut back: such a slot is reused only whole, and only if the
        # engine stopped where the parked sequence does (a stream that
        # ended mid-chunk left its slot some steps further on)
        whole = getattr(self.engine, "recurrent", False)
        for slot, parked in self._parked.items():
            k = min(len(parked), len(ids) - 1)
            m = 0
            while m < k and parked[m] == ids[m]:
                m += 1
            if whole and not (m == len(parked)
                              == self.engine.state_position(slot)):
                continue
            if m > best_m:
                best, best_m = slot, m
        if best is None or best_m < self.min_prefix_reuse:
            return None, 0
        tail_bucket = self.engine.bucket_for(len(ids) - best_m)
        if best_m + tail_bucket > self.engine.max_seq:
            return None, 0
        return best, best_m

    def _quiesce(self, cause: str) -> int:
        """engine.fence_quiesce with a flight-recorder breadcrumb:
        quarantine transitions are exactly the events that explain a
        mysterious pool-dry stall after the fact."""
        n_q = self.engine.quarantined_pages
        freed = self.engine.fence_quiesce()
        if n_q or freed:
            FLIGHT.record("fence_quiesce", cause=cause,
                          quarantined=n_q, freed=freed)
        return freed

    def _stall_for_pages(self, cause: str) -> None:
        """The pool is dry with a chunk in flight or pages fenced behind
        one, and the engine found no cached page that is free at once
        (Engine._make_room evicts those before it raises: a leaf no slot
        has mapped since the retired epoch is in no block table a
        program in flight captured): land and fan out what is in flight
        and unfence, before anything else is dispatched. What is left to
        stall for is pages whose slots went with the chunk in flight
        still holding their rows: a tree too young to have older leaves,
        a pool the live sequences fill. The device runs dry meanwhile,
        so the stall is a span of its own (sched.stall; the wait, the
        collects, the fan-out and its releases nest inside it) and is
        counted by cause in tpu_model_page_stalls_total; the pass it
        interrupts counts as stalled (_admit_waiting). Evicting cached
        pages that are free at once is no stall."""
        self._pass_stalled = True
        METRICS.inc("tpu_model_page_stalls_total", 1.0, _STALL_CAUSE[cause])
        with span("sched.stall", cause=cause):
            self._drain_pending()
            self._quiesce(cause)

    def _next_waiting(self) -> Optional[Request]:
        """Priority-aware head of the waiting line. Preempted requests
        still re-admit ahead of queued ones OF THE SAME CLASS (they
        already held a place in line), but a queued higher-priority
        request now beats a preempted lower-priority one — the FIFO
        version of this method is what made overload ordering
        arbitrary."""
        best_i = None
        for i, r in enumerate(self._preempted):
            if best_i is None or r.rank < self._preempted[best_i].rank:
                best_i = i
        qrank = self._admission.peek_rank()
        if best_i is not None:
            if qrank is None or self._preempted[best_i].rank <= qrank:
                return self._preempted.pop(best_i)
        return self._admission.pop()

    def _evict_one_parked(self, n_pages: int = 1) -> bool:
        """Return cached pages to the pool under pressure. Radix mode:
        evict up to ``n_pages`` LRU-unreferenced radix leaves (page
        granular — cold tails of cold prefixes go first). Parked-slot
        mode: drop one whole parked prefix (oldest parked first). False
        when there was nothing to evict."""
        if self._use_radix:
            return self.engine.radix_evict(n_pages) > 0
        for slot in list(self._parked):
            if self._running[slot] is None:
                self._parked.pop(slot)
                self.engine.free_slot_pages(slot)
                return True
        return False

    def _stitch_admission(self, slot: int, req: Request) -> int:
        """Radix-mode admission prep: probe the tree, apply the reuse
        floor and the tail-bucket fit (trimming page-by-page keeps the
        stitch page-aligned — the partial boundary drops first), then
        stitch the shared pages into ``slot``. A dry pool during the
        copy-on-write falls back to a cold admit (stitch leaves the slot
        clean) after nudging eviction along."""
        ids = req.admit_ids
        want = self.engine.prefix_probe(ids)
        ps = self.engine.ecfg.page_size
        while (want >= self.min_prefix_reuse
               and want + self.engine.bucket_for(len(ids) - want)
               > self.engine.max_seq):
            want = (want - 1) // ps * ps
        if want < self.min_prefix_reuse:
            return 0
        try:
            t0 = time.perf_counter()
            got = self.engine.stitch(slot, ids, want)
            ls = getattr(self.engine, "last_stitch", None)
            if got:
                # per-tier breakdown rides the request to _post_admit
                # (metrics attribution); restitch latency is observed
                # enqueue-side — the uploads themselves overlap the tail
                # prefill asynchronously
                req._tier_stitch = ls
                if ls and (ls["t1"] or ls["t2"]):
                    METRICS.observe("tpu_model_restitch_seconds",
                                    time.perf_counter() - t0)
                req.trace.event("stitch", slot=slot, reused=got, tiers=ls)
            return got
        except PagesExhausted:
            if self._pending is not None or self.engine.quarantined_pages:
                # likely fenced, not dry: unfence instead of evicting
                self._stall_for_pages("pool_dry_stitch")
            else:
                self._evict_one_parked()
            return 0

    def _pages_for(self, n_tokens: int) -> int:
        """Eviction sizing hint: pages a prompt of ``n_tokens`` needs
        (+1 headroom). Radix eviction is page-granular, so freeing one
        page per failed admission would thrash retry passes."""
        ps = getattr(self.engine.ecfg, "page_size", 1) or 1
        return -(-n_tokens // ps) + 1

    def _observe_wait(self, req: Request):
        """Record the request's queue wait (global + per-class series).
        Every way out of the waiting line observes exactly once: first
        admission (_post_admit) or any shed — a shed IS the end of that
        request's wait, and a wait histogram that drops its worst
        entries under overload reads dangerously healthy."""
        wait = max(time.monotonic() - req.stats.t_submit, 0.0)
        METRICS.observe("tpu_model_queue_wait_seconds", wait)
        METRICS.observe("tpu_model_class_queue_wait_seconds", wait,
                        f'{{class="{req.priority}"}}')

    def _shed(self, req: Request, cause: str = "deadline"):
        """Reject a request that will never hold a slot: deadline
        expired while it waited (cause="deadline") or it was displaced
        by a higher-priority arrival under queue pressure
        (cause="queue_full"). The caller never got a token, so this
        maps to 503 + Retry-After (DeadlineExceeded raised from
        chunks()) rather than a terminal stream frame."""
        retry_after = self._retry_after_estimate(req.rank)
        req.error = ("deadline exceeded while queued"
                     if cause == "deadline"
                     else "shed under queue pressure by a "
                          "higher-priority request")
        req.stats.t_done = time.monotonic()
        req.trace.event("shed", cause=cause)
        FLIGHT.record("shed", rid=req.id, cause=cause, cls=req.priority,
                      tenant=req.tenant, retry_after_s=retry_after)
        with self._lock:
            self.finished.append(req.stats)
        self._observe_wait(req)
        METRICS.inc("tpu_model_requests_shed_total")
        METRICS.inc("tpu_model_shed_total",
                    labels=shed_labels(req.priority, cause))
        req.out.put(("shed", (req.error, retry_after)))

    def _shed_expired(self):
        """Drop queued/preempted requests whose deadline already passed
        or that were cancelled while still waiting — without this sweep
        a request deep in the queue behind busy slots would hold its
        reader (and its queue slot) until a decode slot finally freed."""
        now = time.monotonic()

        def expired(r):
            return r.deadline is not None and now > r.deadline

        def dead(r):
            return expired(r) or r.cancelled.is_set()

        for req in self._admission.sweep(dead):
            if req.cancelled.is_set():
                req.out.put(("done", "cancelled"))
            else:
                self._shed(req)
        # throttled requests whose rate-limit debt has drained become
        # ordinary preempted requests again (same resume machinery)
        ripe = [r for r in self._throttled if r.resume_at <= now]
        for req in ripe:
            self._throttled.remove(req)
            self._preempted.append(req)
        # a preempted/throttled request already streamed tokens from its
        # first admission — its expiry is a mid-generation timeout
        # (terminal frame), not a shed
        for pool in (self._preempted, self._throttled):
            for req in [r for r in pool if expired(r)]:
                pool.remove(req)
                req.stats.t_done = time.monotonic()
                with self._lock:
                    self.finished.append(req.stats)
                METRICS.inc("tpu_model_request_timeouts_total")
                req.out.put(("done", "timeout"))

    def _request_error(self, req: Request, msg: str):
        """Terminal error frame for a request that never held (or just
        lost) a slot."""
        req.error = msg
        req.stats.t_done = time.monotonic()
        with self._lock:
            self.finished.append(req.stats)
        req.out.put(("error", msg))

    def _own(self, slot: int, req: Request, mode: str):
        """The slot has its owner: the next decode launch counts it in
        _decoding(). ``mode`` says whether the loop waited for the
        admission's token before going on ("awaited") or launched it and
        collects the token behind the next chunk's launch ("launched")."""
        req.slot = slot
        self._running[slot] = req
        METRICS.inc("tpu_model_admissions_total", 1.0, _ADMIT_MODE[mode])

    def _post_admit(self, slot: int, req: Request, first: int,
                    launched: bool = False):
        """Shared admission tail (one-shot, batched, and the final
        chunked piece), run once the first token is on the host: stats,
        slot ownership (a launched admission has it already), grammar
        gate, first-token emit."""
        if not launched:
            self._own(slot, req, "awaited")
        if req.stats.t_admitted == 0:
            # first admission only — a preempted request re-admitting
            # must not re-count its prompt in throughput stats (nor
            # re-observe its queue wait: that wait already happened)
            self.total_prompt += req.stats.n_prompt
            self._observe_wait(req)
        req.stats.t_admitted = time.monotonic()
        req.trace.event("admitted", slot=slot,
                        reused=int(req.stats.n_reused))
        FLIGHT.record("admit", rid=req.id, slot=slot,
                      n_prompt=int(req.stats.n_prompt),
                      reused=int(req.stats.n_reused))
        # prefix-cache accounting per ADMISSION (re-admissions re-count:
        # a preempted request's second prefill is real compute): hit =
        # tokens served from cache (radix stitch or parked-slot extend),
        # miss = tokens actually prefilled
        n_re = min(req.stats.n_reused, len(req.admit_ids))
        METRICS.inc("tpu_model_prefix_hit_tokens_total", float(n_re))
        METRICS.inc("tpu_model_prefix_miss_tokens_total",
                    float(len(req.admit_ids) - n_re))
        # tiered attribution of the same tokens (ISSUE 18): which tier
        # served the reuse (0 = HBM-shared, 1 = host restitch, 2 =
        # fleet-snapshot restitch); misses split into never-cached
        # tokens (tier 0) and spilled tokens the break-even model chose
        # to recompute (tier 1/2)
        ls = getattr(req, "_tier_stitch", None) or {}
        t12 = ls.get("t1", 0) + ls.get("t2", 0)
        skip = ls.get("skip1", 0) + ls.get("skip2", 0)
        for tier, n in (("0", max(n_re - t12, 0)),
                        ("1", ls.get("t1", 0)), ("2", ls.get("t2", 0))):
            if n:
                METRICS.inc("tpu_model_tier_hit_tokens_total", float(n),
                            f'{{tier="{tier}"}}')
        for tier, n in (("0", len(req.admit_ids) - n_re - skip),
                        ("1", ls.get("skip1", 0)),
                        ("2", ls.get("skip2", 0))):
            if n > 0:
                METRICS.inc("tpu_model_tier_miss_tokens_total", float(n),
                            f'{{tier="{tier}"}}')
        req._tier_stitch = None
        # grammar check before emitting (see _fanout)
        if (req.constraint is not None
                and first not in req.eog_ids
                and not req.constraint.advance(first)):
            self._finish(slot, req, "stop")
        elif not self._emit_first(req, first):
            # EOG is a natural stop; an exhausted max_tokens budget is a
            # truncation — Ollama clients tell them apart by done_reason
            self._finish(slot, req, "stop"
                         if req.all_tokens[-1] in req.eog_ids
                         else "length")
        elif req.constraint is not None:
            self._refresh_mask(slot, req)

    def _expired_at_admission(self, req: Request) -> bool:
        """Deadline re-check at the moment a request is about to touch
        the engine. A request can expire AFTER the `_next_waiting` pop —
        earlier admissions in the same pass block on prefill dispatches —
        and admitting it anyway wastes a full prefill before a
        mid-generation `timeout`. A fresh request (never emitted a
        token) sheds with 503 + Retry-After; a resumed one already
        streamed tokens, so its expiry stays a terminal timeout frame.
        Returns True when the request was terminated here."""
        if req.deadline is None or time.monotonic() <= req.deadline:
            return False
        if req.resume_ids is not None:
            METRICS.inc("tpu_model_request_timeouts_total")
            req.stats.t_done = time.monotonic()
            with self._lock:
                self.finished.append(req.stats)
            req.out.put(("done", "timeout"))
        else:
            self._shed(req)
        return True

    def _launches(self, req: Optional[Request] = None) -> bool:
        """Whether an admission is launched and its first token collected
        behind the next chunk's launch, or awaited where it is made.
        Launched wherever nothing needs the host between a prefill and the
        first decode step. Awaited: a synchronous loop; a constrained
        request (its first token advances the automaton, and the mask of
        its first decode step follows from that)."""
        return (self.async_dispatch
                and (req is None or req.constraint is None))

    def _admit_one(self, slot: int, req: Request, reuse_len: int,
                   tries: int = 3) -> bool:
        """One admission (fresh or prefix-reusing), launched or awaited
        (_launches). Returns False when the paged pool ran dry and the
        request was requeued — the caller should stop admitting this
        pass. The engine has by then evicted every cached page that the
        fence lets go at once and still found too few (_make_room), so
        ``tries``: a try that finds the pool dry reclaims pages
        (unfences, else evicts) and a request that came cold is tried
        again where it stands: at most once after each."""
        if self._expired_at_admission(req):
            return True
        launch = self._launches(req)
        cold = not reuse_len
        eng = self.engine
        try:
            mask_row = (req.constraint.mask_row()
                        if req.constraint is not None else None)
            admit = eng.admit_launch if launch else eng.admit
            try:
                if reuse_len:
                    out = (eng.extend_launch if launch else eng.extend)(
                        slot, req.admit_ids, reuse_len, req.opts,
                        mask_row=mask_row)
                else:
                    out = admit(slot, req.admit_ids, req.opts,
                                embeds=req.embeds, mask_row=mask_row)
            except PagesExhausted:
                if not (reuse_len and self._use_radix):
                    raise
                # the stitched tail ran dry (extend already rolled the
                # shared mappings back): fall back to a COLD admit once —
                # a genuinely dry pool raises again and requeues below
                reuse_len = 0
                req._tier_stitch = None
                out = admit(slot, req.admit_ids, req.opts,
                            embeds=req.embeds, mask_row=mask_row)
            req.stats.n_reused = reuse_len
        except PagesExhausted as e:
            # paged pool dry, the cached pages that were free at once
            # taken already (Engine._make_room): under async dispatch
            # first drain the pipeline and unfence (what is missing is
            # fenced behind the in-flight dispatch: quarantined pages,
            # leaves whose slots went while it flew), else evict cached
            # pages (nothing is in flight then: they are free at once,
            # or become so by the stall the next try takes). A request
            # that came cold is then tried again
            # where it stands: the pass has paid for the stall, and a
            # request sent to the next pass waits a whole cycle beside
            # pages that are free. One that came with a prefix goes to
            # the head of the next pass, which stitches it again (trying
            # it here would admit it cold and forfeit the hit); so does
            # one for which nothing was reclaimed or the tries are used
            # up: it waits for a finisher (unless it can never fit at all)
            if not self.engine.admissible(len(req.admit_ids)):
                self._request_error(
                    req, f"prompt needs more KV pages than the pool "
                         f"has: {e}")
                return True
            if self._pending is not None or self.engine.quarantined_pages:
                self._stall_for_pages("pool_dry_admit")
                reclaimed = True
            else:
                reclaimed = self._evict_one_parked(
                    self._pages_for(len(req.admit_ids)))
            if cold and reclaimed and tries > 1:
                return self._admit_one(slot, req, 0, tries - 1)
            self._preempted.insert(0, req)
            return False
        except Exception as e:  # surfacing engine errors to the caller
            self._request_error(req, str(e))
            return True
        if launch:
            self._own(slot, req, "launched")
            n = len(req.admit_ids)
            self._launched.append((out, [(slot, req, reuse_len, n, n)]))
            return True
        kind = "extend" if reuse_len else "admit"
        dur = self._note_prefill(kind)
        n_new = len(req.admit_ids) - reuse_len
        self.acct.on_prefill(dur, reuse_len, n_new,
                             self.engine.bucket_for(n_new))
        req.trace.event("prefill", kind=kind, dur_ms=round(dur * 1e3, 3),
                        n_tokens=n_new)
        self._post_admit(slot, req, out)
        return True

    def _note_prefill(self, kind: str,
                      blocked: Optional[float] = None) -> float:
        """Account one admit/extend dispatch whose token just reached the
        host: its seconds are the engine's own (dispatch_ms: what the
        dispatch took, Engine._landed), so the scheduler reads no clock of
        its own here. ``blocked`` is how long this thread stood waiting
        for it: all of it for an awaited admission, the collect's wait for
        a launched one."""
        dur = self.engine.dispatch_ms[kind] / 1e3
        if blocked is None:
            blocked = dur
        METRICS.inc("tpu_model_admission_stall_ms_total", blocked * 1e3)
        METRICS.observe("tpu_model_dispatch_seconds", dur,
                        f'{{kind="{kind}"}}')
        self.acct.on_wait(blocked)
        return dur

    def _collect_launched(self):
        """The first tokens of the admissions this pass launched, oldest
        first, and for each the tail an awaited admission runs where it
        is made. A request that was cancelled, preempted or timed out
        since its launch has left its slot: its token is dropped by the
        rule _fanout_rows drops rows by, the owner's identity. A device
        error surfaces here and errors the admission's own requests; a
        wedged device (WatchdogTimeout) goes to the supervisor, for which
        what is still in _launched is one more pending dispatch whose
        owners stand in _running."""
        while self._launched:
            # popped BEFORE waiting, as _drain_pending pops: a failed
            # fetch must never be tried again
            handle, items = self._launched.pop(0)
            try:
                with span("sched.collect", m=len(items)) as sp:
                    toks = self._watched(handle.wait)
            except WatchdogTimeout:
                raise
            except Exception as e:  # noqa: BLE001 — the owners' error frame
                for slot, req, *_ in items:
                    if self._running[slot] is req:
                        # the frame first: if the release raises too, the
                        # supervisor must not find this owner again
                        self._running[slot] = None
                        self._prefilling.pop(slot, None)
                        req.slot = None
                        self._request_error(req, str(e))
                        self.engine.release(slot)
                continue
            dur = self._note_prefill(handle.kind, sp.dur)
            m = len(items)
            for (slot, req, start, end, of), tok in zip(items, toks):
                n_new = end - start
                # a batched dispatch's time is split evenly, so the ring's
                # busy_s doesn't count the dispatch m times
                self.acct.on_prefill(dur / m, start, n_new,
                                     self.engine.bucket_for(n_new))
                # from the launch, so that the request's queue stage ends
                # and its prefill stage begins there (trace.fold_stages)
                since = round((handle.t_done - handle.t_launch) * 1e3, 3)
                if end < of:
                    # a chunked admission's piece: its token is no one's
                    req.trace.event("prefill_piece", kind=handle.kind,
                                    done=end, of=of, dur_ms=since)
                    continue
                req.trace.event(
                    "prefill", kind=handle.kind, n_tokens=n_new,
                    dur_ms=since, **({"batched": m} if m > 1 else {}))
                if self._running[slot] is req:
                    self._post_admit(slot, req, tok, launched=True)

    def _dispatch_piece(self, slot: int, req: Request, start: int,
                        end: int):
        """One piece of a chunked admission: prefill ``[start, end)`` of
        the request's prompt into ``slot``, launched or awaited as a
        one-shot admission is (_launches), and charge it to the step's
        budget. A piece short of the prompt's end runs with default
        options and leaves the slot parked: cache and lengths stay, the
        slot goes engine-inactive so decode dispatches skip it. The final
        piece runs with the request's real options/grammar mask and
        samples its TTFT token (PRNG-seed-identical to a one-shot
        admission: the seed derives from (slot, full prompt length)); it
        ends the job. Whatever the engine raises is the caller's."""
        ids = req.admit_ids
        final = end == len(ids)
        launch = self._launches(req)
        eng = self.engine
        kw = {}
        if final:
            kw = dict(opts=req.opts,
                      mask_row=(req.constraint.mask_row()
                                if req.constraint is not None else None))
        if start:
            out = (eng.extend_launch if launch else eng.extend)(
                slot, ids[:end], start, **kw)
        else:
            out = (eng.admit_launch if launch else eng.admit)(
                slot, ids[:end], **kw)
        METRICS.inc("tpu_model_prefill_chunks_total")
        if final:
            self._prefilling.pop(slot, None)
        else:
            eng.release(slot, park=True)
        if launch:
            self._piece_budget -= end - start
            if final:
                self._own(slot, req, "launched")
            self._launched.append(
                (out, [(slot, req, start, end, len(ids))]))
            return
        # an awaited piece held this thread: no second one this step
        self._piece_budget = 0
        kind = "extend" if start else "admit"
        dur = self._note_prefill(kind)
        self.acct.on_prefill(dur, start, end - start,
                             self.engine.bucket_for(end - start))
        req.trace.event("prefill_piece", kind=kind, done=end,
                        of=len(ids), dur_ms=round(dur * 1e3, 3))
        if final:
            self._post_admit(slot, req, out)

    def _start_chunked(self, slot: int, req: Request,
                       reuse_len: int) -> bool:
        """First piece of a chunked admission: prefill one
        prefill_chunk-sized bucket, park the slot, and register the job;
        the remaining pieces follow while the step's budget lasts and
        otherwise interleave with decode dispatches (_advance_prefill).
        Returns False when the paged pool ran dry and the request was
        requeued."""
        if self._expired_at_admission(req):
            return True
        ids = req.admit_ids
        try:
            try:
                self._dispatch_piece(slot, req, reuse_len,
                                     reuse_len + self.prefill_chunk)
            except PagesExhausted:
                if not (reuse_len and self._use_radix):
                    raise
                # stitched first piece ran dry mid-COW/tail: cold-start
                # the chunked prefill once (stitch/extend rolled the
                # shared mappings back)
                reuse_len = 0
                req._tier_stitch = None
                self._dispatch_piece(slot, req, 0, self.prefill_chunk)
            req.stats.n_reused = reuse_len
        except PagesExhausted as e:
            if not self.engine.admissible(len(ids)):
                self._request_error(
                    req, f"prompt needs more KV pages than the pool "
                         f"has: {e}")
                return True
            if self._pending is not None or self.engine.quarantined_pages:
                # fenced, not dry (see _admit_one): unfence, don't evict
                self._stall_for_pages("pool_dry_admit")
            else:
                self._evict_one_parked(self._pages_for(len(ids)))
            self._preempted.insert(0, req)
            return False
        except Exception as e:
            self._request_error(req, str(e))
            return True
        req.slot = slot
        self._running[slot] = req
        self._prefilling[slot] = _PrefillJob(
            req, reuse_len + self.prefill_chunk)
        return self._advance_job(slot)

    def _abort_prefill(self, slot: int, reason: str):
        job = self._prefilling.pop(slot)
        req = job.req
        self._running[slot] = None
        self.engine.release(slot)
        req.stats.t_done = time.monotonic()
        with self._lock:
            self.finished.append(req.stats)
        req.out.put(("done", reason))

    def _advance_prefill(self):
        """The next pieces of the chunked-admission jobs, oldest job
        first, while the step's budget of prompt tokens lasts
        (_piece_tokens): decoding slots never stall for more than that
        per dispatch, and no job waits for another's last piece."""
        for slot in list(self._prefilling):
            if self._piece_budget <= 0 or not self._advance_job(slot):
                return

    def _advance_job(self, slot: int) -> bool:
        """Pieces for the job in ``slot`` until its prompt is in or the
        step's budget is spent. Returns False when the paged pool ran dry
        and the request was requeued."""
        while self._piece_budget > 0 and slot in self._prefilling:
            job = self._prefilling[slot]
            req = job.req
            if req.cancelled.is_set():
                self._abort_prefill(slot, "cancelled")
                return True
            if req.deadline is not None and time.monotonic() > req.deadline:
                if req.resume_ids is None:
                    # no token ever reached the client: this is a shed
                    # (503 + Retry-After), not a mid-generation timeout
                    self._prefilling.pop(slot)
                    self._running[slot] = None
                    req.slot = None
                    self.engine.release(slot)
                    self._shed(req)
                else:
                    METRICS.inc("tpu_model_request_timeouts_total")
                    self._abort_prefill(slot, "timeout")
                return True
            ids = req.admit_ids
            end = min(job.done + self.prefill_chunk, len(ids))
            try:
                self._dispatch_piece(slot, req, job.done, end)
            except PagesExhausted:
                # mid-prefill pool pressure: back out and requeue; the
                # re-admission restarts the prompt (no tokens were
                # emitted)
                self._prefilling.pop(slot, None)
                self._running[slot] = None
                req.slot = None
                self.engine.release(slot)
                self._evict_one_parked(self._pages_for(len(ids)))
                self._preempted.insert(0, req)
                return False
            # any other engine failure propagates to the supervisor,
            # which errors every running request (this one included)
            # exactly once and restarts — _fail_running clears
            # _prefilling
            job.done = end
        return True

    def _flush_admit_batch(self, batch: dict):
        """Admit the same-bucket groups collected this pass: groups of 4
        then 2 take ONE batched dispatch each; leftovers (and any group
        whose batched dispatch failed) fall back to sequential
        admission."""
        for bucket, items in batch.items():
            # deadlines re-checked here too: earlier groups' dispatches
            # may have burned this batch's remaining budget
            items = [(s, r) for s, r in items
                     if not self._expired_at_admission(r)]
            launch = self._launches()    # no batched request is constrained
            admit_many = (self.engine.admit_many_launch if launch
                          else self.engine.admit_many)
            while len(items) >= 2:
                m = 4 if len(items) >= 4 else 2
                group, items = items[:m], items[m:]
                try:
                    out = admit_many(
                        [s for s, _ in group],
                        [r.admit_ids for _, r in group],
                        [r.opts for _, r in group])
                except Exception:  # noqa: BLE001 — pool dry, injected
                    # fault, ...: the batched program mutated nothing
                    # (paged grows roll back), so each request retries
                    # on the single-admit path with its own error
                    # handling
                    for s, r in group:
                        self._admit_one(s, r, 0)
                    continue
                # batched admissions are always cold (a resumed request
                # must not re-report its first admission's reuse as a
                # fresh cache hit)
                for _, r in group:
                    r.stats.n_reused = 0
                if launch:
                    for s, r in group:
                        self._own(s, r, "launched")
                    self._launched.append(
                        (out, [(s, r, 0, len(r.admit_ids), len(r.admit_ids))
                               for s, r in group]))
                    continue
                dur = self._note_prefill("admit")
                # one batched dispatch: split its wall time evenly so the
                # ring's busy_s doesn't count the dispatch m times
                for _, r in group:
                    self.acct.on_prefill(dur / m, 0, len(r.admit_ids),
                                         bucket)
                for (s, r), tok in zip(group, out):
                    r.trace.event("prefill", kind="admit", batched=m,
                                  dur_ms=round(dur * 1e3, 3),
                                  n_tokens=len(r.admit_ids))
                    self._post_admit(s, r, tok)
            for s, r in items:
                self._admit_one(s, r, 0)

    def _free_slots(self) -> List[int]:
        """The slots an admission pass may fill: those mid-chunked-prefill
        are engine-inactive but TAKEN."""
        return [s for s in self.engine.free_slots()
                if s not in self._prefilling]

    def _admit_waiting(self):
        """One admission pass: waiting requests into free slots, in the
        waiting line's order, until either runs out or the pool does. A
        pass that took at least one request off the line is counted in
        tpu_model_admission_passes_total, stalled="yes" where it had to
        stall for pages (_stall_for_pages) on the way."""
        free = self._free_slots()
        batch: dict = {}   # prefill bucket → [(slot, req)] to batch-admit
        took = self._pass_stalled = False
        try:
            while free:
                req = self._next_waiting()
                if req is None:
                    return
                took = True
                if req.cancelled.is_set():
                    req.out.put(("done", "cancelled"))
                    continue
                if (req.deadline is not None
                        and time.monotonic() > req.deadline):
                    # expired between the sweep and this pop
                    if req.resume_ids is not None:
                        METRICS.inc("tpu_model_request_timeouts_total")
                        req.out.put(("done", "timeout"))
                    else:
                        self._shed(req)
                    continue
                reuse_slot, reuse_len = self._best_prefix(req)
                if reuse_slot is not None:
                    slot = reuse_slot
                    free.remove(slot)
                else:
                    # prefer slots that (a) sit on a dp shard whose
                    # sub-pool can actually hold this prompt (paged×dp:
                    # shard-blind picks would raise PagesExhausted and
                    # thrash evictions while another shard idles) and
                    # (b) have no parked prefix, keeping reusable caches
                    # alive as slots allow
                    n_tok = len(req.admit_ids)

                    def _pick():
                        for cond in (
                                lambda s: s not in self._parked
                                and self.engine.can_admit(s, n_tok),
                                lambda s: self.engine.can_admit(s, n_tok),
                                lambda s: s not in self._parked):
                            for s in free:
                                if cond(s):
                                    return s
                        return free[0]
                    slot = _pick()
                    free.remove(slot)
                # the slot's parked cache is spoken for either way: on
                # success the request owns it; on failure the slot state
                # is unknown and must not be offered for reuse again (a
                # stale entry would also crash the NEXT request's
                # free.remove in this same pass)
                self._parked.pop(slot, None)
                ids = req.admit_ids
                if self._use_radix and req.embeds is None:
                    # radix mode: stitch the tree's longest usable prefix
                    # into the slot; the tail admits via extend below
                    # (reuse 0 = cold admit, slot left clean)
                    reuse_len = self._stitch_admission(slot, req)
                piece = self.prefill_chunk
                if (piece and len(ids) - reuse_len > piece
                        and req.embeds is None
                        and len(ids) + piece <= self.engine.max_seq):
                    # long prompt: admit piecewise, inside the step's
                    # budget of pieces
                    if not self._start_chunked(slot, req, reuse_len):
                        return
                    continue
                if (not reuse_len and req.embeds is None
                        and req.constraint is None
                        and self.engine.supports_admit_many):
                    # same-bucket fresh admissions coalesce into one
                    # batched dispatch at the end of the pass
                    bucket = self.engine.bucket_for(len(ids))
                    batch.setdefault(bucket, []).append((slot, req))
                    continue
                if not self._admit_one(slot, req, reuse_len):
                    return
        finally:
            self._flush_admit_batch(batch)
            if took:
                METRICS.inc("tpu_model_admission_passes_total", 1.0,
                            _PASS_STALLED[self._pass_stalled])

    def _loop(self):
        while not self._stop.is_set():
            try:
                self._step()
            except Exception as e:  # noqa: BLE001 — a decode error must not
                # kill the daemon thread: that would leave every in-flight
                # tokens() reader blocked forever while /healthz stays green.
                traceback.print_exc(file=sys.stderr)
                FLIGHT.record("engine_failure", error=str(e)[:200],
                              consecutive=self._consecutive_failures + 1)
                self._consecutive_failures += 1
                final = self._consecutive_failures > self.max_restarts
                # no replay on the terminal failure: a stream parked in
                # _recovering would only be errored again by the broken
                # drain below — classify it straight to the error frame
                self._fail_running(str(e), replay=not final)
                if final:
                    with self._lock:
                        self.broken = True
                        self._drain_waiting(("error", f"engine failed: {e}"))
                    return
                self._supervised_restart()

    def _supervised_restart(self):
        """Rebuild engine state in-process after a decode-loop failure.

        Crash-only recovery: the requests that were mid-flight on the
        failing step were already errored by _fail_running; everything
        still waiting or preempted stays queued and is re-admitted once
        the engine is clean. Costs a slot-state reset, NOT a model
        reload or pod restart — the weights and compiled executables are
        untouched. Goes terminally `broken` only when max_restarts
        consecutive rebuilds all fail to produce one good step.
        """
        # release EVERY slot (not just the running ones): a failing step
        # leaves cache/page accounting in an unknown state, so parked
        # prefixes are unsafe to reuse and their pages must go back to
        # the pool. release() also resets host-side lengths and masks.
        for slot in range(self.engine.n_slots):
            try:
                self.engine.release(slot)
            except Exception:  # lint: allow(exception-hygiene): best-effort teardown
                pass
        self._parked.clear()
        # the radix tree's pages were released with the slots above only
        # if nothing pinned them — drop every tree reference too, or the
        # rebuilt engine would stitch prefixes whose cache contents are
        # unknown (and the pins would leak pool pages forever)
        radix_reset = getattr(self.engine, "radix_reset", None)
        if radix_reset is not None:
            try:
                radix_reset()
            except Exception:  # lint: allow(exception-hygiene): best-effort teardown
                pass
        self.n_restarts += 1
        METRICS.inc("tpu_model_engine_restarts_total")
        # black-box post-mortem: record the restart itself, then dump
        # the ring so the job log shows the last N structured events
        # (admissions, the injected fault, the failure) BEFORE this
        # recovery — chaos CI greps for this block
        FLIGHT.record("restart", n=self.n_restarts,
                      consecutive=self._consecutive_failures)
        FLIGHT.dump(f"supervised restart #{self.n_restarts}")
        # capped exponential backoff before retrying; interruptible so
        # shutdown() never waits behind a sleeping supervisor
        delay = min(self.restart_backoff
                    * (2 ** (self._consecutive_failures - 1)),
                    self.RESTART_BACKOFF_CAP)
        if delay > 0:
            self._stop.wait(delay)
        # re-admit the replayable streams ahead of the waiting queue:
        # resume_ids is already set, so the normal preempt/resume path
        # re-prefills prompt+generated (chunked for long contexts) and
        # generation continues from the next token on the same output
        # queue — bit-identical for greedy and seeded streams
        if self._recovering:
            recov, self._recovering = self._recovering, []
            self._preempted[:0] = recov
            FLIGHT.record("replay_readmit", n=len(recov))
            self._wake.set()

    @staticmethod
    def _replay_ineligible(req: Request) -> Optional[str]:
        """Why a stream can NOT be replayed bit-identically, or None.

        The determinism contract (engine.py): greedy streams
        (temperature == 0) and seeded streams (opts.seed >= 0, base key
        slot-independent, per-step keys fold_in(key, position)) resume
        byte-identical through the preempt/resume machinery. Unseeded
        temperature sampling derives its base key from (slot, seq_len) —
        both change on resume — and mirostat's mu state is re-seeded at
        admission, so neither can promise the same continuation.
        Multimodal prompts can't re-prefill from token ids at all."""
        if req.embeds is not None:
            return "multimodal"
        o = req.opts
        if o.temperature > 0.0 and o.seed < 0:
            return "nondeterministic"
        if o.mirostat:
            return "nondeterministic"
        return None

    def _fail_running(self, message: str, replay: bool = True):
        # the in-flight async dispatch (and any mid-chunked-prefill
        # state) dies with the engine state; every owner is still in
        # _running. Replayable streams move to _recovering — after the
        # supervised rebuild they re-admit through the preempt/resume
        # machinery and continue on the same output queue, so the client
        # sees a stall, never an error. Everything else (non-
        # deterministic, multimodal, over the replay budget, injected
        # replay fault, or ``replay=False`` because the loop is going
        # terminally broken) falls back to today's exactly-ONE error
        # frame.
        FLIGHT.record("fail_running", error=message[:200],
                      n_running=self.n_active)
        self._pending = None
        self._launched.clear()
        self._prefilling.clear()
        budget = replay_token_budget()
        max_streams = replay_max_streams() if replay else 0
        taken = 0
        for slot, req in enumerate(self._running):
            if req is None:
                continue
            self._running[slot] = None
            cause = (self._replay_ineligible(req) if replay
                     else "broken")
            cost = len(req.prompt_ids) + len(req.all_tokens)
            if cause is None and (taken >= max_streams or cost > budget):
                cause = "over_budget"
            if cause is None:
                try:
                    FAULTS.check("scheduler.replay")
                except InjectedFault:
                    cause = "faulted"
            if cause is None:
                budget -= cost
                taken += 1
                req.resume_ids = np.concatenate(
                    [req.prompt_ids,
                     np.asarray(req.all_tokens, np.int32)])
                req.slot = None
                self._recovering.append(req)
                self.n_replays += 1
                METRICS.inc("tpu_model_replayed_requests_total")
                METRICS.inc("tpu_model_replayed_tokens_total",
                            float(cost))
                req.trace.event("replay", slot=slot,
                                n_generated=req.stats.n_generated)
                FLIGHT.record("replay", rid=req.id, slot=slot,
                              outcome="recovered", tokens=cost,
                              n_generated=req.stats.n_generated)
            else:
                self.n_replay_fallbacks += 1
                METRICS.inc("tpu_model_replay_fallback_total",
                            labels=f'{{cause="{cause}"}}')
                FLIGHT.record("replay", rid=req.id, slot=slot,
                              outcome="fallback", cause=cause)
                req.error = message
                req.stats.t_done = time.monotonic()
                req.out.put(("error", message))
            try:
                self.engine.release(slot)
            except Exception:  # lint: allow(exception-hygiene): best-effort slot reset
                pass
        # the releases above (and the restart's parked/radix teardown
        # next) must not strand pages in quarantine — the failed epoch
        # will never be acked by a wait. Drain via the fence if the
        # devices still answer, else reclaim host-side: device programs
        # are serialized by donated-cache data dependencies, so any
        # zombie dispatch finishes before a post-restart program could
        # touch a recycled page.
        try:
            self.engine.fence_quiesce()
        except Exception:  # noqa: BLE001 — poisoned device state
            pt = getattr(self.engine, "_pt", None)
            if pt is not None:
                pt.drain_quarantine()
        self._fence_ack = 0

    def _drain_waiting(self, msg):
        for req in self._preempted + self._throttled + self._recovering:
            req.out.put(msg)
        self._preempted.clear()
        self._throttled.clear()
        self._recovering.clear()
        for req in self._admission.drain():
            req.out.put(msg)

    def _relieve_pressure(self, n_steps: Optional[int]):
        """Paged mode: make sure every active slot has pages for the next
        decode chunk. Pressure relief order: (1) evict parked prefix
        caches, (2) preempt the newest active requests — their generation
        state is requeued (resume_ids) and continues on the same output
        stream after re-admission. Multimodal requests are preempted last
        (their image embeds cannot be re-prefilled from token ids) and
        errored if no alternative exists."""
        while True:
            victims = self.engine.prepare_decode(n_steps)
            if not victims:
                return
            # pipeline stall beats sacrifice: under async dispatch the
            # missing pages may merely be FENCED behind the in-flight
            # dispatch (quarantined until it materialises), not truly
            # exhausted — drain the pipeline and unfence before evicting
            # anyone's cache or preempting a generation. One stall per
            # pool-dry event, vs a re-prefill per needless preemption.
            if self._pending is not None or self.engine.quarantined_pages:
                self._stall_for_pages("pool_dry_decode")
                continue
            if self._evict_one_parked():
                continue
            cand = [s for s in victims if self._running[s] is not None]
            if not cand:
                return  # nothing actionable; decode_n will surface it
            non_mm = [s for s in cand if self._running[s].embeds is None]
            if non_mm:
                # priority-aware sacrifice: lowest class first, newest
                # admission within a class — a best_effort straggler
                # yields its pages before any high request does
                slot = max(non_mm, key=self._newest_lowest)
                self._preempt_slot(slot, cause="pool_pressure")
            else:
                slot = cand[0]
                req = self._running[slot]
                self._running[slot] = None
                self.engine.release(slot)
                req.error = ("preempted under KV-pool pressure; multimodal "
                             "requests cannot resume")
                req.stats.t_done = time.monotonic()
                with self._lock:
                    self.finished.append(req.stats)
                req.out.put(("error", req.error))

    def _newest_lowest(self, slot: int) -> tuple:
        """Preemption order: the lowest class first, within it the newest
        admission. One launched and not yet collected carries no stamp of
        its own (_post_admit sets it): it is the newest there is."""
        req = self._running[slot]
        return req.rank, req.stats.t_admitted or float("inf")

    def _preempt_slot(self, slot: int, cause: str,
                      resume_delay: float = 0.0) -> Request:
        """Evict a running (non-multimodal) request from its slot,
        recording resume_ids so re-admission re-prefills prompt+generated
        onto the same output stream (seed-identical for greedy). With
        ``resume_delay`` the request parks in _throttled and only
        becomes admissible once its rate-limit debt drains."""
        req = self._running[slot]
        self._running[slot] = None
        self.engine.release(slot)
        req.resume_ids = np.concatenate(
            [req.prompt_ids, np.asarray(req.all_tokens, np.int32)])
        req.slot = None
        self.n_preemptions += 1
        METRICS.inc("tpu_model_preemptions_total")
        req.trace.event("preempted", slot=slot, cause=cause,
                        n_generated=req.stats.n_generated)
        FLIGHT.record("preempt", rid=req.id, slot=slot, cause=cause,
                      n_generated=req.stats.n_generated)
        if resume_delay > 0.0:
            req.resume_at = time.monotonic() + resume_delay
            self._throttled.append(req)
        else:
            self._preempted.append(req)
        return req

    def _preempt_for_priority(self):
        """With every slot busy and a strictly-higher-priority request
        waiting, evict ONE lowest-priority running request (newest
        admission breaks ties) so the high request's TTFT doesn't hide
        behind a best_effort generation. At most one victim per step —
        the freed slot is admitted this same pass, so pressure converges
        without thrashing. Gated by TPU_PRIORITY_PREEMPT (default on)."""
        if not self._priority_preempt:
            return
        if any(s not in self._prefilling
               for s in self.engine.free_slots()):
            return
        ranks = [r.rank for r in self._preempted]
        qrank = self._admission.peek_rank()
        if qrank is not None:
            ranks.append(qrank)
        if not ranks:
            return
        want = min(ranks)
        cand = [s for s, r in enumerate(self._running)
                if r is not None and s not in self._prefilling
                and r.embeds is None and r.rank > want]
        if not cand:
            return
        slot = max(cand, key=self._newest_lowest)
        self._preempt_slot(slot, cause="priority")

    def _throttle_over_limit(self):
        """Mid-stream rate limiting: a best_effort slot whose tenant's
        decode-token bucket has gone negative is preempted (same
        resume machinery — the surviving stream is bit-identical for
        greedy sampling) and parks in _throttled until the debt drains.
        Higher classes are debited but never throttled."""
        if not self._limiter.enabled:
            return
        for slot, req in list(self._decoding().items()):
            if (req.priority != "best_effort" or req.embeds is not None
                    or req.stats.n_generated <= 0):
                continue
            delay = self._limiter.debt_delay(req.tenant)
            if delay <= 0.0:
                continue
            self.n_throttles += 1
            METRICS.inc(
                "tpu_model_tenant_throttles_total",
                labels=f'{{class="{req.priority}",tenant="{req.tenant}"}}')
            req.trace.event("throttled", tenant=req.tenant,
                            delay_ms=round(delay * 1e3, 1))
            FLIGHT.record("throttle", rid=req.id, slot=slot,
                          tenant=req.tenant, cls=req.priority,
                          delay_ms=round(delay * 1e3, 1))
            self._preempt_slot(slot, cause="throttle", resume_delay=delay)

    def _watchdog_timeout_s(self) -> float:
        """Dispatch-wait budget in seconds; 0 disables the watchdog.

        Explicit TPU_DISPATCH_WATCHDOG_MS wins (0 = off). Otherwise the
        ceiling derives from the PR 7 dispatch histograms: once enough
        dispatches are observed, 100x the mean launch-to-host latency
        (clamped to [15s, 120s]) — generous enough that GC pauses and
        bucket recompiles never fire it, tight enough that a wedged
        device stops hiding behind a green /healthz. Before the
        histograms warm up (first dispatches compile) a fixed 120s
        floor applies."""
        ms = os.environ.get("TPU_DISPATCH_WATCHDOG_MS", "").strip()
        if ms:
            v = float(ms)
            return v / 1e3 if v > 0 else 0.0
        n, total = METRICS.hist_totals("tpu_model_dispatch_seconds")
        if n >= 64:
            return min(max(100.0 * (total / n), 15.0), 120.0)
        return 120.0

    @staticmethod
    def _wd_worker(req_q: queue.Queue, resp_q: queue.Queue):
        while True:
            fn = req_q.get()
            if fn is None:
                return
            try:
                resp_q.put((True, fn()))
            except BaseException as e:  # noqa: BLE001 — ferried to caller
                resp_q.put((False, e))

    def _watched(self, fn):
        """Run a blocking dispatch wait under the hung-dispatch
        watchdog: the wait executes on a persistent helper thread while
        the scheduler thread waits on the response queue with a
        timeout. On expiry the worker is abandoned (its eventual result
        goes to queues nothing reads — a fresh worker+queues serve the
        next wait) and WatchdogTimeout rides the normal supervisor
        path: restart, then replay. The engine.watchdog fault point
        runs ON the worker so an armed delay:Nms simulates a wedge."""
        timeout = self._watchdog_timeout_s()
        if timeout <= 0:
            FAULTS.check("engine.watchdog")
            return fn()

        def task():
            FAULTS.check("engine.watchdog")
            return fn()

        if self._wd_thread is None or not self._wd_thread.is_alive():
            self._wd_req = queue.Queue()
            self._wd_resp = queue.Queue()
            self._wd_thread = threading.Thread(
                target=self._wd_worker, args=(self._wd_req, self._wd_resp),
                daemon=True, name="tpu-dispatch-watchdog")
            self._wd_thread.start()
        self._wd_req.put(task)
        try:
            ok, val = self._wd_resp.get(timeout=timeout)
        except queue.Empty:
            self.n_watchdog_fires += 1
            METRICS.inc("tpu_model_watchdog_fires_total")
            FLIGHT.record("watchdog", timeout_s=round(timeout, 3),
                          fires=self.n_watchdog_fires)
            self._wd_thread = None      # abandon: never reuse its queues
            raise WatchdogTimeout(
                f"dispatch wait exceeded watchdog budget "
                f"{timeout:.1f}s (wedged device?)") from None
        if ok:
            return val
        raise val

    # -- device-grammar plumbing ------------------------------------------

    def _grammar_table(self, req: Request):
        """The engine-installed GrammarTable for ``req``'s constraint, or
        None when device grammar is unavailable for it (engine knob off,
        table build failed, or a DIFFERENT grammar currently owns the
        device tables while slots run on it). Resolved once per request
        and cached on it; GrammarTable.for_table itself caches the BFS
        per TokenTable, so repeat requests share one table build."""
        c = req.constraint
        if (c is None or not getattr(c, "grammar_table_ok", False)
                or not getattr(self.engine, "_grammar_device", False)):
            return None
        if req._gtable is not None:
            return req._gtable or None
        try:
            gt = GrammarTable.for_table(c.table,
                                        cap=self.engine._gstates_cap)
        except Exception:  # lint: allow(exception-hygiene): any table-build failure falls back to host masks
            gt = None
        if gt is None or not self.engine.install_grammar(
                ("grammar", id(gt)), gt.mask, gt.trans):
            req._gtable = False
            return None
        req._gtable = gt
        return gt

    def _refresh_mask(self, slot: int, req: Request):
        """Install ``req``'s current PDA mask on ``slot``; when the PDA
        state sits inside the installed device table the slot enters
        device-grammar mode — the mask then refreshes ON DEVICE per
        sampled token and the slot keeps the full decode chunk instead
        of one token per (synchronous) dispatch."""
        gid = -1
        gt = self._grammar_table(req)
        if gt is not None:
            gid = gt.state_id(req.constraint.state)
        self.engine.set_mask(slot, req.constraint.mask_row(), gid=gid)

    def _grammar_ack(self, slot: int, over: int):
        """Roll back a device-grammar slot's launch-time host-length
        over-advance (the frozen steps after an on-device escape),
        mirrored so followers reconcile at the same call position."""
        if over <= 0:
            return
        rb = np.zeros((self.engine.n_slots,), np.int64)
        rb[slot] = over
        self.engine.rollback_lengths(rb)

    def _wait_handle(self, handle, snapshot=None) -> np.ndarray:
        """Materialise a launched dispatch and reconcile host state: the
        paged fence ack, the dispatch's latency and goodput, and a
        ``dispatch`` span on every request of ``snapshot`` that still
        owns its slot."""
        with span("sched.wait") as sp:
            toks_n = self._watched(handle.wait)
        # breakdown: only the time the scheduler actually BLOCKED here is
        # dispatch-wait (under async overlap the device may already be
        # done); `dur` below is the full launch→host device span
        self.acct.on_wait(sp.dur, sp.t1)
        self._fence_ack = handle.epoch
        self._consecutive_failures = 0
        # dispatch latency: what THIS dispatch took, from the later of
        # its launch and its predecessor's tokens reaching the host
        # (Engine._landed) to its own; a chunk launched behind one that
        # still ran does not count that one's remainder. The trace event
        # below is anchored at the launch, which makes async overlap
        # visible (a launch far before its materialize = host work
        # hidden behind device compute).
        dur = ((handle.t_done - handle.t_begin)
               if handle.t_done is not None else 0.0)
        METRICS.observe("tpu_model_dispatch_seconds", dur,
                        '{kind="decode"}')
        self._chunk_s.append(dur)
        if self.acct.enabled:
            # goodput/FLOPs split of the dispatch grid: active slots'
            # host-mirrored lengths as contexts, the full slot batch as
            # the padded capacity
            hl, act = self.engine._host_lengths, self.engine.active
            ctxs = [int(hl[s]) for s in range(len(act)) if act[s]]
            n_rows = int(np.asarray(toks_n).shape[0])
            self.acct.on_decode(dur, ctxs, n_rows, self.engine.n_slots)
        if snapshot is not None:
            for s, r in snapshot.items():
                if self._running[s] is r:
                    r.trace.event_at(handle.t_launch, "dispatch",
                                     kind="decode", epoch=handle.epoch,
                                     dur_ms=round(dur * 1e3, 3))
        return toks_n

    def _drain_pending(self):
        """Materialise and fan out the in-flight async dispatch, if any,
        and collect the admissions launched behind it. Pops BEFORE
        waiting: if the fetch itself fails (poisoned device state) the
        supervisor must error the owners, never re-deliver."""
        prev, self._pending = self._pending, None
        self._land(prev)

    def _land(self, prev):
        """Bring to the host, in the order the device runs them, the
        dispatch ``prev`` (a _pending pair, or None) and the admissions
        launched behind it; then fan ``prev`` out. The first tokens are
        collected BEFORE the fan-out: a first token is not held behind a
        chunk's worth of queue puts."""
        toks_n = None
        if prev is not None:
            handle, snapshot = prev
            toks_n = self._wait_handle(handle, snapshot)
        try:
            self._collect_launched()
        finally:
            # whatever the collect raised, prev's tokens are on the host:
            # deliver them before the supervisor errors whoever is left
            if prev is not None:
                self._fanout(toks_n, snapshot)

    def _decoding(self) -> dict:
        """slot → request for every slot the NEXT decode dispatch will
        advance (mid-chunked-prefill slots are engine-inactive and
        excluded)."""
        return {s: r for s, r in enumerate(self._running)
                if r is not None and s not in self._prefilling}

    def _count_vacancy(self, now: float):
        """Slot-seconds since the previous iteration began: all of them
        into tpu_model_slot_seconds_total, and into
        tpu_model_slot_vacant_seconds_total those of the slots that
        iteration's admission pass left free (a slot freed later, by its
        fan-out, is admitted at the earliest by the next pass), by whether
        a request still waited when the pass ended."""
        if self._vacancy is None:
            return
        t_last, free, waiting = self._vacancy
        dt = now - t_last
        METRICS.inc("tpu_model_slot_seconds_total",
                    self.engine.n_slots * dt)
        METRICS.inc("tpu_model_slot_vacant_seconds_total", free * dt,
                    '{queue="waiting"}' if waiting else '{queue="empty"}')

    def _host_masked(self, decoding: dict) -> bool:
        """Whether a slot of ``decoding`` needs a fresh HOST grammar mask
        for every token (device-grammar slots advance their automaton on
        the device): such a step empties the pipeline first."""
        gdev = self.engine._gdev_mode
        return any(r.constraint is not None and not gdev[s]
                   for s, r in decoding.items())

    def _unfilled(self) -> int:
        """Free slots beyond the requests that wait for one. The waiters
        that were cancelled or have expired are swept out first (and get
        their frames): they will fill no slot."""
        self._shed_expired()
        waiting = (len(self._admission)
                   + sum(not r.cancelled.is_set() for r in self._preempted))
        return len(self._free_slots()) - waiting

    def _hold_until(self) -> Optional[float]:
        """The _now() at which a hold before this step's pass must end so
        that the launch behind the pass still reaches the device before
        the chunk in flight lands; None where the step holds nothing.
        Measured, not set: the chunk in flight began when it was launched
        or, if later, when its predecessor's tokens reached the host
        (Engine._landed's rule) and takes what the shorter of the last
        two chunks took (_chunk_s); the step's first program takes the
        host what recent ones took (_lead_s). No hold before both have been
        measured, nor in a step that will not double-buffer: a loop that
        awaits its admissions (_launches), a host-masked slot (the
        synchronous branch)."""
        if (self._lead_s is None or len(self._chunk_s) < 2
                or not self._launches()
                or self._host_masked(self._decoding())):
            return None
        began = max(self._pending[0].t_launch, self.engine._t_landed)
        return began + min(self._chunk_s) - self._lead_s

    def _hold_pass(self):
        """Before the step's pass, with a chunk in flight: sleep on _wake
        while free slots outnumber the requests waiting and the hold's
        deadline (_hold_until) has not come. The device needs the next
        chunk queued only by the time the one in flight ends, and a pass
        made a chunk early finds nobody: a finisher's successor arrives
        milliseconds after the fan-out that freed its slot and would wait
        a whole further chunk for the next pass. submit() sets _wake, so
        an arrival ends the sleep and the condition is read again; ONE
        pass then admits everyone who came (same-bucket arrivals share an
        admit_many). Nothing configures it. How it ended is counted in
        tpu_model_pass_holds_total: every free slot got its waiter
        (filled), the deadline came first, also where it had passed
        before the hold began (deadline), or there was nothing to hold
        for (none). An exclusive task or a shutdown ends it as the
        deadline does. The sleep is time this thread stood waiting on the
        dispatch in flight (acct.on_wait), as the wait it shortens is."""
        if self._pending is None:
            return
        end = "none"
        until = self._hold_until()
        # cleared before the waiters are counted: whoever submits after
        # the count sets it again and ends the first sleep
        self._wake.clear()
        if until is not None and self._unfilled() > 0:
            with span("sched.hold") as sp:
                while True:
                    left = until - self._now()
                    if left <= 0 or self._tasks or self._stop.is_set():
                        end = "deadline"
                        break
                    self._wake.wait(left)
                    self._wake.clear()
                    if self._unfilled() <= 0:
                        end = "filled"
                        break
                sp.set(end=end)
            self.acct.on_wait(sp.dur, sp.t1)
        METRICS.inc("tpu_model_pass_holds_total", 1.0, _HOLD_END[end])

    def _step(self):
        self._hold_pass()
        if self._tasks:
            # exclusive tasks see a quiet pipeline: land any in-flight
            # dispatch first so a KV import's cache upload never races a
            # decode reading the same buffers
            self._drain_pending()
            self._run_tasks()
        if self._pending is not None and self.engine.paged:
            # the chunk _fence_ack names was waited by the previous
            # step's _land, which also collected every admission launched
            # behind it, and nothing is materialised between here and
            # this step's launch, whose retire= says the same a pass
            # later: the pass's allocations find unfenced (and its
            # evictions free at once) what that chunk held
            self.engine.fence_retire(self._fence_ack)
        enqueue_s = self.engine.enqueue_s
        with span("sched.housekeep") as sp:
            t_step = sp.t0
            self._count_vacancy(t_step)
            self._shed_expired()
            self._throttle_over_limit()
            self._preempt_for_priority()
        self._piece_budget = self._piece_tokens
        if self._prefilling:
            with span("sched.prefill"):
                self._advance_prefill()
        with span("sched.admit") as sp:
            n_before = self.n_active
            self._admit_waiting()
            n_after = self.n_active
            sp.set(n=n_after - n_before)
        self._vacancy = (t_step, self.engine.n_slots - n_after,
                         bool(self._preempted)
                         or not self._admission.empty())
        if not self._decoding():
            self._drain_pending()
            # idle with pages still fenced (the last dispatch's frees):
            # unfence now so a quiet scheduler never parks pool capacity
            # in quarantine (and the conftest leak check sees zero)
            if self.engine.quarantined_pages:
                self._quiesce("idle")
            if not self._prefilling:
                with span("sched.idle") as sp:
                    self._wake.wait(timeout=0.05)
                self.acct.on_idle(sp.dur, sp.t1)
                self._wake.clear()
            return
        # drop cancelled and over-deadline slots before paying for a
        # step; under double-buffering their in-flight rows are dropped
        # by _fanout's snapshot identity check
        now = time.monotonic()
        for slot, req in self._decoding().items():
            if req.cancelled.is_set():
                self._finish(slot, req, "cancelled")
            elif req.deadline is not None and now > req.deadline:
                # mid-generation wall-clock exceeded: clean terminal
                # frame, slot released and immediately reusable
                METRICS.inc("tpu_model_request_timeouts_total")
                self._finish(slot, req, "timeout")
        decoding = self._decoding()
        if not decoding:
            self._drain_pending()
            return
        # chunked decode: ecfg.decode_chunk steps per device round-trip.
        # A slot that stops mid-chunk has its remaining rows discarded
        # (_running[slot] goes None); the over-decoded cache entries are
        # zeroed by release(). HOST-masked grammar slots need a fresh
        # host-side PDA mask per token, so the engine freezes them after
        # the chunk's FIRST step (per-slot budgets) — they advance one
        # token per dispatch while the rest of the batch keeps the full
        # chunk (round-1 weak #5: one format:"json" request used to drop
        # everyone to n=1). Device-grammar slots (engine._gdev_mode)
        # keep the full chunk: their mask refreshes on device from the
        # installed table. Only when EVERY active slot is host-masked is
        # a 1-step dispatch cheaper.
        gdev = self.engine._gdev_mode
        n_steps = (1 if all(r.constraint is not None and not gdev[s]
                            for s, r in decoding.items())
                   else None)
        with span("sched.housekeep"):
            self._relieve_pressure(n_steps)
        decoding = self._decoding()
        if not decoding:
            self._drain_pending()
            return
        # only HOST-masked grammar slots force the pipeline empty (fresh
        # PDA mask per token); device-grammar slots advance their
        # automaton on device and ride async like everyone else
        constrained = self._host_masked(decoding)
        if not self.async_dispatch or constrained:
            # synchronous path: grammar needs a fresh host PDA mask
            # between dispatches, so the pipeline must be empty before
            # this one dispatches. (In paged mode decode_n self-retires
            # its epoch, so sync dispatches also drain any quarantine
            # the async stretch left behind.)
            if self.async_dispatch:
                METRICS.inc("tpu_model_async_fallback_total", 1.0,
                            '{cause="grammar"}')
                FLIGHT.record("async_fallback", cause="grammar")
            self._drain_pending()
            with span("sched.wait") as sp:
                toks_n = self._watched(
                    lambda: self.engine.decode_n(n_steps))
            self._consecutive_failures = 0
            t0, dur = sp.t0, sp.dur
            METRICS.observe("tpu_model_dispatch_seconds", dur,
                            '{kind="decode"}')
            self.acct.on_wait(dur, sp.t1)
            if self.acct.enabled:
                hl = self.engine._host_lengths
                self.acct.on_decode(
                    dur, [int(hl[s]) for s in decoding],
                    int(np.asarray(toks_n).shape[0]),
                    self.engine.n_slots)
            for s, r in decoding.items():
                if self._running[s] is r:
                    r.trace.event_at(t0, "dispatch", kind="decode",
                                     sync=True,
                                     dur_ms=round(dur * 1e3, 3))
            self._fanout(toks_n, decoding)
            return
        # double-buffered async dispatch: launch dispatch N+1 FIRST (the
        # step began by holding the pass, and so this launch, until N was
        # about to land: _hold_pass), then materialise dispatch N, collect
        # the first tokens of the admissions this pass launched between
        # the two, and fan N out — the pass's host work and the
        # detokenise/queue work overlap device compute, and the device's
        # queue holds the chunk in flight, the pass's prefills and the
        # next chunk. Device programs stay ordered through their
        # donated-state data dependencies. The retire= ack unfences pages
        # freed behind dispatches we have already materialised (paged
        # mode; no-op dense).
        try:
            with span("sched.launch"):
                handle = (self.engine.decode_n_launch(
                              retire=self._fence_ack)
                          if self.engine.paged
                          else self.engine.decode_n_launch())
        except Exception:
            # dispatch N's tokens were already computed — deliver them
            # before the supervisor errors whoever is left
            self._drain_pending()
            raise
        prev, self._pending = self._pending, (handle, decoding)
        # whether the device's queue ran dry before this launch: asked of
        # the program queued last before it (the pass's last prefill, else
        # the chunk in flight), which syncs nothing
        if prev is None:
            timing = "empty"
        else:
            before = self._launched[-1][0] if self._launched else prev[0]
            timing = "late" if before.ready() else "ahead"
        METRICS.inc("tpu_model_decode_launches_total", 1.0,
                    _LAUNCH_TIMING[timing])
        if prev is not None:
            # what the host took until the step's first program was handed
            # to the runtime. Not a step that drained for pages on its way
            # (prev is None: it waited a chunk out), and not the time
            # inside the runtime's launch call, where a launch is held
            # while 32 programs are in flight: t_queued is stamped after
            # that wait, and a lead that holds it ends every hold at once
            first = self._launched[0][0] if self._launched else handle
            lead = first.t_queued - t_step - (first.enqueue_s - enqueue_s)
            self._lead_s = max(lead, 0.9 * (self._lead_s or 0.0))
        self._land(prev)

    def _fanout(self, toks_n, snapshot: dict):
        with span("sched.fanout"):
            self._fanout_rows(toks_n, snapshot)

    def _fanout_rows(self, toks_n, snapshot: dict):
        """Deliver one dispatch's token rows [n, B] to the requests in
        ``snapshot`` (slot → request AT LAUNCH time). Under
        double-buffering a slot may have finished, been preempted, or
        been re-admitted since the dispatch launched — rows for a slot
        whose occupant changed are dropped (the over-decoded cache
        positions are never attended; a preempted request resumes from
        exactly the tokens it was delivered).

        Per-slot chunk buffers: ONE queue item (and one monotonic stamp)
        per request per dispatch, not per token — at decode_chunk=32 this
        cuts queue/lock traffic on the consumer path 32×, which is the
        bulk of the HTTP-vs-engine throughput gap (BENCH_r05).

        Device-grammar slots consume MULTIPLE rows per dispatch: the host
        mirrors the device automaton through the installed GrammarTable
        (one trans lookup per token, validated against the exact PDA) and
        stops consuming at the row where the device escaped the table —
        later rows were sampled with the slot frozen and are garbage.
        The escape's launch-time host-length over-advance rolls back via
        _grammar_ack, the mask re-installs from the exact PDA state
        (re-entering device mode when that state is back in the table),
        and the ALREADY-LAUNCHED next dispatch — which ran with the slot
        still frozen — is marked in _gdiscard so its rows are dropped and
        its budget acked when IT fans out."""
        pend: dict = {}
        # lint: allow(host-sync-hot-path): toks_n was fetched by DecodeHandle.wait — shape read of a host array
        n_rows = int(np.asarray(toks_n).shape[0])
        # slot → [GrammarTable|None, mirrored device state id] for
        # device-grammar slots this dispatch; st < 0 = stop consuming
        gwalk: dict = {}

        def _flush(slot: int, req: Request):
            buf = pend.pop(slot, None)
            if buf:
                # chunk-normalized inter-token latency: one observation
                # per delivered chunk, spread over its tokens — the
                # per-token ITL a client actually experiences under
                # chunked decode, at 1/decode_chunk the observe() cost
                now = time.monotonic()
                if req._t_last_emit:
                    METRICS.observe(
                        "tpu_model_itl_seconds",
                        max(now - req._t_last_emit, 0.0) / len(buf))
                req._t_last_emit = now
                # tenant accounting at delivery time — every class pays
                # into its bucket; only best_effort is throttled on debt
                self._limiter.debit(req.tenant, len(buf))
                METRICS.inc("tpu_model_tenant_decode_tokens_total",
                            float(len(buf)),
                            f'{{tenant="{req.tenant}"}}')
                req.out.put(("tokens", buf))

        def _walk_start(slot: int, req: Request):
            """None = host-masked (1-token budget); else [gt, st] with
            ``st`` the mirrored device automaton state (< 0: discard
            every row of this dispatch for the slot)."""
            marked = self._gdiscard.pop(slot, None)
            if marked is req:
                # this dispatch launched while the slot sat frozen after
                # an escape: every row is garbage, and its whole launch
                # budget is overshoot
                self._grammar_ack(slot, n_rows)
                return [None, -1]
            if not self.engine._gdev_mode[slot]:
                return None
            gt = self._grammar_table(req)
            if gt is None:
                return None
            st = gt.state_id(req.constraint.state)
            if st < 0:   # host/device bookkeeping diverged: recover
                self._grammar_ack(slot, n_rows)
                self._refresh_mask(slot, req)
                return [gt, -1]
            return [gt, st]

        # lint: allow(host-sync-hot-path): toks_n was fetched by DecodeHandle.wait — the sanctioned sync point
        for row_idx, row in enumerate(np.asarray(toks_n)):
            any_running = False
            for slot, req in snapshot.items():
                if (self._running[slot] is not req
                        or slot in self._prefilling):
                    continue   # slot changed hands since launch
                any_running = True
                walk = None
                if req.constraint is not None:
                    if slot not in gwalk:
                        gwalk[slot] = _walk_start(slot, req)
                    walk = gwalk[slot]
                    if walk is None:
                        if row_idx >= 1:
                            continue  # host-masked: frozen after 1 token
                    elif walk[1] < 0:
                        continue  # device walk ended: rows are garbage
                tid = int(row[slot])  # lint: allow(host-sync-hot-path): row is a host array post-wait
                # grammar check BEFORE emitting: a dead-end state (empty
                # mask → uniform sampling over -inf logits) must not leak
                # an illegal token into the client's JSON stream
                if (req.constraint is not None
                        and tid not in req.eog_ids
                        and not req.constraint.advance(tid)):
                    if walk is not None:
                        self._grammar_ack(slot, n_rows - (row_idx + 1))
                    _flush(slot, req)
                    self._finish(slot, req, "stop")
                    continue
                if req.stats.n_generated == 0:
                    req.stats.t_first_token = time.monotonic()
                req.all_tokens.append(tid)  # EOG incl.: it's in the cache
                if tid in req.eog_ids:
                    if walk is not None:
                        # EOG transitions escape on device: the slot
                        # advanced this row then froze — reconcile the
                        # chunk's remaining budget before release
                        self._grammar_ack(slot, n_rows - (row_idx + 1))
                    _flush(slot, req)
                    self._finish(slot, req, "stop")
                    continue
                req.stats.n_generated += 1
                self.total_generated += 1
                pend.setdefault(slot, []).append(tid)
                if req.stats.n_generated >= req.max_tokens:
                    _flush(slot, req)
                    # budget exhausted = truncation, not natural stop
                    # (Ollama semantics: clients distinguish the two)
                    self._finish(slot, req, "length")
                # host-side length tracking (no device sync): the cache
                # holds the prompt plus one entry per decode step so far
                elif (req.stats.n_prompt + req.stats.n_generated
                      >= self.engine.max_seq - 1):
                    _flush(slot, req)
                    self._finish(slot, req, "length")
                elif req.constraint is not None:
                    if walk is None:
                        self._refresh_mask(slot, req)
                        continue
                    gt = walk[0]
                    nid = (int(gt.trans[walk[1], tid])  # lint: allow(host-sync-hot-path): gt.trans is host numpy (GrammarTable)
                           if tid < gt.trans.shape[1] else -1)
                    if nid >= 0:
                        walk[1] = nid   # stay on device: no host mask
                        continue
                    # device escaped AFTER emitting this token: the rest
                    # of the chunk is garbage — reconcile the launch-time
                    # over-advance, re-install the mask from the exact
                    # PDA state (re-entering device mode when it is back
                    # in the table), and mark the already-in-flight next
                    # dispatch, which ran with the slot still frozen
                    walk[1] = -1
                    self._grammar_ack(slot, n_rows - (row_idx + 1))
                    if (self._pending is not None
                            and self._pending[1].get(slot) is req):
                        self._gdiscard[slot] = req
                    self._refresh_mask(slot, req)
            if not any_running:
                break
        # end of dispatch: flush every still-running slot's chunk
        for slot in list(pend):
            req = self._running[slot]
            if req is not None:
                _flush(slot, req)
        pend.clear()
