"""Prometheus text-format metrics for the model server.

The reference exposes only controller-runtime metrics on the manager
(/root/reference/cmd/main.go:61,100-104) and has **no model-server metrics at
all** (SURVEY.md §5). These are the serving metrics the BASELINE target is
measured by: output tok/s and TTFT, plus queue/slot gauges. Scraped at
/metrics on the model server, optionally via a ServiceMonitor like the
reference's (deploy/monitor.yaml).
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Dict, List, Optional, Tuple


class Histogram:
    DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                       5.0, 10.0, 30.0, 60.0)

    def __init__(self, buckets=DEFAULT_BUCKETS):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)
        self.total = 0.0
        self.n = 0

    def observe(self, v: float):
        self.total += v
        self.n += 1
        self.counts[bisect.bisect_left(self.buckets, v)] += 1

    def render(self, name: str, labels: str = "") -> List[str]:
        out = []
        cum = 0
        lab = labels[:-1] + "," if labels else "{"
        for b, c in zip(self.buckets, self.counts):
            cum += c
            out.append(f'{name}_bucket{lab}le="{b}"}} {cum}')
        cum += self.counts[-1]
        out.append(f'{name}_bucket{lab}le="+Inf"}} {cum}')
        out.append(f"{name}_sum{labels} {self.total}")
        out.append(f"{name}_count{labels} {self.n}")
        return out


class Metrics:
    """Tiny registry: counters, gauges (callables), histograms."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, str], float] = {}
        self._gauges: Dict[Tuple[str, str], object] = {}
        self._hists: Dict[Tuple[str, str], Histogram] = {}
        self._help: Dict[str, str] = {}
        self._buckets: Dict[str, Tuple[float, ...]] = {}

    def _key(self, name, labels):
        return (name, labels)

    def describe(self, name: str, help_: str,
                 buckets: Optional[Tuple[float, ...]] = None):
        """HELP text of a family; ``buckets`` are the upper edges every
        histogram of the family is created with (default: Histogram's)."""
        self._help[name] = help_
        if buckets is not None:
            self._buckets[name] = tuple(buckets)

    def _new_hist(self, name: str) -> Histogram:
        return Histogram(self._buckets.get(name, Histogram.DEFAULT_BUCKETS))

    def inc(self, name: str, value: float = 1.0, labels: str = ""):
        with self._lock:
            k = self._key(name, labels)
            self._counters[k] = self._counters.get(k, 0.0) + value

    def get(self, name: str, labels: str = "") -> float:
        """Current value of a counter (0.0 if never incremented)."""
        with self._lock:
            return self._counters.get(self._key(name, labels), 0.0)

    def gauge_fn(self, name: str, fn, labels: str = ""):
        with self._lock:
            self._gauges[self._key(name, labels)] = fn

    def remove_gauge(self, name: str, labels: str = ""):
        with self._lock:
            self._gauges.pop(self._key(name, labels), None)

    def observe(self, name: str, v: float, labels: str = ""):
        with self._lock:
            k = self._key(name, labels)
            if k not in self._hists:
                self._hists[k] = self._new_hist(name)
            self._hists[k].observe(v)

    def seed_histogram(self, name: str, labels: str = ""):
        """Materialise an empty histogram so its buckets scrape as 0,
        not absent — the histogram analog of the inc(name, 0.0)
        counter pre-seeds below."""
        with self._lock:
            k = self._key(name, labels)
            if k not in self._hists:
                self._hists[k] = self._new_hist(name)

    def hist_buckets(self, name: str,
                     labels: str = "") -> Tuple[Tuple[float, ...],
                                                Tuple[int, ...]]:
        """(bucket upper bounds, per-bucket counts incl. the +Inf
        overflow slot) for one histogram — a snapshot callers can delta
        across a measurement window and feed to histogram_quantile-style
        interpolation (bench.py's ITL phases). Empty histogram renders
        as the default buckets with zero counts."""
        with self._lock:
            h = self._hists.get(self._key(name, labels))
            if h is None:
                h = self._new_hist(name)
            return h.buckets, tuple(h.counts)

    def hist_totals(self, name: str) -> Tuple[int, float]:
        """(observation count, value sum) aggregated across every label
        set of a histogram — e.g. total device busy-seconds across all
        tpu_model_dispatch_seconds program kinds, for the admission
        queue model's throughput estimate. (0, 0.0) when never observed."""
        with self._lock:
            n, total = 0, 0.0
            for (hname, _labels), h in self._hists.items():
                if hname == name:
                    n += h.n
                    total += h.total
            return n, total

    def render(self) -> str:
        with self._lock:
            # evaluate gauge callables FIRST: a failing one is counted in
            # tpu_model_metrics_gauge_errors_total (a silently-vanishing
            # series is how a dead weakref or a torn-down engine hides
            # from dashboards), and counters render after this pass so
            # the drop is visible in the SAME scrape. Direct dict
            # mutation, NOT self.inc(): the lock is non-reentrant.
            gauge_vals: List[Tuple[str, str, float]] = []
            for (name, labels), fn in sorted(self._gauges.items()):
                try:
                    gauge_vals.append((name, labels, float(fn())))
                except Exception:
                    k = self._key("tpu_model_metrics_gauge_errors_total",
                                  "")
                    self._counters[k] = self._counters.get(k, 0.0) + 1.0
            lines: List[str] = []
            seen = set()

            def header(name, mtype):
                if name not in seen:
                    seen.add(name)
                    if name in self._help:
                        lines.append(f"# HELP {name} {self._help[name]}")
                    lines.append(f"# TYPE {name} {mtype}")

            for (name, labels), v in sorted(self._counters.items()):
                header(name, "counter")
                lines.append(f"{name}{labels} {v}")
            for name, labels, v in gauge_vals:
                header(name, "gauge")
                lines.append(f"{name}{labels} {v}")
            for (name, labels), h in sorted(self._hists.items()):
                header(name, "histogram")
                lines.extend(h.render(name, labels))
            return "\n".join(lines) + "\n"


GLOBAL = Metrics()
GLOBAL.describe("tpu_model_generated_tokens_total",
                "Output tokens generated across all requests")
GLOBAL.describe("tpu_model_prompt_tokens_total", "Prompt tokens prefilled")
GLOBAL.describe("tpu_model_requests_total", "Completed generate requests")
GLOBAL.describe("tpu_model_ttft_seconds", "Time to first token")
GLOBAL.describe("tpu_model_decode_tokens_per_second",
                "Per-request steady-state decode rate")
GLOBAL.describe("tpu_model_active_slots", "Busy decode slots")
GLOBAL.describe("tpu_model_queue_depth", "Requests waiting for a slot")
GLOBAL.describe("tpu_model_kv_free_pages",
                "Free pages in the paged KV pool (paged mode)")
GLOBAL.describe("tpu_model_preemptions_total",
                "Requests preempted and requeued under KV-pool pressure")
GLOBAL.describe("tpu_model_stream_frames_total",
                "Streamed NDJSON/SSE frames written (after coalescing; "
                "compare to tpu_model_generated_tokens_total for the "
                "tokens-per-frame ratio)")
GLOBAL.describe("tpu_model_engine_restarts_total",
                "Supervised in-process engine restarts after decode-loop "
                "failures (no pod restart, no model reload)")
GLOBAL.describe("tpu_model_request_timeouts_total",
                "Requests cut off mid-generation by deadline_ms "
                "(terminal frame finish reason 'timeout')")
GLOBAL.describe("tpu_model_requests_shed_total",
                "Requests shed before holding a slot: deadline expired "
                "while queued, or admission queue full (HTTP 503)")
GLOBAL.describe("tpu_model_followers_lost_total",
                "Multi-host follower connections lost (send failure or "
                "missed heartbeat); the world is degraded afterwards")
GLOBAL.describe("tpu_model_admission_stall_ms_total",
                "Wall-clock milliseconds the scheduler thread stood "
                "blocked on admission prefill work: the whole dispatch of "
                "an awaited admission (one-shot, batched, a "
                "chunked-prefill piece), the collect's wait of a launched "
                "one; divide by tpu_model_prefill_chunks_total for "
                "ms/piece")
GLOBAL.describe("tpu_model_admissions_total",
                "Requests admitted into a slot, by how the loop took the "
                "first token (mode=launched|awaited): launched = the "
                "prefill was dispatched without a host sync and its token "
                "collected behind the next decode chunk's launch; awaited "
                "= the loop waited for it where the admission was made (a "
                "synchronous loop, a constrained request)")
GLOBAL.describe("tpu_model_pass_holds_total",
                "Admission passes made with a decode chunk in flight, by "
                "how the hold before the pass ended (end=filled|deadline|"
                "none): filled = the scheduler held the pass until every "
                "free slot had a request waiting; deadline = it held "
                "until the chunk in flight was about to land (less what a "
                "step takes the host to hand its first program to the "
                "runtime) with slots still free; none = nothing to hold for (no free slot without a "
                "waiter, no measured chunk yet). A "
                "pass that begins with no chunk in flight is not counted "
                "here (after a drain for pages: the passes of a paged "
                "pool that is always full); "
                "tpu_model_admission_passes_total counts every pass")
GLOBAL.describe("tpu_model_admission_passes_total",
                "Admission passes that took at least one request off the "
                "waiting line, by whether the pass had to stall for pages "
                "on the way (stalled=yes|no): yes = it found the paged "
                "pool dry with a chunk in flight or pages fenced behind "
                "one, and landed, fanned out and unfenced before it "
                "admitted (span sched.stall); a contiguous cache counts "
                "no only")
GLOBAL.describe("tpu_model_page_stalls_total",
                "Stalls for pages (span sched.stall), by what found the "
                "pool dry (cause=pool_dry_admit|pool_dry_stitch|"
                "pool_dry_decode): an admission, a radix stitch's "
                "copy-on-write, the next decode chunk's pages. Each "
                "drains the chunk in flight with the device running dry "
                "behind it; eviction of cached pages with nothing in "
                "flight is not counted. Not "
                "tpu_model_admission_stall_ms_total, which is the "
                "scheduler thread blocked on prefill work")
GLOBAL.describe("tpu_model_radix_evicted_pages_total",
                "Pages the radix prefix cache evicted, by whether the "
                "page table's epoch fence let the page reach the free "
                "list at once (fence=free|fenced): free = no slot had "
                "mapped it since the last retired epoch, so no program "
                "in flight can hold it in a block table (also every "
                "eviction with nothing in flight, and a spill to the "
                "host tier); fenced = it went to the quarantine until "
                "the chunk in flight lands. An allocation that runs dry "
                "evicts only pages that are free at once, so on a paged "
                "pool that is always full free / (free + fenced) says "
                "how often a pass got its pages without a stall")
GLOBAL.describe("tpu_model_decode_launches_total",
                "Decode chunks launched by the double-buffered loop, by "
                "what the device's queue held when the launch returned "
                "(timing=ahead|late|empty): ahead = the program queued "
                "before it (the chunk in flight, or the pass's last "
                "prefill) had not finished; late = it had, so the device "
                "stood dry until this launch; empty = no chunk was in "
                "flight at all (the first chunk, or after a drain for "
                "pages)")
GLOBAL.describe("tpu_model_prefill_chunks_total",
                "Chunked-prefill pieces dispatched (stall-free admission "
                "of long prompts in bucket-sized pieces, as many a "
                "scheduler step as hold the prompt tokens its decode chunk "
                "advances)")
GLOBAL.describe("tpu_model_prefix_hit_tokens_total",
                "Prompt tokens served from the prefix cache at admission "
                "(radix page stitch or parked-slot extend) instead of "
                "being prefilled")
GLOBAL.describe("tpu_model_prefix_miss_tokens_total",
                "Prompt tokens actually prefilled at admission; "
                "hit / (hit + miss) is the prefix-cache hit rate")
GLOBAL.describe("tpu_model_radix_nodes",
                "Radix prefix-cache tree nodes resident (one cached "
                "page_size token chunk each)")
GLOBAL.describe("tpu_model_radix_pages",
                "Physical KV pages pinned by the radix prefix cache "
                "(tier-0 nodes; spilled nodes hold host bytes instead)")
GLOBAL.describe("tpu_model_tier_hit_tokens_total",
                "Prompt tokens served from the tiered KV cache at "
                "admission, by serving tier: 0 = HBM-resident radix "
                "pages shared in place, 1 = host-arena pages restitched "
                "by async host-to-HBM copy, 2 = fleet-snapshot pages "
                "restitched after import")
GLOBAL.describe("tpu_model_tier_miss_tokens_total",
                "Prompt tokens prefilled at admission, by missed tier: "
                "0 = never cached (cold), 1/2 = spilled pages the "
                "copy-vs-recompute break-even model chose to recompute "
                "instead of restitch")
GLOBAL.describe("tpu_model_spilled_pages_total",
                "Radix KV pages spilled from HBM to the tier-1 host "
                "arena on LRU eviction (quiescent pages only; a plain "
                "eviction under fence pressure does not count)")
GLOBAL.describe("tpu_model_restitch_seconds",
                "Stitch-call latency histogram for admissions that "
                "restitched at least one host-tier page (enqueue-side: "
                "the host-to-HBM uploads themselves run async, "
                "overlapped with the tail prefill)")
GLOBAL.describe("tpu_model_host_cache_bytes",
                "Tier-1 host arena occupancy in bytes (live gauge; 0 "
                "when TPU_HOST_CACHE_GB is unset)")
GLOBAL.describe("tpu_model_host_cache_pages",
                "Spilled KV pages resident in the tier-1 host arena "
                "(live gauge)")
GLOBAL.describe("tpu_model_cache_bytes",
                "Device bytes of the loaded model's cache as allocated, by "
                "what holds them (kind=full|window|state|index): "
                "full-length rows or pages of keys and values (latent "
                "attention's: one row [latent | rotated key] a position); "
                "the rings of sliding_window positions a slot that "
                "window-attention layers keep instead of a full row; "
                "recurrent state; the keys of latent attention's indexer "
                "(index: only where the model has an indexer)")
GLOBAL.describe("tpu_model_ring_positions",
                "Positions the window-attention layers' rings of the "
                "loaded model hold (what=live: min(a slot's length, the "
                "ring's length) a slot a window layer, from the host's "
                "mirror of the lengths) against the positions they were "
                "allocated (what=allocated); only where the model has "
                "window layers")
GLOBAL.describe("tpu_model_latent_positions",
                "Cached positions latent attention's rows of the loaded "
                "model hold (what=live: an active slot's length a latent layer, "
                "from the host's mirror of the lengths) against the "
                "positions they were allocated (what=allocated: slots x "
                "the served context a latent layer); only where the model "
                "has latent attention")
GLOBAL.describe("tpu_model_async_fallback_total",
                "Decode dispatches that fell back to synchronous while "
                "TPU_ASYNC_DISPATCH was on: per-dispatch for grammar "
                "(host PDA mask between dispatches), once at startup for "
                "paged_dp (dp-sharded page pools stay sync); a "
                "silently-sync deployment shows here")
GLOBAL.describe("tpu_model_prefix_reused_tokens_total",
                "Prompt tokens served from a parked prefix cache on the "
                "request's FIRST admission (per-request view of the "
                "hit/miss token counters)")
GLOBAL.describe("tpu_model_itl_seconds",
                "Inter-token latency histogram, chunk-normalized: each "
                "delivered decode chunk observes (gap since previous "
                "delivery) / (tokens in chunk) — the per-token cadence "
                "a streaming client actually experiences")
GLOBAL.describe("tpu_model_queue_wait_seconds",
                "Submit-to-first-admission wait histogram (first "
                "admission only; a preempted request's re-admission "
                "does not re-observe). Shed requests observe their "
                "submit-to-shed wait here too — a shed IS the end of "
                "that request's queue wait")
GLOBAL.describe("tpu_model_class_queue_wait_seconds",
                "Queue wait histogram by priority class "
                "(class=high|normal|best_effort): same observation "
                "points as tpu_model_queue_wait_seconds, labelled — "
                "the per-class p99 the overload SLO gates on")
GLOBAL.describe("tpu_model_shed_total",
                "Requests shed before holding a slot, by priority "
                "class and cause (cause=queue_full|deadline|"
                "slo_predict|tenant_cap); class=\"high\" staying 0 "
                "under overload is the admission policy's contract")
GLOBAL.describe("tpu_model_tenant_throttles_total",
                "Mid-stream throttle preemptions of over-rate tenants "
                "(per-tenant decode-token rate limits; best-effort "
                "class only — the request resumes on the same stream "
                "once the token bucket refills)")
GLOBAL.describe("tpu_model_tenant_decode_tokens_total",
                "Decode tokens delivered per tenant "
                "(tenant=\"default\" is the no-key bucket) — the "
                "series behind WDRR fairness dashboards")
GLOBAL.describe("tpu_model_dispatch_seconds",
                "Device dispatch latency histogram by program kind "
                "(kind=decode|admit|extend): what the dispatch "
                "took, from the later of its launch and its predecessor's "
                "tokens reaching the host to its own tokens on the host "
                "(a dispatch queued behind one that still runs does not "
                "count that one's remainder; the engine's last values "
                "are in /api/ps dispatch)")
GLOBAL.describe("tpu_model_metrics_gauge_errors_total",
                "Gauge callables that raised during /metrics render; a "
                "nonzero rate means a series is silently missing from "
                "scrapes (dead weakref, torn-down engine)")
GLOBAL.describe("tpu_model_hbm_bytes_in_use",
                "Accelerator memory in use on local device 0 "
                "(jax memory_stats; 0 when the backend reports none)")
GLOBAL.describe("tpu_model_flight_recorder_events",
                "Structured events recorded into the flight-recorder "
                "ring so far (runtime/trace.py); the ring keeps only "
                "the last TPU_FLIGHT_EVENTS of them")
GLOBAL.describe("tpu_model_replayed_requests_total",
                "In-flight streams recovered across a supervised engine "
                "restart by replay (re-prefill of prompt+generated, "
                "bit-identical continuation on the same stream) instead "
                "of an error frame")
GLOBAL.describe("tpu_model_replayed_tokens_total",
                "Prompt+generated tokens re-prefilled by restart "
                "replay; bounded per restart by "
                "TPU_RESTART_REPLAY_TOKENS")
GLOBAL.describe("tpu_model_replay_fallback_total",
                "In-flight streams that could NOT be replayed across a "
                "restart and got the exactly-once error instead, by "
                "cause (cause=nondeterministic|multimodal|over_budget|"
                "faulted|broken)")
GLOBAL.describe("tpu_model_drain_started_total",
                "Graceful-drain activations (SIGTERM / preStop): new "
                "submits shed 503 while running streams finish")
GLOBAL.describe("tpu_model_drain_shed_total",
                "Requests shed by graceful drain: new submits refused "
                "while draining, plus stragglers cut at "
                "TPU_DRAIN_TIMEOUT_S")
GLOBAL.describe("tpu_model_watchdog_fires_total",
                "Hung-dispatch watchdog fires (dispatch wait exceeded "
                "TPU_DISPATCH_WATCHDOG_MS or the histogram-derived "
                "ceiling); each one forces a supervised restart + "
                "replay")
GLOBAL.describe("tpu_model_recompiles_total",
                "Mid-serving XLA compiles, by program kind (kind=decode|"
                "admit|admit_many|extend): an executable-cache miss "
                "OUTSIDE warm_buckets, paid inside a timed dispatch. "
                "Nonzero after warmup means the warm plan missed a "
                "signature")
GLOBAL.describe("tpu_model_useful_tokens_total",
                "Useful token positions computed per dispatch kind "
                "(kind=decode|prefill): active slots' steps, real "
                "prompt positions — the goodput numerator "
                "(runtime/accounting.py)")
GLOBAL.describe("tpu_model_padded_tokens_total",
                "Padding-waste token positions per dispatch kind: empty "
                "batch slots x steps, prefill bucket positions past the "
                "prompt chunk — the waste half of the goodput split")
GLOBAL.describe("tpu_model_decode_steps_total",
                "Decode steps launched, by the sampler each took on the "
                "device (sampler=argmax|candidates): argmax where no "
                "live slot's temperature is above zero, so the step "
                "skips the top-1024 candidate sort of the vocabulary "
                "(ops/sampling.needs_candidates, evaluated over the "
                "host's mirror of the slots active at launch)")
GLOBAL.describe("tpu_model_moe_expert_tokens_total",
                "Tokens the routers of a model with experts kept each "
                "expert for in decode steps, summed over the routed layers "
                "(expert=0..router width-1, of all the router's outputs, "
                "held on this chip or not): counted on the device, taken "
                "to the host once a decode chunk beside the tokens; "
                "seeded when the engine of such a model is built "
                "(seed_expert_tokens), absent for a model without a router")
GLOBAL.describe("tpu_model_index_positions_total",
                "Cached positions the decode steps of a model with latent "
                "attention's indexer had before them (what=seen: a step of "
                "a sequence of n positions, its new one counted, sees n) "
                "and the positions its attention read of them (what=kept: "
                "min(n, index_topk)), a layer, over the active slots: "
                "from the host's lengths once a decode chunk, no device "
                "work; kept = seen says the selection slept. Seeded when "
                "the engine of such a model is built "
                "(seed_index_positions), absent for other models")
GLOBAL.describe("tpu_model_model_flops_total",
                "Analytic model FLOPs issued for active slots (matmul "
                "terms only, MFU convention of Chowdhery et al.); rate() "
                "over this / peak = MFU over any window")
GLOBAL.describe("tpu_model_breakdown_seconds_total",
                "Scheduler wall-clock classified by phase "
                "(phase=dispatch_wait|host|idle): where the serving "
                "thread's time goes between device programs")
GLOBAL.describe("tpu_model_mfu",
                "Achieved model-FLOPs utilization vs device peak over "
                "the last 60s (0..1; 0 when no peak is known — CPU "
                "without TPU_PEAK_FLOPS)")
GLOBAL.describe("tpu_model_autoscale_decisions_total",
                "Autoscaler scale actions taken, by action "
                "(action=up|down|to_zero|wake): each is one damped "
                "single-step move of the desired replica count "
                "(operator/autoscale.py)")
GLOBAL.describe("tpu_model_autoscale_holds_total",
                "Autoscaler passes that held the last decision instead "
                "of scaling, by cause (cause=no_data|stale|flap|"
                "cooldown): no_data/stale are the fail-static guard — "
                "a missing or stale replica scrape must never produce "
                "a scale action")
GLOBAL.describe("tpu_model_remediation_replacements_total",
                "Broken replicas replaced by the operator, by cause "
                "(cause=unreachable|crash_loop): the pod is deleted and "
                "the ReplicaSet recreates it — the fleet never shrinks "
                "below minReplicas")
GLOBAL.describe("tpu_model_remediation_backoff_holds_total",
                "Remediation opportunities skipped because the "
                "exponential replacement backoff was still closed "
                "(doubles per replacement up to the cap; resets on a "
                "clean scrape pass)")
GLOBAL.describe("tpu_model_warm_snapshot_saves_total",
                "AOT warm-bucket executable cache snapshots persisted "
                "to the image-store PVC at drain time (scale-to-zero "
                "fast cold-start)")
GLOBAL.describe("tpu_model_scrape_failures_total",
                "Replica /api/ps scrapes the operator lost, by cause "
                "(cause=fault|http|network|parse): each one is a hole "
                "in the autoscaler's evidence — correlate with "
                "tpu_model_autoscale_holds_total{cause=\"no_data\"} to "
                "attribute fail-static holds (operator/client.py)")
GLOBAL.describe("tpu_model_gateway_routes_total",
                "Gateway routing decisions by resolution path "
                "(path=affinity|probe|least_loaded): affinity = "
                "prefix-hash table hit, probe = /api/prefix_probe "
                "scatter won, least_loaded = no cache evidence "
                "(operator/gateway.py)")
GLOBAL.describe("tpu_model_gateway_failovers_total",
                "Streams the gateway moved off a dead replica, by "
                "outcome (result=replayed|requeued|errored): replayed = "
                "mid-stream continuation on a healthy replica (zero "
                "client error frames), requeued = unstarted request "
                "re-dispatched, errored = non-replayable stream given "
                "the exactly-once error with Retry-After")
GLOBAL.describe("tpu_model_gateway_ejections_total",
                "Replica circuits opened by the gateway health state "
                "machine, by trigger (cause=failures|slow|not_ready)")
GLOBAL.describe("tpu_model_gateway_half_open_probes_total",
                "Half-open circuit probe requests admitted (exactly one "
                "per eject window), by outcome (result=ok|fail)")
GLOBAL.describe("tpu_model_gateway_replicas",
                "Replicas the gateway currently tracks in each health "
                "state (state=probe|healthy|ejected|half_open|draining) "
                "— the circuit-state view of the fleet")
GLOBAL.describe("tpu_model_warm_snapshot_restores_total",
                "Engine warm-ups served from a persisted warm snapshot "
                "instead of a from-scratch warm_buckets compile pass — "
                "a woken replica's first request must not trip "
                "tpu_model_recompiles_total")
GLOBAL.describe("tpu_model_gateway_persist_writes_total",
                "Journal/affinity snapshot records appended to the "
                "gateway's crash-recovery log (TPU_GATEWAY_PERSIST), "
                "fsync batched per flush window")
GLOBAL.describe("tpu_model_gateway_persist_restores_total",
                "Journaled streams restored from the persist log at "
                "gateway restart (each is a request a reconnecting "
                "client can splice byte-identically)")
GLOBAL.describe("tpu_model_gateway_drain_total",
                "Gateway graceful-drain activations (SIGTERM / preStop): "
                "stop accepting, finish proxied streams, persist, exit")
GLOBAL.describe("tpu_model_follower_lag_seconds",
                "Slowest follower's broadcast send lag over the control "
                "plane (bounded by TPU_CP_SEND_TIMEOUT_S — a follower "
                "that exceeds the bound is declared dead, not slow)")
GLOBAL.describe("tpu_model_leader_lost_total",
                "Follower exits after a silent leader (no control-stream "
                "traffic, heartbeats included, for longer than "
                "TPU_CP_LEADER_TIMEOUT_S): fail-static clean exit "
                "instead of hanging on the broadcast socket")
GLOBAL.describe("tpu_model_chaos_events_total",
                "Randomized chaos-campaign fault events injected, by "
                "fault point (runtime/chaos.py; the label set is the "
                "full FAULTS catalog)")
GLOBAL.describe("tpu_model_disagg_handoffs_total",
                "Disaggregated prefill->decode handoffs at the gateway, "
                "by outcome (result=transferred|replayed|"
                "unified_fallback): transferred = KV pages moved and the "
                "decode pool continued the stream, replayed = transfer "
                "failed and the journal replay path re-prefilled on "
                "decode, unified_fallback = no decode replica routable "
                "so the request served unified — every rung is "
                "bit-identical to the client (ISSUE 20)")
GLOBAL.describe("tpu_model_kv_transfer_pages_total",
                "KV pages imported over replica-to-replica transfer "
                "(/api/kv_import pull from the prefill replica)")
GLOBAL.describe("tpu_model_kv_transfer_bytes_total",
                "Wire bytes of KV page payload imported over "
                "replica-to-replica transfer (pre-decode, i.e. the "
                "kv_wire blob size; bounded per-export by "
                "TPU_DISAGG_TRANSFER_MB_S pacing)")
GLOBAL.describe("tpu_model_kv_transfer_seconds",
                "End-to-end KV transfer latency histogram per handoff "
                "(decode-side: pull from prefill + upload + radix "
                "graft); only transfers that imported >0 pages observe")
GLOBAL.describe("tpu_model_disagg_pool_replicas",
                "Replicas the gateway tracks per disagg pool "
                "(pool=unified|prefill|decode); unified fleets read "
                "everything under pool=\"unified\"")
# log-spaced, 1 ms to 60 s in 50 steps of 24.6%: a percentile read from
# bucket deltas lies within ~12% (the default buckets: a factor of two)
STAGE_BUCKETS = tuple(round(1e-3 * 6e4 ** (i / 50.0), 6) for i in range(51))
GLOBAL.describe("tpu_model_span_seconds",
                "Host time inside each span of the closed vocabulary in "
                "runtime/trace.py (span=http.ingress|http.flush|sched.*|"
                "engine.*), nested spans included; while a profiler "
                "session runs the same spans are TraceAnnotations on the "
                "device planes' clock",
                buckets=(1e-5, 1e-4, 1e-3, 0.01, 0.1, 1.0, 10.0))
GLOBAL.describe("tpu_model_request_stage_seconds",
                "A finished request's time by stage (stage=ingress: "
                "handler start to queued; queue: to the start of its "
                "first prefill dispatch; prefill: to the first token on "
                "the host; first_flush: first token to its frame written; "
                "decode: first token to finish), folded from its "
                "RequestTrace when the response ends — off with "
                "TPU_TRACE=0",
                buckets=STAGE_BUCKETS)
GLOBAL.describe("tpu_model_admit_dispatch_seconds",
                "One admission dispatch (admit, admit_many, extend; a "
                "batched one once) by part, observed when its first "
                "token reaches the host (part=launch: host staging and "
                "the call into the runtime, held there or not; behind: "
                "queued on the device behind the chunk in flight and the "
                "pass's earlier prefills; run: what the dispatch itself "
                "took, the time its token lay unfetched included). The "
                "parts add up to the prefill event's dur_ms, which "
                "tpu_model_request_stage_seconds{stage=\"prefill\"} "
                "reads; on with TPU_TRACE=0 too",
                buckets=STAGE_BUCKETS)
GLOBAL.describe("tpu_model_slot_vacant_seconds_total",
                "Slot-seconds a decode slot stood free, split by whether "
                "a request was waiting for admission meanwhile "
                "(queue=waiting|empty); over "
                "tpu_model_slot_seconds_total it is the share of "
                "capacity admission left unused")
GLOBAL.describe("tpu_model_slot_seconds_total",
                "Slot-seconds the scheduler loop accounted: max_slots x "
                "wall time of its iterations")
# pre-seed the failure counters at 0: alert rules rate() over these, and
# a series that first appears AT the first failure hides that failure
# (the stall/chunk counters likewise: a mixed-load dashboard must read 0,
# not absent, on an idle server)
for _name in ("tpu_model_engine_restarts_total",
              "tpu_model_request_timeouts_total",
              "tpu_model_requests_shed_total",
              "tpu_model_followers_lost_total",
              "tpu_model_admission_stall_ms_total",
              "tpu_model_prefill_chunks_total",
              "tpu_model_prefix_hit_tokens_total",
              "tpu_model_prefix_miss_tokens_total",
              "tpu_model_spilled_pages_total",
              # traffic counters: an idle (or freshly-restarted) server
              # must scrape 0, not absent — a dashboard rate() over an
              # absent series renders "no data" exactly when someone is
              # checking whether the server serves at all
              "tpu_model_preemptions_total",
              "tpu_model_requests_total",
              "tpu_model_generated_tokens_total",
              "tpu_model_prompt_tokens_total",
              "tpu_model_stream_frames_total",
              "tpu_model_prefix_reused_tokens_total",
              # lifecycle counters (restart replay / drain / watchdog):
              # the whole point is alerting on rare events, so the
              # series must exist from the first scrape
              "tpu_model_replayed_requests_total",
              "tpu_model_replayed_tokens_total",
              "tpu_model_drain_started_total",
              "tpu_model_drain_shed_total",
              "tpu_model_watchdog_fires_total",
              # render() itself maintains this one; pre-seeded so the
              # zero-error steady state is a visible 0
              "tpu_model_metrics_gauge_errors_total"):
    GLOBAL.inc(_name, 0.0)
# replay fallbacks are labelled by cause; pre-seed every cause so a
# rate() alert on any of them reads 0, not absent, on a healthy server
for _cause in ("nondeterministic", "multimodal", "over_budget",
               "faulted", "broken"):
    GLOBAL.inc("tpu_model_replay_fallback_total", 0.0,
               f'{{cause="{_cause}"}}')
# the async-fallback counter is labelled, so pre-seed every cause — an
# alert on rate(cause="grammar") must read 0, not absent, while async
# dispatch is running clean
# tiered KV cache: the full 3-tier hit/miss matrix must read 0, not
# absent, before the first admission — the churn dashboards compute
# per-tier hit rates from these from the very first scrape
for _tier in ("0", "1", "2"):
    GLOBAL.inc("tpu_model_tier_hit_tokens_total", 0.0,
               f'{{tier="{_tier}"}}')
    GLOBAL.inc("tpu_model_tier_miss_tokens_total", 0.0,
               f'{{tier="{_tier}"}}')
# the restitch histogram likewise: a latency dashboard over a server
# that has never restitched must read empty buckets, not "no data"
GLOBAL.seed_histogram("tpu_model_restitch_seconds")
for _cause in ("grammar", "paged_dp"):
    GLOBAL.inc("tpu_model_async_fallback_total", 0.0,
               f'{{cause="{_cause}"}}')
# admission-control counters: every class × cause combination pre-seeded
# so overload alert rules (and the tpu_model_shed_total{class="high"}==0
# invariant check) read 0, not absent, on a healthy server. Label keys
# are rendered in sorted order (class before cause) — reads via
# METRICS.get must use the identical string (admission.shed_labels)
for _class in ("high", "normal", "best_effort"):
    for _cause in ("queue_full", "deadline", "slo_predict", "tenant_cap"):
        GLOBAL.inc("tpu_model_shed_total", 0.0,
                   f'{{class="{_class}",cause="{_cause}"}}')
GLOBAL.inc("tpu_model_tenant_throttles_total", 0.0,
           '{class="best_effort",tenant="default"}')
GLOBAL.inc("tpu_model_tenant_decode_tokens_total", 0.0,
           '{tenant="default"}')
# utilization accounting (runtime/accounting.py): the recompile alert and
# the goodput/waste dashboards must read 0, not absent, from the first
# scrape — a recompile series that first appears AT the first mid-serving
# compile hides exactly the event it exists to expose
for _kind in ("decode", "admit", "admit_many", "extend"):
    GLOBAL.inc("tpu_model_recompiles_total", 0.0, f'{{kind="{_kind}"}}')
for _kind in ("decode", "prefill"):
    GLOBAL.inc("tpu_model_useful_tokens_total", 0.0, f'{{kind="{_kind}"}}')
    GLOBAL.inc("tpu_model_padded_tokens_total", 0.0, f'{{kind="{_kind}"}}')
for _sampler in ("argmax", "candidates"):
    GLOBAL.inc("tpu_model_decode_steps_total", 0.0,
               f'{{sampler="{_sampler}"}}')
for _mode in ("launched", "awaited"):
    GLOBAL.inc("tpu_model_admissions_total", 0.0, f'{{mode="{_mode}"}}')
for _end in ("filled", "deadline", "none"):
    GLOBAL.inc("tpu_model_pass_holds_total", 0.0, f'{{end="{_end}"}}')
for _timing in ("ahead", "late", "empty"):
    GLOBAL.inc("tpu_model_decode_launches_total", 0.0,
               f'{{timing="{_timing}"}}')
for _stalled in ("yes", "no"):
    GLOBAL.inc("tpu_model_admission_passes_total", 0.0,
               f'{{stalled="{_stalled}"}}')
for _cause in ("pool_dry_admit", "pool_dry_stitch", "pool_dry_decode"):
    GLOBAL.inc("tpu_model_page_stalls_total", 0.0, f'{{cause="{_cause}"}}')
for _fence in ("free", "fenced"):
    GLOBAL.inc("tpu_model_radix_evicted_pages_total", 0.0,
               f'{{fence="{_fence}"}}')
for _part in ("launch", "behind", "run"):
    GLOBAL.seed_histogram("tpu_model_admit_dispatch_seconds",
                          f'{{part="{_part}"}}')
GLOBAL.inc("tpu_model_model_flops_total", 0.0)


def seed_expert_tokens(n_experts: int) -> None:
    """One series at 0 for each output of a model's router: the label
    values are the model's, so the engine seeds them when it is built."""
    for _e in range(n_experts):
        GLOBAL.inc("tpu_model_moe_expert_tokens_total", 0.0,
                   f'{{expert="{_e}"}}')


def seed_index_positions() -> None:
    """Both series at 0: the engine of a model with an indexer seeds them
    when it is built."""
    for _what in ("seen", "kept"):
        GLOBAL.inc("tpu_model_index_positions_total", 0.0,
                   f'{{what="{_what}"}}')


for _queue in ("waiting", "empty"):
    GLOBAL.inc("tpu_model_slot_vacant_seconds_total", 0.0,
               f'{{queue="{_queue}"}}')
GLOBAL.inc("tpu_model_slot_seconds_total", 0.0)
for _phase in ("dispatch_wait", "host", "idle"):
    GLOBAL.inc("tpu_model_breakdown_seconds_total", 0.0,
               f'{{phase="{_phase}"}}')
# closed-loop fleet control (operator/autoscale.py): scale decisions,
# fail-static holds, and remediation are exactly the rare events alert
# rules watch — every labelled combination pre-seeded so rate() reads 0,
# not absent, on a fleet that has never scaled or broken
for _action in ("up", "down", "to_zero", "wake"):
    GLOBAL.inc("tpu_model_autoscale_decisions_total", 0.0,
               f'{{action="{_action}"}}')
for _cause in ("no_data", "stale", "flap", "cooldown"):
    GLOBAL.inc("tpu_model_autoscale_holds_total", 0.0,
               f'{{cause="{_cause}"}}')
for _cause in ("unreachable", "crash_loop"):
    GLOBAL.inc("tpu_model_remediation_replacements_total", 0.0,
               f'{{cause="{_cause}"}}')
GLOBAL.inc("tpu_model_remediation_backoff_holds_total", 0.0)
GLOBAL.inc("tpu_model_warm_snapshot_saves_total", 0.0)
GLOBAL.inc("tpu_model_warm_snapshot_restores_total", 0.0)
# fleet gateway (operator/gateway.py) + scrape attribution: failovers and
# circuit ejections are the rare events the fleet dashboards alert on, so
# every labelled combination must read 0, not absent, before the first
# replica ever misbehaves
for _cause in ("fault", "http", "network", "parse"):
    GLOBAL.inc("tpu_model_scrape_failures_total", 0.0,
               f'{{cause="{_cause}"}}')
for _path in ("affinity", "probe", "least_loaded"):
    GLOBAL.inc("tpu_model_gateway_routes_total", 0.0,
               f'{{path="{_path}"}}')
for _result in ("replayed", "requeued", "errored"):
    GLOBAL.inc("tpu_model_gateway_failovers_total", 0.0,
               f'{{result="{_result}"}}')
for _cause in ("failures", "slow", "not_ready"):
    GLOBAL.inc("tpu_model_gateway_ejections_total", 0.0,
               f'{{cause="{_cause}"}}')
for _result in ("ok", "fail"):
    GLOBAL.inc("tpu_model_gateway_half_open_probes_total", 0.0,
               f'{{result="{_result}"}}')
# gateway crash recovery + multi-host partition tolerance: rare-event
# counters the robustness dashboards alert on — all visible as 0 from
# the first scrape (tpu_model_follower_lag_seconds is a live gauge the
# control plane registers, not a counter)
GLOBAL.inc("tpu_model_gateway_persist_writes_total", 0.0)
GLOBAL.inc("tpu_model_gateway_persist_restores_total", 0.0)
GLOBAL.inc("tpu_model_gateway_drain_total", 0.0)
GLOBAL.inc("tpu_model_leader_lost_total", 0.0)
# disaggregated serving (ISSUE 20): every handoff rung pre-seeded — the
# acceptance dashboards alert on replayed/unified_fallback rates, and a
# fleet that has never handed off must read 0, not absent
for _result in ("transferred", "replayed", "unified_fallback"):
    GLOBAL.inc("tpu_model_disagg_handoffs_total", 0.0,
               f'{{result="{_result}"}}')
GLOBAL.inc("tpu_model_kv_transfer_pages_total", 0.0)
GLOBAL.inc("tpu_model_kv_transfer_bytes_total", 0.0)
GLOBAL.seed_histogram("tpu_model_kv_transfer_seconds")
# chaos-campaign event counter: one series per registered fault point
# (this literal list mirrors runtime/faults.py CATALOG; test_faults
# asserts the two stay in sync)
for _point in ("admission.predict", "detok.feed", "engine.admit",
               "engine.step", "engine.watchdog", "follower.send",
               "gateway.handoff", "gateway.route", "gateway.stream",
               "kube.request", "operator.scrape", "pages.alloc",
               "pages.export", "pages.import", "pages.restitch",
               "pages.spill", "scheduler.replay"):
    GLOBAL.inc("tpu_model_chaos_events_total", 0.0,
               f'{{point="{_point}"}}')


class Stopwatch:
    def __init__(self):
        self.t0 = time.monotonic()

    def elapsed(self):
        return time.monotonic() - self.t0
