"""Single declaration point for every ``TPU_*`` environment variable.

Every knob the package reads — directly via ``os.environ`` or through a
helper (``pick_i``/``pick_f`` in the autoscaler, ``arm_from_env`` in the
fault injector, ``_parse_kv_floats`` in admission) — is declared here
exactly once with its type, default, owning subsystem and a one-line
doc.  The ``knob-registry`` lint pass (tools/invariant_lint) enforces
the contract in three directions:

- a ``TPU_*`` read anywhere in the package must have a declaration here;
- a declaration here must still be mentioned by code (no stale rows);
- every declared knob must appear in the docs/en *and* docs/zh-CN knob
  tables, and the docs must not mention undeclared names.

The registry is data, not plumbing: call sites keep their existing
``os.environ.get(...)`` reads (so defaults stay next to the logic that
interprets them) and this module is the place a human or the linter
looks to see the full surface.  ``python -m
ollama_operator_tpu.runtime.knobs`` prints the catalog.

Types are informal: ``int`` / ``float`` / ``bool`` (0/1 or
false-ish strings) / ``str`` / ``enum`` (closed value set) / ``map``
(``k=v,k=v`` grammar).  ``default=None`` means "unset = feature off or
value derived elsewhere"; the doc says which.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    type: str
    default: Any
    subsystem: str
    doc: str


REGISTRY: Dict[str, Knob] = {}


def declare(name: str, type: str, default: Any, subsystem: str,
            doc: str) -> Knob:
    """Register one knob.  Raises on duplicate declaration so the file
    can't silently shadow an earlier row."""
    if name in REGISTRY:
        raise ValueError(f"knob {name} declared twice")
    k = Knob(name, type, default, subsystem, doc)
    REGISTRY[name] = k
    return k


def lookup(name: str) -> Optional[Knob]:
    return REGISTRY.get(name)


def all_knobs() -> List[Knob]:
    return [REGISTRY[n] for n in sorted(REGISTRY)]


# -- engine -----------------------------------------------------------------

declare("TPU_ENGINE_DTYPE", "enum", None, "engine",
        "weight dtype override (bfloat16|bf16|float32|int8|int4); unset = "
        "resolved per model at load")
declare("TPU_KV_DTYPE", "enum", None, "engine",
        "KV-cache storage dtype (bfloat16|float32|int8|int4); int4 is "
        "paged-only (nibble-packed pages); unset = int8 on TPU, float32 "
        "on CPU")
declare("TPU_MAX_SLOTS", "int", 0, "engine",
        "continuous-batching slots; 0 = per-model default (32 paged, "
        "8 dense)")
declare("TPU_MAX_SEQ_LEN", "int", 4096, "engine",
        "maximum sequence length a slot can hold")
declare("TPU_DECODE_CHUNK", "int", 0, "engine",
        "decode steps per device round-trip; 0 = backend default "
        "(32 TPU, 8 CPU)")
declare("TPU_MIN_PREFILL_BUCKET", "int", 0, "engine",
        "floor for the padded prefill bucket ladder; 0 = engine-config "
        "default")
declare("TPU_WARM_SNAPSHOT_EXECS", "bool", None, "engine",
        "0 skips serialising warm executables into the snapshot; unset = "
        "backend default")

# -- paged KV ---------------------------------------------------------------

declare("TPU_PAGED", "bool", None, "paged",
        "1 forces the paged KV cache, 0 forces dense; unset = per-model "
        "default (paged for GQA and MHA on a TPU)")
declare("TPU_PAGE_SIZE", "int", 0, "paged",
        "KV pool page size in tokens; 0 = backend default (128 paged TPU, "
        "else 64)")
declare("TPU_N_PAGES", "int", 0, "paged",
        "KV pool page count; 0 = dense-equivalent "
        "max_slots*max_seq_len/page_size")

# -- scheduler --------------------------------------------------------------

declare("TPU_ASYNC_DISPATCH", "bool", 1, "scheduler",
        "0 disables double-buffered async decode dispatch")
declare("TPU_GRAMMAR_DEVICE", "bool", 1, "scheduler",
        "0 disables device-side constrained decode (precomputed grammar "
        "mask/transition tables indexed by a device-resident FSM state); "
        "constrained slots then pay one sync dispatch per token")
declare("TPU_GRAMMAR_STATES", "int", 64, "scheduler",
        "device grammar-table capacity in automaton states; walks that "
        "leave the table escape to host masks for that request")
declare("TPU_PREFILL_CHUNK", "int", None, "scheduler",
        "prefill chunk size in tokens; unset = adaptive per-model choice")
declare("TPU_PREFIX_CACHE", "bool", 1, "scheduler",
        "0 disables the radix prefix cache")
declare("TPU_MIN_PREFIX_REUSE", "int", 16, "scheduler",
        "minimum shared-token run before the prefix cache reuses pages")
declare("TPU_HOST_CACHE_GB", "float", 0, "scheduler",
        "tier-1 host-RAM arena size in GiB for spilled radix KV pages "
        "(fractional OK); 0 disables tiering and eviction frees pages")
declare("TPU_HOST_CACHE_BW_GBPS", "float", 8, "scheduler",
        "assumed host-to-HBM copy bandwidth in GB/s for the "
        "restitch-vs-recompute break-even model")
declare("TPU_HOST_CACHE_BREAK_EVEN", "int", 0, "scheduler",
        "flat token floor overriding the break-even model: restitch "
        "spilled runs of >= this many tokens, recompute shorter ones; "
        "0 = use the FLOPs/bandwidth model")
declare("TPU_HOST_CACHE_SNAPSHOT", "bool", 1, "scheduler",
        "0 disables tier-2 prefix snapshots (export at drain, import "
        "at load) on the shared weight-cache volume")
declare("TPU_HOST_CACHE_SNAPSHOT_MB", "int", 64, "scheduler",
        "byte budget for an exported tier-2 prefix snapshot "
        "(most-recently-used prefixes first)")
declare("TPU_PRIORITY_PREEMPT", "bool", 1, "scheduler",
        "0 disables priority preemption of running low-priority slots")
declare("TPU_DISPATCH_WATCHDOG_MS", "int", None, "scheduler",
        "hung-dispatch watchdog bound in ms; unset = histogram-derived, "
        "0 = off")

# -- admission --------------------------------------------------------------

declare("TPU_DEFAULT_PRIORITY", "enum", "normal", "admission",
        "priority class for requests that don't set one "
        "(high|normal|best_effort)")
declare("TPU_TTFT_SLO_MS", "int", None, "admission",
        "TTFT SLO for admission control in ms; unset disables SLO-aware "
        "shedding")
declare("TPU_ADMIT_THROUGHPUT_TPS", "float", None, "admission",
        "fixed tokens/s throughput for the TTFT queue model; unset = "
        "measured online")
declare("TPU_WDRR_QUANTUM", "float", 256, "admission",
        "weighted deficit round-robin quantum in tokens per tenant turn")
declare("TPU_TENANT_WEIGHTS", "map", None, "admission",
        "per-tenant WDRR weights, e.g. teamA=2,teamB=1")
declare("TPU_TENANT_LIMITS", "map", None, "admission",
        "per-tenant token-rate limits, e.g. teamA=50,teamB=100")
declare("TPU_TENANT_TOKEN_RATE", "float", 0, "admission",
        "default per-tenant token refill rate; 0 disables rate limiting")
declare("TPU_TENANT_BURST_S", "float", 2, "admission",
        "token-bucket burst window in seconds of refill")
declare("TPU_TENANT_MAX_QUEUED", "int", 0, "admission",
        "per-tenant queued-request cap; 0 = unlimited")

# -- server / HTTP ----------------------------------------------------------

declare("TPU_PRELOAD_MODEL", "str", None, "server",
        "model name to load at startup")
declare("TPU_WEIGHT_CACHE", "str", None, "server",
        "transcoded-weights cache directory")
declare("TPU_STORE_ONLY", "bool", 0, "server",
        "1 runs registry/store mode with no inference engine")
declare("TPU_XLA_CACHE", "bool", 1, "server",
        "0 disables the persistent XLA compilation cache (kept where "
        "JAX_COMPILATION_CACHE_DIR says, else at one fixed path in the "
        "checkout)")
declare("TPU_EXPECT_PLATFORM", "str", None, "server",
        "fail startup unless the JAX backend matches (tpu|cpu); set by "
        "the operator on TPU pods")
declare("TPU_HTTP_WORKERS", "int", 64, "server",
        "HTTP server thread-pool size")
declare("TPU_STREAM_FLUSH_TOKENS", "int", 16, "server",
        "stream chunk coalescing: flush after this many tokens")
declare("TPU_STREAM_FLUSH_MS", "int", 25, "server",
        "stream chunk coalescing: flush after this many milliseconds")
declare("TPU_REQUEST_DEADLINE_MS", "int", None, "server",
        "server-side request deadline in ms; unset disables")
declare("TPU_PROFILE_PORT", "int", 0, "server",
        "jax.profiler server port; 0 = off")
declare("TPU_DEBUG_PROFILE", "bool", 0, "server",
        "1 enables the /debug/profile capture endpoint")

# -- parallelism ------------------------------------------------------------

declare("TPU_TENSOR_PARALLEL", "int", 0, "parallel",
        "tensor-parallel ways; 0 = all local devices")
declare("TPU_SEQUENCE_PARALLEL", "int", 1, "parallel",
        "sequence-parallel ways (ring attention, sequence-sharded KV)")
declare("TPU_EXPERT_PARALLEL", "int", 1, "parallel",
        "expert-parallel ways for MoE meshes")
declare("TPU_DATA_PARALLEL", "int", 0, "parallel",
        "in-engine data-parallel ways; 0 = derive from leftover devices")

# -- multi-host -------------------------------------------------------------

declare("TPU_DIST_HOSTS", "int", 1, "multihost",
        "number of processes in the slice (StatefulSet replicas); "
        "operator-injected")
declare("TPU_DIST_CHIPS_PER_HOST", "int", None, "multihost",
        "chips each process owns (informational); operator-injected")
declare("TPU_DIST_COORDINATOR", "str", None, "multihost",
        "host:port of process 0 for jax.distributed; operator-injected")
declare("TPU_DIST_POD_NAME", "str", None, "multihost",
        "this pod's name; the trailing -<ordinal> is the process index")
declare("TPU_DIST_STS_NAME", "str", None, "multihost",
        "StatefulSet name used to derive peer DNS names; "
        "operator-injected")
declare("TPU_DIST_CONTROL", "str", None, "multihost",
        "host:port of the leader control stream the follower replays; "
        "operator-injected")
declare("TPU_CP_HEARTBEAT_S", "float", 10, "multihost",
        "control-plane heartbeat period in seconds; 0 disables")
declare("TPU_CP_LEADER_TIMEOUT_S", "float", 60, "multihost",
        "follower exits cleanly (fail static) when the leader control "
        "stream is silent this long; 0 disables the watchdog")
declare("TPU_CP_SEND_TIMEOUT_S", "float", 20, "multihost",
        "leader-side per-follower send backpressure bound; a broadcast "
        "blocked past this counts the follower dead (FollowerLost) "
        "instead of wedging every dispatch; 0 disables")

# -- lifecycle --------------------------------------------------------------

declare("TPU_DRAIN_TIMEOUT_S", "float", 30, "lifecycle",
        "graceful-drain budget on SIGTERM before hard stop")
declare("TPU_ENGINE_MAX_RESTARTS", "int", 3, "lifecycle",
        "supervisor restart budget before the pod fails")
declare("TPU_ENGINE_RESTART_BACKOFF_S", "float", 0.05, "lifecycle",
        "base backoff between supervised engine restarts")
declare("TPU_RESTART_REPLAY_MAX", "int", 64, "lifecycle",
        "max in-flight streams the restart replays; 0 disables replay")
declare("TPU_RESTART_REPLAY_TOKENS", "int", 65536, "lifecycle",
        "max total tokens a restart replay may regenerate before "
        "fail-safe erroring")
declare("TPU_WARM_BUCKETS", "bool", 1, "lifecycle",
        "0 skips prefill-bucket warm-up compilation at startup")
declare("TPU_WARM_SNAPSHOT", "bool", 1, "lifecycle",
        "0 disables warm-state snapshot save/restore across restarts")

# -- observability ----------------------------------------------------------

declare("TPU_TRACE", "bool", 1, "observability",
        "0 disables per-request timeline tracing")
declare("TPU_TRACE_KEEP", "int", 256, "observability",
        "finished request timelines kept for /debug/trace")
declare("TPU_FLIGHT_EVENTS", "int", 512, "observability",
        "flight-recorder ring size in structured events")
declare("TPU_ACCOUNTING", "bool", 1, "observability",
        "0 disables TPU utilization/goodput accounting")
declare("TPU_ACCOUNTING_RING_S", "int", 120, "observability",
        "seconds of per-second aggregates /debug/utilization keeps")
declare("TPU_PEAK_FLOPS", "float", None, "observability",
        "per-chip peak FLOP/s override for MFU; unset = detected from "
        "the device kind")

# -- faults -----------------------------------------------------------------

declare("TPU_FAULTS", "str", None, "faults",
        "fault-injection arming grammar, e.g. "
        "engine.step=fail:once,kube.request=delay:10ms")

# -- operator ---------------------------------------------------------------

declare("TPU_SERVER_IMAGE", "str", None, "operator",
        "model-server image the operator deploys; unset = built-in "
        "release image")

# -- autoscale --------------------------------------------------------------

declare("TPU_AUTOSCALE", "bool", 0, "autoscale",
        "1 enables the closed-loop replica autoscaler")
declare("TPU_AUTOSCALE_MIN", "int", 1, "autoscale",
        "replica floor; 0 allows scale-to-zero")
declare("TPU_AUTOSCALE_MAX", "int", 8, "autoscale",
        "replica ceiling")
declare("TPU_AUTOSCALE_TARGET_OCCUPANCY", "float", 0.75, "autoscale",
        "sustained slot occupancy above this scales up")
declare("TPU_AUTOSCALE_LOW_OCCUPANCY", "float", 0.30, "autoscale",
        "sustained occupancy at/below this with an empty queue scales "
        "down")
declare("TPU_AUTOSCALE_UP_COOLDOWN_S", "float", 30, "autoscale",
        "minimum gap between up moves")
declare("TPU_AUTOSCALE_DOWN_COOLDOWN_S", "float", 120, "autoscale",
        "minimum gap between down moves")
declare("TPU_AUTOSCALE_UP_STREAK", "int", 2, "autoscale",
        "consecutive hot observations required to scale up")
declare("TPU_AUTOSCALE_DOWN_STREAK", "int", 3, "autoscale",
        "consecutive cold observations required to scale down")
declare("TPU_AUTOSCALE_IDLE_TTL_S", "float", 0, "autoscale",
        "idle seconds before scale-to-zero; 0 = never")
declare("TPU_AUTOSCALE_BACKLOG_TOKENS", "int", 4096, "autoscale",
        "queued prompt tokens per replica that force an up move")
declare("TPU_AUTOSCALE_STALE_S", "float", 30, "autoscale",
        "metrics older than this are ignored by the loop")
declare("TPU_AUTOSCALE_FLAP_WINDOW_S", "float", 300, "autoscale",
        "window for flap detection")
declare("TPU_AUTOSCALE_FLAP_MAX_FLIPS", "int", 4, "autoscale",
        "direction changes inside the window that freeze the loop")
declare("TPU_AUTOSCALE_FLAP_HOLD_S", "float", 180, "autoscale",
        "freeze duration after flap detection")
declare("TPU_REMEDIATION_BACKOFF_S", "float", 10, "autoscale",
        "base backoff between replica remediation deletes")
declare("TPU_REMEDIATION_BACKOFF_CAP_S", "float", 300, "autoscale",
        "remediation backoff ceiling")

# -- gateway ----------------------------------------------------------------

declare("TPU_GATEWAY_PORT", "int", 11434, "gateway",
        "listen port of the fleet gateway process")
declare("TPU_GATEWAY_REPLICAS", "str", None, "gateway",
        "comma-separated replica base URLs (static discovery); unset = "
        "discover via TPU_GATEWAY_SELECTOR")
declare("TPU_GATEWAY_SELECTOR", "str", None, "gateway",
        "namespace/app pod selector for in-cluster replica discovery; "
        "operator-injected")
declare("TPU_GATEWAY_HASH_CHUNK", "int", 256, "gateway",
        "prompt characters per page-aligned prefix-hash chunk in the "
        "routing law")
declare("TPU_GATEWAY_PROBE", "bool", 1, "gateway",
        "0 skips the /api/prefix_probe scatter on an affinity miss "
        "(route straight to least-loaded)")
declare("TPU_GATEWAY_EJECT_FAILURES", "int", 3, "gateway",
        "consecutive request/scrape failures that open a replica's "
        "circuit")
declare("TPU_GATEWAY_EJECT_S", "float", 10, "gateway",
        "seconds a replica's circuit stays open before half-open "
        "admits one probe request")
declare("TPU_GATEWAY_SLOW_SCRAPE_MS", "float", 1000, "gateway",
        "scrape latency above this counts as a health failure")
declare("TPU_GATEWAY_SCRAPE_S", "float", 2, "gateway",
        "period of the gateway's background health/load scrape loop")
declare("TPU_GATEWAY_HEDGE_MS", "float", 0, "gateway",
        "first-byte wait before a queued-but-unstarted request fails "
        "over to another replica; 0 = only on replica death")
declare("TPU_GATEWAY_JOURNAL", "int", 512, "gateway",
        "completed-request journal entries kept for failover replay "
        "bookkeeping")
declare("TPU_GATEWAY_PERSIST", "str", None, "gateway",
        "crash-recovery journal: unset/0 disables, 1 writes the "
        "append-log to <TPU_WEIGHT_CACHE>/gateway-journal.ndjson, "
        "anything else is an explicit log path")
declare("TPU_GATEWAY_PERSIST_FLUSH_MS", "float", 50, "gateway",
        "persist-log fsync batching window in ms; a crash loses at most "
        "this much journal progress (downgrading a resume to the "
        "exactly-once error frame)")

# -- disaggregated prefill/decode pools (ISSUE 20) --------------------------

declare("TPU_DISAGG", "enum", "auto", "disagg",
        "gateway disaggregation gate: auto (default) hands off whenever "
        "both a prefill and a decode replica are routable, 0 disables "
        "routing-level disaggregation even if pool Deployments exist")
declare("TPU_DISAGG_ROLE", "enum", None, "disagg",
        "this replica's pool, set by the operator on pool Deployments "
        "(prefill|decode); unset = unified replica. Informational on "
        "the server (surfaced in /api/ps lifecycle) — routing is the "
        "gateway's job")
declare("TPU_DISAGG_HANDOFF_TIMEOUT_S", "float", 30, "disagg",
        "bound on one prefill->decode handoff leg (the gateway's "
        "/api/kv_import call and the decode replica's pull from the "
        "prefill replica); expiry downgrades the handoff to journal "
        "replay on the decode pool — never a client error")
declare("TPU_DISAGG_TRANSFER_MB_S", "float", 0, "disagg",
        "KV page transfer pacing in MB/s applied on the export side's "
        "chunked writes; 0 = unthrottled (page copies already ride the "
        "host arena, not HBM bandwidth)")
declare("TPU_DISAGG_PREFILL_MIN", "int", 1, "disagg",
        "prefill pool autoscale floor when spec.disaggregate.prefill "
        "sets no minReplicas")
declare("TPU_DISAGG_PREFILL_MAX", "int", 4, "disagg",
        "prefill pool autoscale ceiling when spec.disaggregate.prefill "
        "sets no maxReplicas (prefill scales on queued backlog tokens)")
declare("TPU_DISAGG_DECODE_MIN", "int", 1, "disagg",
        "decode pool autoscale floor when spec.disaggregate.decode "
        "sets no minReplicas")
declare("TPU_DISAGG_DECODE_MAX", "int", 8, "disagg",
        "decode pool autoscale ceiling when spec.disaggregate.decode "
        "sets no maxReplicas (decode scales on slot occupancy)")


def _main() -> None:
    by_sub: Dict[str, List[Knob]] = {}
    for k in all_knobs():
        by_sub.setdefault(k.subsystem, []).append(k)
    for sub in sorted(by_sub):
        print(f"[{sub}]")
        for k in by_sub[sub]:
            d = "unset" if k.default is None else k.default
            print(f"  {k.name:34s} {k.type:6s} default={d!s:8s} {k.doc}")
    print(f"{len(REGISTRY)} knobs")


if __name__ == "__main__":
    _main()
