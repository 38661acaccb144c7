"""Container factories for model-serving pods.

The reference builds two containers (/root/reference/pkg/model/pod.go):
`NewOllamaServerContainer` — the `ollama/ollama` image running `serve` with
the blob PVC mounted, /api/tags probes with FailureThreshold 2500 — and
`NewOllamaPullerContainer` — `ollama pull <image>` pointed at the store
Service. Same roles here, but the server image is the TPU runtime
(JAX/XLA engine + Ollama-compatible HTTP front) and the server container
additionally carries TPU resources/topology selectors and the
jax.distributed env for multi-host slices (no reference analog —
SURVEY.md §7 hard part 3).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from .types import TpuPlacement

# Default runtime image; pinned per-release by kustomize exactly like the
# reference pins ghcr.io/nekomeowww/ollama-operator
# (/root/reference/config/manager/kustomization.yaml:5-8).
SERVER_BASE_IMAGE = "ghcr.io/ollama-operator-tpu/tpu-runtime"

STORE_MOUNT = "/root/.ollama"
CACHE_SUBPATH = "tpu-cache"  # transcoded-weights cache inside the same PVC
VOLUME_NAME = "image-storage"
PORT = 11434

# The reference tolerates hours of model loading before probes fail
# (pod.go:50,62: FailureThreshold 2500 × 10s). Transcode+shard of a 70B is
# minutes, not hours, but a cold pull still dominates — keep the window.
PROBE_FAILURE_THRESHOLD = 2500

# Graceful-termination geometry.  On SIGTERM the server drains: /readyz
# flips to 503, new submits shed with Retry-After, running streams finish
# within TPU_DRAIN_TIMEOUT_S (runtime/scheduler.py drain()).  The preStop
# sleep holds the container alive while the endpoints controller
# deprograms the pod from the Service, so no connection is routed to a
# server that is already draining; the grace period must cover
# preStop + drain + engine teardown or the kubelet SIGKILLs mid-drain.
PRESTOP_SLEEP_S = 5
DRAIN_TIMEOUT_S = 30
TERMINATION_GRACE_S = PRESTOP_SLEEP_S + DRAIN_TIMEOUT_S + 25


def _probe(path: str, initial_delay: int = 5,
           failure_threshold: int = PROBE_FAILURE_THRESHOLD
           ) -> Dict[str, Any]:
    return {
        "httpGet": {"path": path, "port": PORT},
        "initialDelaySeconds": initial_delay,
        "periodSeconds": 10,
        "failureThreshold": failure_threshold,
    }


def new_server_container(
    *,
    read_only: bool,
    image: str = SERVER_BASE_IMAGE,
    model: Optional[str] = None,
    store_only: bool = False,
    placement: Optional[TpuPlacement] = None,
    context_length: Optional[int] = None,
    quantization: Optional[str] = None,
    tp: int = 0,
    extra_env: Optional[List[Dict[str, Any]]] = None,
) -> Dict[str, Any]:
    """The serving container (pod.go:14-66 equivalent).

    read_only mirrors the reference's store-vs-model mount split: the store
    StatefulSet mounts the PVC RW (image_store.go:169), model pods RO
    (model.go:97). The transcoded-weights cache needs RW, so model pods get
    a separate subPath mount for it (cache writes are content-addressed and
    concurrent-safe, gguf/store.py).
    """
    env = [
        {"name": "OLLAMA_HOST_BIND", "value": "0.0.0.0"},
        {"name": "OLLAMA_MODELS", "value": f"{STORE_MOUNT}/models"},
        {"name": "TPU_WEIGHT_CACHE", "value": f"{STORE_MOUNT}/{CACHE_SUBPATH}"},
    ]
    if store_only:
        env.append({"name": "TPU_STORE_ONLY", "value": "1"})
    if model:
        env.append({"name": "TPU_PRELOAD_MODEL", "value": model})
    if context_length:
        env.append({"name": "TPU_MAX_SEQ_LEN", "value": str(context_length)})
    if quantization:
        # CRD quantization -> the server's weight-dtype knob (CRD spells
        # bf16, the server bfloat16); int8/int4 also turn on the quantized
        # KV cache (the pairing every quantized config wants: half/quarter
        # the weight AND half the cache traffic)
        dtype = {"bf16": "bfloat16"}.get(quantization, quantization)
        env.append({"name": "TPU_ENGINE_DTYPE", "value": dtype})
        if quantization in ("int8", "int4"):
            env.append({"name": "TPU_KV_DTYPE", "value": "int8"})
    if placement is not None:
        # a TPU pod that silently fell back to CPU must crash, not serve
        # at 1/100th speed (server __main__ enforces this)
        env.append({"name": "TPU_EXPECT_PLATFORM", "value": "tpu"})
    if tp:
        env.append({"name": "TPU_TENSOR_PARALLEL", "value": str(tp)})
    # keep the server's drain window in lockstep with the pod's
    # terminationGracePeriodSeconds (workload._pod_template)
    env.append({"name": "TPU_DRAIN_TIMEOUT_S", "value": str(DRAIN_TIMEOUT_S)})
    if not store_only:
        # scale-to-zero fast cold-start: the AOT warm-bucket executable
        # cache is snapshotted into the shared cache volume at drain time
        # and restored on wake (runtime/service.py warm snapshot; the
        # cache subpath is the same PVC the transcoded weights live on)
        env.append({"name": "TPU_WARM_SNAPSHOT", "value": "1"})
        # the persistent XLA compile cache rides the same RW cache
        # subpath, so a restarted pod skips the warm-up compiles; the
        # server takes the place from JAX's own variable and sets none in
        # code (runtime/compile_cache.py)
        env.append({"name": "JAX_COMPILATION_CACHE_DIR",
                    "value": f"{STORE_MOUNT}/{CACHE_SUBPATH}/xla-cache"})
    env.extend(extra_env or [])

    mounts = [{
        "name": VOLUME_NAME,
        "mountPath": STORE_MOUNT,
        "readOnly": not store_only and read_only,
    }]
    if read_only and not store_only:
        # RW cache mount layered over the RO blob mount (same PVC).
        mounts.append({
            "name": VOLUME_NAME,
            "mountPath": f"{STORE_MOUNT}/{CACHE_SUBPATH}",
            "subPath": CACHE_SUBPATH,
            "readOnly": False,
        })

    container: Dict[str, Any] = {
        "name": "server",
        "image": image,
        "args": ["serve"],
        "env": env,
        "ports": [{"name": "http", "containerPort": PORT, "protocol": "TCP"}],
        "volumeMounts": mounts,
        # startup gates liveness through the hours-long pull/transcode
        # window (the reference piles its 2500-failure tolerance onto both
        # probes, pod.go:50,62); once serving, a wedged engine should be
        # restarted in ~30s, not 7h, so liveness itself fails fast.
        "startupProbe": _probe("/healthz"),
        "readinessProbe": _probe("/api/tags"),
        "livenessProbe": _probe("/livez", failure_threshold=3),
        # preStop runs before SIGTERM: the sleep keeps the pod serving
        # while kube-proxy/endpoints converge on its removal, then the
        # server's own SIGTERM handler drains (readyz 503 + shed +
        # stream-preserving finish).  /livez stays ok while draining so
        # the kubelet never restarts a pod mid-drain.
        "lifecycle": {
            "preStop": {
                "exec": {"command": ["sh", "-c",
                                     f"sleep {PRESTOP_SLEEP_S}"]},
            },
        },
    }
    if placement is not None:
        container["resources"] = {
            "requests": {"google.com/tpu": str(placement.chips_per_host)},
            "limits": {"google.com/tpu": str(placement.chips_per_host)},
        }
    return container


def new_gateway_container(
    *,
    namespace: str,
    app: str,
    image: str = SERVER_BASE_IMAGE,
) -> Dict[str, Any]:
    """The fleet-gateway container (operator/gateway.py): cache-aware
    router + circuit breaker + stream-failover front for a replicated
    Model. Runs the same runtime image (the gateway is stdlib-only, the
    image has it), discovers replicas via the pod label selector, and
    needs no TPU — it schedules anywhere.

    Crash recovery: the request journal + affinity table persist to an
    append-log on the shared weight-cache volume (TPU_GATEWAY_PERSIST),
    so a replacement gateway pod restores in-flight replayable streams
    for reconnecting clients. SIGTERM triggers the gateway's own
    begin_drain (mirroring the server drain contract); the preStop sleep
    covers Service endpoint deprogramming exactly as for server pods."""
    return {
        "name": "gateway",
        "image": image,
        "command": ["python", "-m", "ollama_operator_tpu.operator.gateway"],
        "env": [
            {"name": "TPU_GATEWAY_SELECTOR", "value": f"{namespace}/{app}"},
            {"name": "TPU_GATEWAY_PORT", "value": str(PORT)},
            {"name": "TPU_WEIGHT_CACHE",
             "value": f"{STORE_MOUNT}/{CACHE_SUBPATH}"},
            # "1" = journal to <TPU_WEIGHT_CACHE>/gateway-journal.ndjson
            {"name": "TPU_GATEWAY_PERSIST", "value": "1"},
            {"name": "TPU_DRAIN_TIMEOUT_S", "value": str(DRAIN_TIMEOUT_S)},
        ],
        "ports": [{"name": "http", "containerPort": PORT,
                   "protocol": "TCP"}],
        "volumeMounts": [{
            # only the RW cache subpath: the gateway needs a durable home
            # for its journal, not the model blobs
            "name": VOLUME_NAME,
            "mountPath": f"{STORE_MOUNT}/{CACHE_SUBPATH}",
            "subPath": CACHE_SUBPATH,
            "readOnly": False,
        }],
        "startupProbe": _probe("/healthz", failure_threshold=30),
        # ready iff >=1 replica is routable: an all-ejected fleet drops
        # out of the Service instead of 503ing every request
        "readinessProbe": _probe("/readyz", failure_threshold=3),
        "livenessProbe": _probe("/healthz", failure_threshold=3),
        "lifecycle": {
            "preStop": {
                "exec": {"command": ["sh", "-c",
                                     f"sleep {PRESTOP_SLEEP_S}"]},
            },
        },
    }


def new_puller_container(
    *,
    image: str,
    namespace: str,
    server_image: str = SERVER_BASE_IMAGE,
) -> Dict[str, Any]:
    """Init container pulling through the store (pod.go:68-83 equivalent):
    OLLAMA_HOST points at the store Service, so the *store* downloads into
    the shared PVC and every model pod on the cluster reuses the blobs."""
    from .workload import IMAGE_STORE_SERVICE
    return {
        "name": "ollama-image-pull",
        "image": server_image,
        "args": ["pull", image],
        "env": [{
            "name": "OLLAMA_HOST",
            "value": f"{IMAGE_STORE_SERVICE}.{namespace}",
        }],
    }


def multihost_env(headless_service: str, namespace: str, hosts: int,
                  chips_per_host: int) -> List[Dict[str, Any]]:
    """jax.distributed env for a multi-host slice StatefulSet.

    Pod ordinal = process index (parsed from the pod hostname by
    parallel/distributed.py), pod-0's stable DNS name = coordinator.
    The reference has no analog — its replicas are independent servers
    (SURVEY.md §2.3); this is what makes one *sharded model* span hosts.
    """
    return [
        {"name": "TPU_DIST_HOSTS", "value": str(hosts)},
        {"name": "TPU_DIST_CHIPS_PER_HOST", "value": str(chips_per_host)},
        {"name": "TPU_DIST_COORDINATOR",
         "value": f"$(TPU_DIST_STS_NAME)-0.{headless_service}"
                  f".{namespace}.svc:8476"},
        # leader→follower serving control stream (runtime/follower.py):
        # process 0 broadcasts load/engine calls here so the whole slice
        # dispatches identical SPMD programs
        {"name": "TPU_DIST_CONTROL",
         "value": f"$(TPU_DIST_STS_NAME)-0.{headless_service}"
                  f".{namespace}.svc:8477"},
        {"name": "TPU_DIST_POD_NAME",
         "valueFrom": {"fieldRef": {"fieldPath": "metadata.name"}}},
    ]
