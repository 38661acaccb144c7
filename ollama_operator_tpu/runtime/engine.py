"""The serving engine: jitted prefill/decode over a slot-based KV cache.

This (plus scheduler.py) is the TPU-native replacement for llama.cpp's
server loop that the reference delegates to the ollama image
(/root/reference/pkg/model/pod.go:14-66, `ollama serve`). Design:

- **Slots**: a fixed decode batch of ``max_slots`` sequences. Every decode
  step advances all slots in ONE compiled XLA program (continuous batching —
  new requests are prefilled into free slots while others keep decoding).
- **Static shapes**: prefill lengths are padded to power-of-two buckets, so
  the number of compiled programs is O(log max_seq_len), not O(requests).
- **Donation**: KV caches and per-slot state are donated into each step, so
  XLA updates them in place in HBM — no cache copies per token.
- **Sharding**: params are TP-sharded (parallel/sharding.py), caches sharded
  [L, B@dp, KvH@tp, S, hd] (head-first so the pallas kernels read (S, hd)
  tiles directly); the same code runs single-chip (trivial mesh) or over a
  v5e slice.
- All sampling is on-device (ops/sampling.py); the only per-step
  host↔device traffic is the sampled token ids [B] coming back for
  streaming/stop handling.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import sys
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models import decoder
from ..ops import sampling
from ..ops.attention import record_kernels
from ..ops.quant import resolve_mm_kernels
from .faults import FAULTS
from .trace import FLIGHT, device_scope, span
from ..parallel.sharding import (kv_cache_pspec, params_sharding_tree,
                                 resolve_moe_impl)
from ..server.metrics import (GLOBAL as METRICS, seed_expert_tokens,
                              seed_index_positions)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 8
    max_seq_len: int = 2048
    # jnp.bfloat16 / jnp.float32, or jnp.int8 for the quantized KV cache
    # (ops/quant_cache.py: int8 entries + per-(position, head) f32 scales —
    # half the decode cache traffic, double the context per chip)
    cache_dtype: Any = jnp.bfloat16
    min_prefill_bucket: int = 64
    # penalty window CAPACITY (Ollama repeat_last_n default): repeat/
    # presence/frequency penalties see only the last N tokens, maintained
    # as a device-side ring buffer. The ring is statically sized at this
    # engine max; each request's own repeat_last_n (SlotOptions) selects
    # its effective window ≤ this via a per-slot modulus — no recompile.
    repeat_last_n: int = 64
    # decode steps per host round-trip: a lax.scan of this many steps runs
    # as ONE device program, so dispatch/sync latency (nonzero
    # everywhere) amortises across the chunk.
    # Streaming granularity and admission latency grow with it. 0 = let
    # resolve_serving_defaults pick per backend (32 on TPU — the measured
    # serving config, BASELINE.md r3/r4 — 8 elsewhere); direct engine
    # constructions use the explicit value.
    decode_chunk: int = 8
    # paged KV cache (runtime/paged.py + ops/pallas/paged.py): slots share
    # a physical page pool instead of each reserving max_seq_len — HBM
    # scales with live tokens, so max_slots can be 32+ on one chip
    # (SURVEY.md §7 hard-part 2). Meshes: single-device, tp, dp, dp×tp
    # (under dp the pool shards into per-shard sub-pools); sp keeps the
    # dense sequence-sharded cache. The SERVER passes None = decide per
    # model at load (resolve_paged_default); direct engine constructions
    # default off.
    paged: Optional[bool] = False
    # 0 = resolve per backend when paged (128 on TPU — the round-5
    # page-size ladder measured +10.5% over 64 at B=32 and −1.5% at 256;
    # fewer, larger page DMAs amortize the serialized per-page walk);
    # direct engine constructions use the explicit value
    page_size: int = 64
    # data pages in the pool (excl. the trash page); None = the dense
    # equivalent max_slots * max_seq_len / page_size — same HBM ceiling,
    # but shared, so mixed-length batches fit far more concurrency
    n_pages: Optional[int] = None


def resolve_serving_defaults(ecfg: "EngineConfig", cfg: ModelConfig,
                             mesh) -> "EngineConfig":
    """Resolve the server's tri-state knobs into a concrete EngineConfig.

    - ``paged=None`` → resolve_paged_default (GQA on TPU pages, MHA/MoE/
      CPU stay dense; explicit True/False passes through).
    - ``max_slots=0`` → 64 for GQA paged on TPU (r5 ladder: 3902 tok/s
      vs 2848 at 32), 32 for other paged, 8 dense.
    - ``decode_chunk=0`` → 32 on TPU, 8 elsewhere (the config every
      BASELINE.md headline was measured at; round-1's chunk-8 default
      served the 64–116 tok/s class on the same chip).
    - ``page_size=0`` → 128 for GQA paged on TPU (r5 page-size ladder:
      +10.5% over 64 at B=32, 256 regresses; MHA measured −2% so it
      keeps 64), 64 elsewhere.
    - When paged resolved on with auto slots and no explicit pool size,
      the pool is byte-capped: the 32-slot default shares a dense-8
      HBM-equivalent pool (footprint of the old dense default), the
      64-slot GQA default a dense-24 one — the measured minimum that
      holds 64 mixed slots at design load without running dry (r5
      window 3/4). Full-length overload preempts/requeues instead of
      OOMing at load. The pool stores heads padded to the 128-lane tile,
      so for hd<128 models the auto page count shrinks by hd/hd_pool —
      the BYTE ceiling is what's preserved, not the token count.
    """
    import os

    import jax
    on_tpu = jax.default_backend() == "tpu"
    chunk = ecfg.decode_chunk or resolve_decode_chunk_default()
    # prefill-bucket floor: smaller buckets mean finer chunked-prefill
    # pieces (TPU_PREFILL_CHUNK rounds up to a bucket) at the cost of a
    # few more compiled prefill programs — O(log seq) either way. Mostly
    # useful on small-context models where the 64 default leaves no room
    # for a multi-piece admission.
    minb = (int(os.environ.get("TPU_MIN_PREFILL_BUCKET", "0") or 0)
            or ecfg.min_prefill_bucket)
    # page_size 128 only pays for GQA (few kv heads → 16 KB pages at 64;
    # doubling them bought +10.5% in the r5 ladder). An MHA page is
    # already KvH× larger — the same window measured ps=128 at −2%
    # (noise) on phi, so MHA keeps 64.
    gqa = cfg.n_kv_heads < cfg.n_heads
    if ecfg.paged is not None and ecfg.max_slots != 0:
        ps = ecfg.page_size or (128 if on_tpu and ecfg.paged and gqa
                                else 64)
        return dataclasses.replace(ecfg, decode_chunk=chunk, page_size=ps,
                                   min_prefill_bucket=minb)
    paged = (resolve_paged_default(cfg, mesh) if ecfg.paged is None
             else ecfg.paged)
    ps = ecfg.page_size or (128 if on_tpu and paged and gqa else 64)
    # GQA pages at 64 slots on TPU (r5 ladder: 3902 tok/s at 64 vs 2848
    # at 32, TTFT p50 ~112 ms — aggregate throughput is the serving
    # metric); MHA keeps 32 (its paged step is ~3x GQA's, 64 would double
    # streaming latency on an unmeasured combination)
    slots = ecfg.max_slots or ((64 if on_tpu and gqa else 32)
                               if paged else
                               _recurrent_slots(cfg) if on_tpu
                               and cfg.layer_kinds else 8)
    n_pages = ecfg.n_pages
    if paged and n_pages is None and ecfg.max_slots == 0:
        serve_seq = min(ecfg.max_seq_len, cfg.max_seq_len)
        hd_pool = -(-cfg.head_dim // 128) * 128
        # pool byte ceiling: dense-8 equivalent for the 32-slot default,
        # dense-24 for the 64-slot GQA default — measured, not guessed:
        # the r5 window-3 capture showed 64 mixed slots at design load
        # (live ~210/slot) round up to ~160 ps-128 pages, so a dense-16
        # cap (128 pages) ran the pool dry mid-capture; 24×seq holds the
        # design load with ~15% slack (window-4 validation capture)
        ceil_slots = 24 if slots >= 64 else 8
        n_pages = max(1, (ceil_slots * serve_seq) * cfg.head_dim
                      // hd_pool // ps)
    return dataclasses.replace(ecfg, paged=paged, max_slots=slots,
                               n_pages=n_pages, decode_chunk=chunk,
                               page_size=ps, min_prefill_bucket=minb)


def _recurrent_slots(cfg: ModelConfig) -> int:
    """Slots of a hybrid stack's contiguous cache on the TPU, from the
    model alone. With experts, its decode step is bound by the weights of
    the experts it holds, so the batch is what fills them: the smallest
    power of two, 64 at most, that gives every expert of the router four
    tokens a step (8 slots x 10 picks over granite's 72 is 1.1 a step: a
    latency test, not a serving batch). Without experts a step reads every
    weight whatever the batch, and the stack wants the 32 slots the paged
    default gives an MHA model (``resolve_serving_defaults``: "MHA keeps
    32"). Either way what a slot carries beside its full-length keys and
    values stays under an eighth of a v5e chip's 16 GB: the recurrent
    state (granite's 38.7 MB a slot against 8 MB of int8 keys and values
    at 4096 positions; a delta stack's 21.2 MB over nine layers; a
    short-convolution stack's 196 KB) and the window layers' rings
    (K-EXAONE's 1.6 MB). The full-length rows are not counted: they grow
    with the context the server is started at."""
    want = 4 * cfg.n_experts / cfg.n_experts_used if cfg.n_experts else 32
    slots = 8
    while slots < min(want, 64):
        slots *= 2
    carried = cfg.ssm_state_bytes + cfg.window_ring_bytes
    while slots > 8 and slots * carried > (2 << 30):
        slots //= 2
    return slots


def resolve_paged_default(cfg: ModelConfig, mesh) -> bool:
    """The serving default for an unset paged flag, per model and mesh.

    What the ledger's cells decide: GQA and MHA models on one v5e chip
    page. Both dense cells run the paged pool at this default and the
    ``paged_v3`` kernel (ops/pallas/paged.py): starcoder2-3b (GQA, 2 kv
    heads, 64 slots) at 2,629.0 and phi-2 (MHA, 32 kv heads, 32 slots) at
    1,457.2 ``out_tok_s`` (ledger, PR 30). Neither cell has a dense-cache
    twin, so the ledger does not say by how much paging wins.
    What no cell decides ("unmeasured"): MoE stays dense; a stack with
    recurrent layers has no paged form (``Engine`` refuses one); meshes
    page only where the pool can be sharded (tp, or dp with a valid
    dp-manual layout; not sp) and none has a cell. Off the TPU backend the
    default is dense: a CPU dev/kind pod pays per-step compute for every
    slot of a 32-slot batch. An explicit --paged / TPU_PAGED=0|1 always
    wins."""
    import jax
    if jax.default_backend() != "tpu":
        return False
    if cfg.n_experts or cfg.layer_kinds:
        return False
    if mesh is None:
        return True
    shape = dict(mesh.shape)
    if any(sz > 1 for ax, sz in shape.items() if ax not in ("tp", "dp")):
        return False
    if shape.get("dp", 1) > 1:
        from ..models.decoder import _paged_dp_axes
        if _paged_dp_axes(cfg, mesh, cfg.n_kv_heads) is None:
            return False
    return True


def resolve_decode_chunk_default() -> int:
    """Serving decode_chunk when the CR/env/flag leaves it unset.

    Data-driven (BASELINE.md, v5e, as the dev chip was reached then): the
    dispatch+sync round-trip was ~10 ms, so chunk 8 left >50% of the step
    budget in host turnaround; every headline capture since r2 ran chunk 32 (phi
    dense-8 ~570 tok/s vs 64–116 at r1's chunk 8), with chunk 64 only ~3%
    beyond it (589.2 — not worth 2× chunkier streaming by default; it
    remains the explicit-throughput knob, TPU_DECODE_CHUNK=64). CPU pods
    keep 8: per-step compute dominates there, and kind/e2e latency would
    otherwise balloon."""
    import jax
    return 32 if jax.default_backend() == "tpu" else 8


def resolve_engine_dtype(cfg: ModelConfig, backend: str) -> str:
    """Weight serving dtype when neither CR ``spec.quantization`` nor
    --dtype/TPU_ENGINE_DTYPE picked one.

    The zero-config contract (the reference's sample CR serves usably with
    no tuning fields, /root/reference/config/samples/ollama_v1_model.yaml)
    must land in the measured headline band, not the bf16 config nothing
    benches: on a 16 GB v5e chip, int8 weight-only quantization is the
    measured serving config ≤4B (phi int8 ~570 tok/s dense-8; bf16 halves
    that by doubling streamed bytes), and 7B+ needs int4 to leave HBM room
    for the KV pool (mistral-7B int4 = the r4 flagship; bf16 7B does not
    fit at all). MoE expert stacks serve dense bf16 (quantized expert
    matmuls are an unmeasured path), and so does every hybrid stack
    (``cfg.layer_kinds``), with experts or without: quantized matmuls of a
    recurrent mixer are as unmeasured, ``ops/quant.QUANT_LAYER_KEYS`` names
    none of a mixer's leaves (a "quantized" hybrid model would be half
    bfloat16 by accident), and a hybrid stack of 4e9 parameters would
    otherwise land on int4, whose warm plan has never compiled on the chip
    (ROADMAP R1). CPU serves f32 — XLA's CPU thunk
    runtime has no bf16 dots and the quantized matmuls are pallas/TPU
    paths. An explicit spec/env/flag always wins (callers only consult
    this when theirs is unset)."""
    if backend != "tpu":
        return "float32"
    if cfg.n_experts or cfg.layer_kinds:
        return "bfloat16"
    return "int4" if cfg.n_params >= 4e9 else "int8"


def resolve_kv_dtype_default(backend: str) -> str:
    """KV-cache dtype default: int8 on TPU (half the decode cache traffic,
    double the context per chip — every BASELINE.md capture since r2 runs
    it; parity suite covers the quantized cache), f32 on CPU (no bf16
    support in the thunk runtime, and CPU pods are dev/e2e anyway)."""
    return "int8" if backend == "tpu" else "float32"


CACHE_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                "int8": jnp.int8,
                # "int4" stays a STRING sentinel: there is no 4-bit storage
                # array — the pool holds nibble-packed int8 ({"q4", "s"},
                # ops/quant_cache.py) and only the paged cache supports it
                "int4": "int4"}


def resolve_cache_dtype(name_or_dtype) -> Any:
    """Normalise a cache dtype given as a name or jnp dtype; rejects
    anything outside the supported set (a stray dense-int8 cache would
    silently truncate K/V to ±1). int4 resolves to the string sentinel
    "int4" (nibble-packed storage has no jnp dtype of its own)."""
    if isinstance(name_or_dtype, str):
        if name_or_dtype not in CACHE_DTYPES:
            raise ValueError(f"cache dtype {name_or_dtype!r}; expected one "
                             f"of {sorted(CACHE_DTYPES)}")
        return CACHE_DTYPES[name_or_dtype]
    dt = jnp.dtype(name_or_dtype)
    table = {jnp.dtype(v): v for v in CACHE_DTYPES.values()
             if not isinstance(v, str)}
    assert dt in table, f"unsupported cache dtype {dt}"
    return table[dt]


def unpack_mask(mask_bits, V: int):
    """Packed [..., ceil(V/32)] uint32 → bool [..., V] allowed-token mask.

    The grammar-constrained decode path (ops/constrain.py): the host uploads
    one packed row per slot and the decode program unpacks it on device —
    32× less host→device traffic than a dense bool mask, and no logits
    download (sampling stays on device)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (mask_bits[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*mask_bits.shape[:-1], -1)[..., :V] != 0


def prefill_buckets(max_seq_len: int, min_bucket: int):
    b, out = min_bucket, []
    while b < max_seq_len:
        out.append(b)
        b *= 2
    out.append(max_seq_len)
    return out


@dataclasses.dataclass
class SlotOptions:
    """Host-side per-request sampling options (Ollama API options subset)."""
    temperature: float = 0.8
    top_k: int = 40
    top_p: float = 0.9
    min_p: float = 0.0
    typical_p: float = 1.0
    repeat_penalty: float = 1.1
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # mirostat: 0 off, 1/2 replace the static filters with the adaptive
    # surprise truncation (per-slot mu state lives in Engine.mu)
    mirostat: int = 0
    mirostat_tau: float = 5.0
    mirostat_eta: float = 0.1
    seed: int = -1
    # penalty window for THIS request: 0 disables the window, -1 means
    # "engine max"; values above the engine's repeat_last_n capacity clamp
    repeat_last_n: int = 64


class DecodeHandle:
    """An in-flight chunked decode dispatch: the device program is
    launched and the slot state already advanced, but the sampled tokens
    are still device-side futures. ``wait()`` materialises them ([n, B]).

    The point is JAX async dispatch: the caller can launch dispatch N+1
    (or an admission piece) BEFORE waiting on dispatch N, so host-side
    fan-out/detokenise work overlaps device compute. Donated-state data
    dependencies keep device programs ordered regardless of when (or
    whether) wait() runs.

    ``epoch`` is the paged-mode dispatch epoch this launch was stamped
    with (0 for dense engines). Once wait() returns, the program is
    materialised and the caller may pass the epoch back into the next
    ``decode_n_launch(retire=...)`` to unfence pages quarantined up to
    it — wait() itself must NOT retire, because multi-host followers
    replay launches without ever waiting and the free-list order has to
    stay bit-identical across hosts (runtime/paged.py docstring)."""

    __slots__ = ("_engine", "_toks", "_t0", "_out", "epoch", "t_done",
                 "t_begin", "t_queued", "enqueue_s", "sampler", "_load")

    def __init__(self, engine: "Engine", toks, t0: float, epoch: int = 0,
                 sampler: str = "argmax", load=None):
        self._engine = engine
        # a routed model's chunk: [E] picks per expert, still on the device
        self._load = load
        # the costlier sampler any of its steps took (_count_sampler_steps)
        self.sampler = sampler
        self._toks = toks
        self._t0 = t0
        # perf_counter() when the program had been handed to the runtime
        # (the launch's host staging done): from then on the device has
        # it queued; enqueue_s is the engine's clock of the runtime's
        # launch calls at that moment (Engine._enqueue)
        self.t_queued = time.perf_counter()
        self.enqueue_s = engine.enqueue_s
        self._out: Optional[np.ndarray] = None
        self.epoch = epoch
        # perf_counter() when wait() materialised the tokens; with
        # t_launch this makes the async launch→materialize overlap
        # visible to the tracing layer (runtime/trace.py)
        self.t_done: Optional[float] = None
        # the later of the launch and the previous dispatch's t_done: what
        # lies between it and t_done is this dispatch's own time, also
        # when it was launched behind one that still ran (_landed)
        self.t_begin: Optional[float] = None

    @property
    def t_launch(self) -> float:
        """perf_counter() at launch time (set by decode_n_launch)."""
        return self._t0

    def ready(self) -> bool:
        """Whether the device has finished the program, so that wait()
        would not block. Asks the runtime; syncs and fetches nothing."""
        return self._toks is None or self._toks.is_ready()

    def wait(self) -> np.ndarray:
        if self._out is None:
            toks = self._engine._fetch(self._toks)
            self.t_begin, self.t_done = self._engine._landed(
                "decode", self._t0)
            self._out = toks
            self._toks = None
            if self._load is not None:
                # beside the tokens, once a chunk: the program has ended,
                # so this fetch waits for nothing
                self._engine._count_expert_tokens(
                    self._engine._fetch(self._load))
                self._load = None
        return self._out


# tpu_model_admit_dispatch_seconds' label values (AdmitHandle.wait)
_ADMIT_PART = {p: f'{{part="{p}"}}' for p in ("launch", "behind", "run")}
# tpu_model_radix_evicted_pages_total's, by what PageTable.unpin returned
# (Engine._unpin_evicted)
_EVICT_FENCE = {True: '{fence="free"}', False: '{fence="fenced"}'}


def admit_parts(t_launch: float, t_queued: float, t_begin: float,
                t_done: float) -> Tuple[float, float, float]:
    """One admission dispatch by part, from the four instants its handle
    stamps, in seconds: (launch, behind, run). launch: the host's staging
    and the call into the runtime, blocked or not (to t_queued); behind:
    queued on the device behind what was launched before it (to t_begin,
    nothing where its predecessor had landed already); run: what the
    dispatch itself took, by Engine._landed's rule. They add up to
    t_done - t_launch. The stamps are host fetch times, so run also
    holds the time the token lay on the device until the host came for
    it (the first dispatch a pass collects waits for the chunk before
    it to be fetched)."""
    return (t_queued - t_launch, max(t_begin - t_queued, 0.0),
            t_done - max(t_begin, t_queued))


class AdmitHandle:
    """An admission launched and not awaited: what DecodeHandle is to
    ``decode_n``. The prefill program is dispatched and the slot already
    live on the host (``_commit_slot`` ran), so a decode chunk launched
    next carries it; the first sampled token(s) are still on the device.
    ``wait()`` fetches them, one per slot, in the order of ``slots``.
    Followers replay the launch and never wait."""

    __slots__ = ("_engine", "_toks", "_t0", "_out", "kind", "slots",
                 "t_done", "t_begin", "t_queued", "enqueue_s")

    def __init__(self, engine: "Engine", toks, t0: float, kind: str,
                 slots: Sequence[int]):
        self._engine = engine
        self._toks = toks
        self._t0 = t0
        self.t_queued = time.perf_counter()      # as DecodeHandle's
        self.enqueue_s = engine.enqueue_s
        self._out: Optional[List[int]] = None
        # the key of Engine.dispatch_ms this admission reports under
        self.kind = kind
        self.slots = tuple(slots)
        self.t_done: Optional[float] = None
        self.t_begin: Optional[float] = None

    @property
    def t_launch(self) -> float:
        """perf_counter() when the launch began (host staging included)."""
        return self._t0

    def ready(self) -> bool:
        """As DecodeHandle.ready: the prefill has run; nothing is synced."""
        return self._toks is None or self._toks.is_ready()

    def wait(self) -> List[int]:
        if self._out is None:
            toks = self._engine._fetch(self._toks)
            self.t_begin, self.t_done = self._engine._landed(self.kind,
                                                             self._t0)
            # once a dispatch, a batched one too, awaited or launched
            for part, dur in zip(_ADMIT_PART.values(), admit_parts(
                    self._t0, self.t_queued, self.t_begin, self.t_done)):
                METRICS.observe("tpu_model_admit_dispatch_seconds", dur,
                                part)
            self._out = [int(t) for t in toks.reshape(-1)]
            self._toks = None
        return self._out


class Engine:
    """Owns device state and the compiled step functions."""

    def __init__(self, cfg: ModelConfig, params, mesh: Optional[Mesh] = None,
                 ecfg: EngineConfig = EngineConfig()):
        # pallas_call is opaque to GSPMD, but the attention dispatch
        # (ops/attention.py) wraps the kernels in a dp/tp-manual shard_map
        # whenever a >1-device mesh is passed — so real meshes keep the
        # flash kernels (round-1 VERDICT weak #2: the old code forced
        # kernels="xla" here and the tp path served on einsum attention).
        if cfg.n_experts:
            cfg = dataclasses.replace(
                cfg, moe_impl=resolve_moe_impl(cfg, mesh))
        # the quantized matmuls' path hangs on the mesh and the backend,
        # both known here and nowhere below
        cfg = resolve_mm_kernels(cfg, mesh)
        B, S = ecfg.max_slots, min(ecfg.max_seq_len, cfg.max_seq_len)
        if cfg.n_window_layers and cfg.sliding_window > S:
            # a ring is never longer than the context the server was
            # started at: no position a request can reach leaves a window
            # that long, so the shorter ring drops the same keys (none)
            cfg = dataclasses.replace(cfg, sliding_window=S)
        self.cfg = cfg
        self.ecfg = ecfg
        self.mesh = mesh
        # seconds inside the runtime's launch call, all told (_enqueue)
        self.enqueue_s = 0.0
        self.n_slots, self.max_seq = B, S
        # layers that keep keys and values at every position: all, but for
        # a hybrid stack (its window layers keep rings, part of the state)
        L, hd = cfg.n_full_layers, cfg.head_dim
        # a position's two rows in a full layer's cache: a head's keys and
        # values, or latent attention's [latent | rotated key] and the
        # indexer's key (one "head", two widths; without an indexer v_dim
        # is 0 and there is no second tree: v_cache is None)
        KvH, k_dim, v_dim = cfg.cache_row_dims
        V = cfg.vocab_size
        # a hybrid stack's slots carry a state beside their full-length
        # keys and values (models/decoder.py, hybrid section: a recurrent
        # mixer's, or window layers' rings); it rides inside the two cache
        # trees, so every program hands it on with them, and it can be
        # advanced and not cut back
        self.recurrent = bool(cfg.layer_kinds)
        if cfg.n_experts:
            seed_expert_tokens(cfg.n_experts)
        if cfg.kv_latent_dim:
            from .host_cache import host_cache_bytes
            if cfg.index_topk:
                seed_index_positions()
            if ecfg.paged:
                self._refuse_for_latent_rows("a page pool")
            if mesh is not None and mesh.size > 1:
                self._refuse_for_latent_rows(
                    f"a mesh of {dict(mesh.shape)}")
            if host_cache_bytes() > 0:
                self._refuse_for_latent_rows(
                    "the host tier (TPU_HOST_CACHE_GB)")
        if self.recurrent:
            if ecfg.paged:
                raise ValueError(
                    "a model with recurrent layers serves from the "
                    "contiguous cache: its state lives with the slot and "
                    "a page pool has no place for it; set paged=False")
            if mesh is not None and mesh.size > 1:
                raise ValueError(
                    "a model with recurrent layers serves on one device: "
                    f"no sharding of its state is defined; got a mesh of "
                    f"{dict(mesh.shape)}")

        # an engine's compiled closures refer back to it, so an engine
        # nobody holds any more (a model just unloaded, the probe's first
        # engine) keeps its caches on the device until the cycle collector
        # runs: run it before asking for this one's (``LoadedModel`` does
        # so on every backend; here it costs 0.1 s an engine, and host
        # memory needs no such help)
        if jax.default_backend() != "cpu":
            gc.collect()
        cache_dtype = resolve_cache_dtype(ecfg.cache_dtype)
        if cache_dtype is not ecfg.cache_dtype:
            ecfg = dataclasses.replace(ecfg, cache_dtype=cache_dtype)
            self.ecfg = ecfg
        self.quant4 = cache_dtype == "int4"
        self.quant_cache = (self.quant4
                            or jnp.dtype(cache_dtype) == jnp.dtype(jnp.int8))
        if self.quant4 and not ecfg.paged:
            raise ValueError(
                "cache dtype 'int4' requires the paged cache (the dense "
                "cache has no nibble-packed layout); set paged=True or "
                "use int8")
        self.sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
        if self.sp_size > 1:
            assert self.sp_size & (self.sp_size - 1) == 0, (
                f"sp={self.sp_size} must be a power of two (prefill buckets "
                f"are; each bucket must shard evenly over sp)")
            assert S % self.sp_size == 0, (
                f"max_seq_len {S} must be divisible by sp={self.sp_size}")
        self.paged = ecfg.paged
        self._paged_dp = 1
        if self.paged:
            assert self.sp_size == 1, (
                "paged cache: sp meshes keep the dense sequence-sharded "
                "cache (long_context.py)")
            if mesh is not None:
                extra = {ax: sz for ax, sz in dict(mesh.shape).items()
                         if sz > 1 and ax not in ("tp", "dp")}
                assert not extra, (
                    f"paged cache supports single-device, tp, dp, or "
                    f"dp×tp meshes; got {extra}")
                if mesh.shape.get("dp", 1) > 1:
                    from ..models.decoder import _paged_dp_axes
                    assert _paged_dp_axes(cfg, mesh, KvH) is not None, (
                        f"paged dp mesh needs dp×tp covering all devices "
                        f"with heads divisible by tp; got "
                        f"{dict(mesh.shape)}, H={cfg.n_heads}, KvH={KvH}")
                    self._paged_dp = mesh.shape["dp"]
            ps = ecfg.page_size
            assert ps > 0 and ps & (ps - 1) == 0, (
                f"page_size {ps} must be a power of two")
            assert S % ps == 0, f"max_seq_len {S} must be divisible by page_size {ps}"
        if mesh is not None:
            dp = mesh.shape.get("dp", 1)
            assert B % dp == 0, f"max_slots {B} must divide dp {dp}"
            cache_sh = NamedSharding(mesh, kv_cache_pspec(cfg, mesh))
            b_ax = "dp" if dp > 1 else None
            slot_sh = NamedSharding(mesh, P(b_ax))
            # rank-2 slot state (counts [B,V], pring [B,W], masks) needs a
            # CLOSED spec: P("dp") on rank 2 leaves dim 1 open, and GSPMD
            # is then free to shard it differently per program — an AOT
            # decode exec would reject the re-sharded state
            slot_sh2 = NamedSharding(mesh, P(b_ax, None))
            # multi-controller slice (jax.distributed world): the mesh
            # spans devices other processes own, so host values become
            # global arrays via make_array_from_callback — device_put
            # rejects non-addressable shardings
            self._multi = not all(d.process_index == jax.process_index()
                                  for d in mesh.devices.flat)
            assert not (self._multi and dp > 1), (
                "multi-host slices serve with tp/sp meshes; dp-sharded "
                "slot state is process-local (decode outputs ride P('dp') "
                "and the host only reads its own shard) — scale batch "
                "across hosts with CRD replicas instead")
            self._repl_sh = NamedSharding(mesh, P())
        else:
            cache_sh = slot_sh = slot_sh2 = None
            self._multi = False
            self._repl_sh = None
        self._cache_sh, self._slot_sh = cache_sh, slot_sh
        self._slot_sh2 = slot_sh2
        if mesh is not None:
            self._param_sh = params_sharding_tree(params, mesh, cfg)
            params = jax.tree_util.tree_map(self._g, params,
                                            self._param_sh)
        else:
            self._param_sh = None
        self.params = params

        def zeros(shape, dtype, sh):
            return self._g(np.zeros(shape, dtype), sh)

        self._radix = None
        self._arena = None           # tier-1 host arena (host_cache.py)
        self._host_page_bytes = 0
        self.n_spilled_pages = 0     # pages moved HBM → host, lifetime
        self.last_stitch = None      # per-tier token breakdown of the
                                     # most recent stitch() (scheduler
                                     # reads it for the tier metrics)
        if self.paged:
            from .paged import PageTable, ShardedPageTable
            ps = ecfg.page_size
            self._nblk = S // ps
            n_pages = ecfg.n_pages or (B * S) // ps
            # pool head dim padded to the 128-lane tile: an unaligned hd
            # (phi's 80) otherwise makes XLA materialise PADDED temp
            # copies of the whole pool per program (measured on v5e:
            # 2x4 GB HLO temps, OOM at 32 slots). Writers zero-pad K/V;
            # readers slice back (models/decoder.py paged section).
            # hd=128 families (llama/qwen/mixtral) are untouched; hd<128
            # (phi 80, tinyllama 64) pay the padding in pool bytes — on
            # TPU the minor dim would tile to 128 anyway, but on the CPU
            # backend (dev/kind clusters) this genuinely grows host RAM.
            hd_pool = -(-hd // 128) * 128
            dp = self._paged_dp
            if dp > 1:
                # pool PAGE axis sharded over dp: each shard owns an
                # independent sub-pool (own trash page, own free list) and
                # tables carry shard-LOCAL page indices — the paged
                # forward's dp-manual region then never crosses shards
                per_shard = -(-n_pages // dp)
                self._pt = ShardedPageTable(B, dp, per_shard, ps,
                                            self._nblk)
                pool_shape = (L, dp * (per_shard + 1), KvH, ps, hd_pool)
                pg_ax = "dp"
            else:
                self._pt = PageTable(B, n_pages + 1, ps, self._nblk)
                pool_shape = (L, n_pages + 1, KvH, ps, hd_pool)
                pg_ax = None
            h_ax = ("tp" if (mesh is not None
                             and mesh.shape.get("tp", 1) > 1
                             and KvH % mesh.shape["tp"] == 0) else None)
            pool_sh = (NamedSharding(mesh, P(None, pg_ax, h_ax, None, None))
                       if mesh is not None else None)
            if self.quant_cache:
                s_sh = (NamedSharding(mesh, P(None, pg_ax, h_ax, None))
                        if mesh is not None else None)
                # int4 packs two POSITIONS per byte along the page axis
                # ("q4" [L, P, KvH, ps//2, hd_pool] — ops/quant_cache.py),
                # keeping the 128-lane head dim intact for the fused
                # kernel's page DMAs; scales stay per-position f32
                qkey = "q4" if self.quant4 else "q"
                if self.quant4:
                    assert ps >= 2, "int4 KV needs page_size >= 2"
                q_shape = (pool_shape[:-2] + (ps // 2, hd_pool)
                           if self.quant4 else pool_shape)
                cache_sh = {qkey: pool_sh, "s": s_sh}
                # scale arrays lane-padded to the 128 tile like the codes'
                # head dim: the paged kernel DMAs [KvH, ps] f32 slices per
                # page, and Mosaic requires the DMA'd minor dim to be a
                # multiple of 128 lanes (ps=64 default crashes the real
                # lowering). Writers scatter at off < ps; readers slice
                # (:ps); pad lanes stay zero and inert.
                sp_pool = -(-ps // 128) * 128
                s_shape = pool_shape[:-2] + (sp_pool,)
                self.k_cache = {
                    qkey: zeros(q_shape, jnp.int8, pool_sh),
                    "s": zeros(s_shape, jnp.float32, s_sh)}
                self.v_cache = {
                    qkey: zeros(q_shape, jnp.int8, pool_sh),
                    "s": zeros(s_shape, jnp.float32, s_sh)}
            else:
                cache_sh = pool_sh
                self.k_cache = zeros(pool_shape, ecfg.cache_dtype, pool_sh)
                self.v_cache = zeros(pool_shape, ecfg.cache_dtype, pool_sh)
            self._cache_sh = cache_sh
            # admission-order stamps for preemption victim choice
            self._admit_order = np.zeros((B,), np.int64)
            self._admit_seq = 0
            # radix prefix cache: page-granular cross-request KV reuse
            # (single sub-pool only — a dp-sharded pool's table entries
            # are shard-LOCAL page ids, so a tree spanning shards would
            # stitch pages the slot's shard cannot read).
            # TPU_PREFIX_CACHE=0 falls back to the parked-slot path.
            if (dp == 1
                    and os.environ.get("TPU_PREFIX_CACHE", "1").lower()
                    not in ("0", "false")):
                from .radix import RadixCache
                self._radix = RadixCache(ps)
                # tier-1 host arena: radix LRU eviction spills quiescent
                # pages here instead of freeing them (ISSUE 18). Bounded
                # by TPU_HOST_CACHE_GB; 0 keeps eviction tierless.
                from .host_cache import HostArena, host_cache_bytes
                hc_bytes = host_cache_bytes()
                if hc_bytes > 0:
                    def _pg_bytes(tree):
                        return sum(leaf.nbytes // leaf.shape[1]
                                   for leaf in
                                   jax.tree_util.tree_leaves(tree))
                    self._host_page_bytes = (_pg_bytes(self.k_cache)
                                             + _pg_bytes(self.v_cache))
                    self._arena = HostArena(hc_bytes,
                                            self._host_page_bytes)
        elif self.quant_cache:
            from ..ops.quant_cache import empty_cache

            def qzeros(sh, dim, scales=KvH):
                c = empty_cache(L, B, KvH, S, dim)
                if scales != KvH:
                    c["s"] = jnp.zeros((L, B, scales, S), jnp.float32)
                if sh is None:
                    return c
                return jax.tree_util.tree_map(self._g, c, sh)
            cache_sh = self._quant_cache_sharding(cache_sh)
            self._cache_sh = cache_sh
            # a latent row has two scales a position: its latent part's
            # and its rotated key's (ops/quant_cache.quantize_latent)
            self.k_cache = qzeros(cache_sh, k_dim,
                                  2 if cfg.kv_latent_dim else KvH)
            self.v_cache = qzeros(cache_sh, v_dim) if v_dim else None
        else:
            # head-first: (S, hd) tiles
            self.k_cache = zeros((L, B, KvH, S, k_dim), ecfg.cache_dtype,
                                 cache_sh)
            self.v_cache = (zeros((L, B, KvH, S, v_dim), ecfg.cache_dtype,
                                  cache_sh) if v_dim else None)
        full = self.kv_bytes
        if self.recurrent:
            self.k_cache, self.v_cache = decoder.join_state(
                self.k_cache, self.v_cache,
                decoder.empty_state(cfg, B, ecfg.cache_dtype))
        *carried, win = (decoder.split_state(self.k_cache, self.v_cache)[2]
                         or (None, None, None))

        def nbytes(tree):
            return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))
        # device bytes of the cache by what holds them
        # (tpu_model_cache_bytes{kind}): full-length rows or pages, the
        # window layers' rings, recurrent state; where attention is
        # latent, its rows are the full-length ones and its indexer's keys,
        # where it has one, a kind of their own
        self.cache_bytes = {"full": full, "window": nbytes(win),
                            "state": nbytes(carried)}
        if cfg.index_topk:
            index = nbytes(self.v_cache)
            self.cache_bytes.update(full=full - index, index=index)
        self.lengths = zeros((B,), jnp.int32, slot_sh)
        self.counts = zeros((B, V), jnp.int32, slot_sh2)
        # penalty ring: the last repeat_last_n token ids per slot (sentinel
        # V = "empty"; scatter-drop keeps it out of counts)
        W = max(1, ecfg.repeat_last_n)
        self.pring = self._g(np.full((B, W), V, np.int32), slot_sh2)
        self.last_tokens = zeros((B,), jnp.int32, slot_sh)
        # grammar-constraint state: packed per-slot allowed-token masks
        # (all-ones + flag 0 = unconstrained; ops/constrain.py fills rows)
        self.mask_words = (V + 31) // 32
        self._mask_ones = self._gr(
            np.full((self.mask_words,), 0xFFFFFFFF, np.uint32))
        self.mask_bits = self._g(
            np.full((B, self.mask_words), 0xFFFFFFFF, np.uint32), slot_sh2)
        self._constrained = np.zeros((B,), bool)
        self._constr_dev = zeros((B,), jnp.int32, slot_sh)
        # device-resident grammar program (ops/constrain.GrammarTable):
        # gmask [G, mask_words] holds the packed allowed-token mask per
        # precomputed automaton state, gtrans [G, V] the successor state
        # per sampled token (-1 = the walk leaves the table). Each slot
        # carries a device FSM state: >= 0 device-table mode (its mask is
        # gmask[gstate], advanced ON DEVICE after sampling — no host
        # round-trip per token), -1 host-mask mode (mask_bits row, one
        # token per dispatch), -2 escaped (frozen until the host
        # re-installs a fresh mask via set_mask).
        self._gstates_cap = int(os.environ.get("TPU_GRAMMAR_STATES",
                                               "64"))
        self._grammar_device = os.environ.get(
            "TPU_GRAMMAR_DEVICE", "1").lower() not in ("0", "false")
        self._gmask_dev = self._gr(np.zeros(
            (self._gstates_cap, self.mask_words), np.uint32))
        self._gtrans_dev = self._gr(np.full(
            (self._gstates_cap, V), -1, np.int32))
        self._gstate = self._g(np.full((B,), -1, np.int32), slot_sh)
        self._gdev_mode = np.zeros((B,), bool)  # host mirror: gstate >= 0
        self._gtable_key: Any = None
        self.active = np.zeros((B,), bool)  # host-side mask
        self._active_dev = zeros((B,), jnp.int32, slot_sh)
        # per-slot effective penalty window (≤ W ring capacity)
        self._repeat_n = np.full((B,), W, np.int32)
        self._rln_dev = self._g(self._repeat_n, self._slot_sh)
        # host mirror of per-slot lengths — lets decode_n pick the static
        # attention bucket without a device sync
        self._host_lengths = np.zeros((B,), np.int64)
        # last observed wall-clock per dispatch kind (launch→tokens-on-
        # host), exported as gauges — gives dispatch-dominated regressions
        # a number
        self.dispatch_ms = {"decode": 0.0, "admit": 0.0, "extend": 0.0}
        # perf_counter() when the newest dispatch's tokens reached the host
        # (_landed): a dispatch launched before then began no earlier
        self._t_landed = 0.0
        # mid-serving recompile detector: warm_buckets registers every
        # AOT-warmed executable signature; an executable-cache miss
        # outside warming is an XLA compile inside a timed dispatch —
        # counted per program kind
        self._warming = False
        self._warmed_sigs: set = set()
        # (kind, key) -> the "site=kernel" choices that program traced
        self.program_kernels: Dict[Any, tuple] = {}
        self.recompiles: Dict[str, int] = {
            "decode": 0, "admit": 0, "admit_many": 0, "extend": 0}

        # per-slot sampling params, host mirror + device arrays
        self._opts: Dict[int, SlotOptions] = {}
        self.sp = jax.tree_util.tree_map(
            lambda a: self._g(np.asarray(a), slot_sh),
            sampling.SamplingParams.make(B))
        # mirostat surprise budget, re-seeded to 2*tau at admission; rides
        # the slot-state tuple through every decode/admit program
        self.mu = zeros((B,), jnp.float32, slot_sh)

        def _base_keys():
            return jax.vmap(jax.random.fold_in)(
                jnp.broadcast_to(jax.random.key(0), (B,)), jnp.arange(B))
        # typed key arrays can't ride make_array_from_callback — create
        # them as a (collective) jitted program with a global out_sharding
        self.keys = (jax.jit(_base_keys, out_shardings=slot_sh)()
                     if slot_sh is not None else _base_keys())

        # SP prefill shards the chunk over sp — every bucket must divide it
        # (both are powers of two, so raising the floor suffices; the last
        # bucket is S itself, asserted divisible above).
        self._buckets = prefill_buckets(
            S, max(ecfg.min_prefill_bucket, self.sp_size))
        self._compile_fns()

    def _g(self, x, sharding):
        """Host value → device array under ``sharding``. Single-process:
        plain device_put. Multi-controller slice: the mesh spans devices
        other processes own, so build a global array from the (identical)
        host value via make_array_from_callback."""
        if sharding is None:
            if np.ndim(x) == 0:
                # a scalar is no plain transfer: jnp.asarray makes it a
                # program of its own (jit_convert_element_type, four of an
                # admission's nine), which queues, and is held, like one
                return self._enqueue("scalar_upload", jnp.asarray, x)
            return jnp.asarray(x)
        if not self._multi:
            return jax.device_put(x, sharding)
        # lint: allow(host-sync-hot-path): staging host data for device_put — x is host-resident
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])

    def _gr(self, x):
        """Replicated upload (scalars, B=1 rows, packed masks)."""
        return self._g(x, self._repl_sh)

    def _dummy_key(self):
        """Replicated PRNG key for AOT lowering (typed key arrays can't
        ride make_array_from_callback; a jitted maker can)."""
        k = getattr(self, "_dummy_key_val", None)
        if k is None:
            if self._slot_sh is None:
                k = jax.random.key(0)
            else:
                k = jax.jit(jax.random.key, static_argnums=0,
                            out_shardings=self._repl_sh)(0)
            self._dummy_key_val = k
        return k

    def _enqueue(self, program: str, exe, *args):
        """Hand one compiled program to the runtime, inside the span
        engine.enqueue: the call's own time, and the wait where the
        runtime holds a launch because its queue is full (it takes 32
        programs in flight; an admission is about nine: its prefill, its
        key install and the uploads of its scalars, _g). The arguments
        are staged before the call, so an upload among them lies
        outside: an array's in the launch's own span, a scalar's in an
        engine.enqueue of its own. ``enqueue_s`` adds the calls up: a
        clock of the time that was the runtime's and not the host's
        (Scheduler._step takes it out of its lead)."""
        with span("engine.enqueue", program=program) as sp:
            out = exe(*args)
        self.enqueue_s += sp.dur
        return out

    def _landed(self, kind: str, t_launch: float) -> Tuple[float, float]:
        """A handle's tokens just reached the host: (t_begin, t_done) of
        the dispatch, kept in ``dispatch_ms[kind]``. Device programs run
        in launch order, so one launched while its predecessor still ran
        began when that one's tokens landed, not at its own launch: the
        interval is what THIS dispatch took (host staging included where
        the device stood empty), however deep the queue was. Handles are
        waited in launch order; one waited out of order (an awaited
        admission with a chunk in flight) takes the other's remainder."""
        t_done = time.perf_counter()
        t_begin = max(t_launch, self._t_landed)
        self._t_landed = t_done
        self.dispatch_ms[kind] = (t_done - t_begin) * 1e3
        return t_begin, t_done

    @staticmethod
    def _fetch(x) -> np.ndarray:
        """Device→host for replicated values; a multi-controller array is
        not fully addressable, so read one local (identical) shard."""
        if getattr(x, "is_fully_addressable", True):
            return np.asarray(x)
        return np.asarray(x.addressable_data(0))

    def _refuse_for_latent_rows(self, what: str) -> None:
        """Latent attention's cache has one home so far, the contiguous
        cache of one device; everything else says so by name."""
        if self.cfg.kv_latent_dim:
            raise ValueError(
                f"{what}: no form yet for latent rows (one row [latent | "
                "rotated key] and at most one indexer key a position, no "
                "keys or values a head); they serve from the contiguous "
                "cache of one device, without export or a host tier")

    @staticmethod
    def _quant_cache_sharding(cache_sh):
        """Sharding tree for the {"q", "s"} cache: q keeps the dense spec,
        s drops the trailing head_dim axis."""
        if cache_sh is None:
            return None
        spec = cache_sh.spec
        return {"q": cache_sh,
                "s": NamedSharding(cache_sh.mesh, P(*spec[:-1]))}

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _compile_fns(self):
        cfg = self.cfg
        cache_sh, slot_sh = self._cache_sh, self._slot_sh
        slot_sh2 = self._slot_sh2

        def pin(k_cache, v_cache, lengths, counts, last_tokens, pring, mu):
            """Pin slot-state outputs to their canonical shardings — the
            AOT-compiled decode executables require the state sharding to
            be IDENTICAL across admits (GSPMD would otherwise pick a fresh
            output sharding per program and the exec call would reject).
            Rank-2 state pins with the CLOSED spec (see __init__)."""
            if slot_sh is None:
                return (k_cache, v_cache, lengths, counts, last_tokens,
                        pring, mu)
            wsc = jax.lax.with_sharding_constraint
            return (wsc(k_cache, cache_sh), wsc(v_cache, cache_sh),
                    wsc(lengths, slot_sh), wsc(counts, slot_sh2),
                    wsc(last_tokens, slot_sh), wsc(pring, slot_sh2),
                    wsc(mu, slot_sh))

        if self.sp_size > 1:
            from ..parallel import long_context
            mesh = self.mesh
            prefill_impl = partial(long_context.prefill_chunk_sp, cfg=cfg,
                                   mesh=mesh)
            # the sp cache is sequence-sharded; bucketing would cut across
            # shards, so the sp path always attends its full local prefix
            step_impl = partial(long_context.forward_with_cache_sp, cfg=cfg,
                                mesh=mesh)
            self._bucketed_attn = False
        else:
            prefill_impl = partial(decoder.prefill_chunk, cfg=cfg,
                                   mesh=self.mesh)
            step_impl = partial(decoder.forward_with_cache, cfg=cfg,
                                mesh=self.mesh)
            self._bucketed_attn = True

        W = max(1, self.ecfg.repeat_last_n)
        # a routed model's decode steps bring out what their routers kept
        # (tpu_model_moe_expert_tokens_total); the sequence-parallel
        # forward has no such output
        count_routes = bool(cfg.n_experts) and self.sp_size == 1

        def slot_rows(state, slot):
            """One slot's rows [L, 1, ...] of each leaf [L, B, ...] of the
            state (``decoder.split_state``'s: recurrent state or rings)."""
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice(
                    a, (0, slot) + (0,) * (a.ndim - 2),
                    (a.shape[0], 1) + a.shape[2:]), state)

        def put_rows(state, rows, slot):
            return jax.tree_util.tree_map(
                lambda a, r: jax.lax.dynamic_update_slice(
                    a, r.astype(a.dtype), (0, slot) + (0,) * (a.ndim - 2)),
                state, rows)

        def real(n):
            """How many positions of a row are real, for the forward pass
            of a stack whose recurrent state must not see the padding."""
            return {"n_valid": n} if self.recurrent else {}

        def routes(active):
            """Asks a routed model's decode step for its routers' picks
            over the active slots."""
            return {"route_live": active} if count_routes else {}

        def last_row(logits, n):
            """The [V] logits an admission samples from: row n - 1 of the
            chunk's [1, T, V]; a forward pass told ``real(n)`` hands back
            that row alone."""
            if self.recurrent:
                return logits[0, 0]
            return jax.lax.dynamic_index_in_dim(
                logits[0], n - 1, axis=0, keepdims=False)

        def _sample_install(lengths, counts, last_tokens, pring, mu, logits,
                            ring_row, counts_row, slot, total, sp_row, key,
                            mask_row, cflag, rln):
            """Shared admission tail (fresh prefill AND prefix-cache
            extend): grammar-mask + sample the first token from ``logits``
            (the [V] row of the last valid prompt position — the caller
            indexes it), push it through the penalty window
            (``ring_row``/``counts_row`` cover the prompt), and install
            slot state. ``rln`` is the request's effective window (≤ W;
            0 = penalties see nothing). The slot's mirostat budget
            re-seeds to 2*tau here (llama.cpp's init) and absorbs the
            first token's surprise. Returns (tok, lengths, counts,
            last_tokens, pring, mu)."""
            with device_scope("sample"):
                last = logits
                allowed = unpack_mask(mask_row, cfg.vocab_size)
                last = jnp.where((cflag == 1) & ~allowed, sampling.NEG_INF,
                                 last)
                mu_row = 2.0 * sp_row.mirostat_tau
                # position-folded key, SAME stream as the decode steps (which
                # fold the pre-increment length: the token installed at index
                # total-1 would have used fold_in(key, total-1) had it been
                # decoded). Admission and decode drawing from one keystream is
                # what makes a seeded stream resume bit-identically after a
                # preemption or a restart replay — the re-prefill's first
                # sample lands on exactly the fold the uninterrupted decode
                # would have used at that position.
                tok, mu_row = sampling.sample(
                    last[None], counts_row[None], sp_row,
                    jax.random.fold_in(key, total - 1)[None], mu_row)
                tok = tok[0]
                mu = mu.at[slot].set(mu_row[0])
                rmod = jnp.maximum(rln, 1)
                evict = ring_row[total % rmod]
                counts_row = counts_row.at[evict].add(-1, mode="drop")
                tok_entry = jnp.where(rln > 0, tok, jnp.int32(cfg.vocab_size))
                ring_row = ring_row.at[total % rmod].set(tok_entry)
                counts_row = counts_row.at[tok_entry].add(1, mode="drop")
                pring = pring.at[slot].set(ring_row)
                lengths = lengths.at[slot].set(total)
                counts = counts.at[slot].set(counts_row)
                last_tokens = last_tokens.at[slot].set(tok)
            return tok, lengths, counts, last_tokens, pring, mu

        def _insert_prefilled(k_cache, v_cache, lengths, counts,
                              last_tokens, pring, mu, logits, ks, vs,
                              tokens, slot, n_valid, sp_row, key, mask_row,
                              cflag, rln, table_row=None):
            """Fresh-prefill admission: build the penalty window from the
            LAST ``rln`` prompt tokens of the device-side chunk (image pad
            positions carry id == vocab_size, which the scatter-add drops —
            image tokens never enter the counts), sample, and install
            chunk K/V + slot state."""
            k_cache, v_cache, state = decoder.split_state(k_cache, v_cache)
            if state is not None:
                # the state the prompt ends in goes over whatever the
                # slot's last tenant left: release zeroes nothing
                ks, vs, ended = decoder.split_state(ks, vs)
                if self.quant_cache:
                    ended = decoder.quantize_rings(ended)
                state = put_rows(state, ended, slot)
            last = last_row(logits, n_valid)
            # ring of the last rln prompt tokens: absolute positions
            # n_valid-rln .. n_valid-1 land in slots pos % rln (each slot
            # exactly once — no scatter duplicates); ring capacity is the
            # static W, entries >= rln stay sentinel
            T = tokens.shape[1]
            rmod = jnp.maximum(rln, 1)
            idx = jnp.arange(W, dtype=jnp.int32)
            pos = n_valid - rln + idx
            valid = (idx < rln) & (pos >= 0)
            vals = jnp.where(
                valid, tokens[0][jnp.clip(pos, 0, T - 1)],
                jnp.int32(cfg.vocab_size))
            slot_idx = jnp.where(valid, pos % rmod, jnp.int32(W))
            ring_row = jnp.full((W,), cfg.vocab_size, jnp.int32
                                ).at[slot_idx].set(vals, mode="drop")
            counts_row = jnp.zeros((cfg.vocab_size,), jnp.int32
                                   ).at[vals].add(1, mode="drop")
            (tok, lengths, counts, last_tokens, pring,
             mu) = _sample_install(
                lengths, counts, last_tokens, pring, mu, last, ring_row,
                counts_row, slot, n_valid, sp_row, key, mask_row, cflag,
                rln)
            if self.paged and self._paged_dp > 1:
                k_cache, v_cache = decoder.paged_insert_dp(
                    cfg, k_cache, v_cache, ks, vs, table_row, n_valid,
                    self.mesh)
            elif self.paged:
                k_cache, v_cache = decoder.paged_insert(
                    cfg, k_cache, v_cache, ks, vs, table_row, n_valid)
            elif self.quant_cache:
                from ..ops.quant_cache import quantize_kv, quantize_latent
                with device_scope("attn.kv_write"):
                    if cfg.kv_latent_dim:
                        # [L,1,1,T,C+dr+pad]; its two scales to [L,1,2,T]
                        kq, ksc = quantize_latent(ks, cfg.kv_latent_dim,
                                                  cfg.latent_key_residual)
                        ksc = jnp.moveaxis(ksc[:, :, 0], -1, 2)
                    else:
                        kq, ksc = quantize_kv(ks)      # [L,1,KvH,T,hd]
                    if vs is not None:      # a second row to keep
                        vq, vsc = quantize_kv(vs)
                    dus = jax.lax.dynamic_update_slice
                    k_cache = {
                        "q": dus(k_cache["q"], kq, (0, slot, 0, 0, 0)),
                        "s": dus(k_cache["s"], ksc, (0, slot, 0, 0))}
                    if vs is not None:
                        v_cache = {
                            "q": dus(v_cache["q"], vq, (0, slot, 0, 0, 0)),
                            "s": dus(v_cache["s"], vsc, (0, slot, 0, 0))}
            else:
                with device_scope("attn.kv_write"):
                    k_cache = jax.lax.dynamic_update_slice(
                        k_cache, ks.astype(k_cache.dtype),
                        (0, slot, 0, 0, 0))
                    if vs is not None:
                        v_cache = jax.lax.dynamic_update_slice(
                            v_cache, vs.astype(v_cache.dtype),
                            (0, slot, 0, 0, 0))
            k_cache, v_cache = decoder.join_state(k_cache, v_cache, state)
            return (tok, *pin(k_cache, v_cache, lengths, counts,
                              last_tokens, pring, mu))

        def _admit(params, k_cache, v_cache, lengths, counts, last_tokens,
                   pring, mu, tokens, slot, n_valid, sp_row, key, mask_row,
                   cflag, rln, table_row=None):
            """Prefill a padded B=1 chunk AND insert it into the slot state
            — one device program, one host round-trip per admission.
            ``table_row`` [NBLK] — the slot's block table (paged mode)."""
            logits, ks, vs = prefill_impl(params, tokens=tokens,
                                          **real(n_valid))
            return _insert_prefilled(k_cache, v_cache, lengths, counts,
                                     last_tokens, pring, mu, logits, ks, vs,
                                     tokens, slot, n_valid, sp_row, key,
                                     mask_row, cflag, rln, table_row)

        def _make_admit_many(m):
            """Batched fresh admission: prefill ``m`` same-bucket prompts
            in ONE device program and insert each into its slot. The
            prefill is batch-generic (causal masking makes each row's
            logits independent of the others), and the per-slot inserts
            unroll statically — the program is keyed by (m, bucket)."""
            def _admit_many(params, k_cache, v_cache, lengths, counts,
                            last_tokens, pring, mu, tokens, slots,
                            n_valids, sp_rows, keys_m, mask_row, rlns,
                            table_rows=None):
                logits, ks, vs = prefill_impl(params, tokens=tokens,
                                              **real(n_valids))
                toks = []
                for i in range(m):
                    # row i of what the prefill made (K/V are trees where
                    # the stack carries a recurrent state beside them)
                    logits_i = logits[i:i + 1]
                    ks_i, vs_i = jax.tree_util.tree_map(
                        lambda a: a[:, i:i + 1], (ks, vs))
                    (tok, k_cache, v_cache, lengths, counts, last_tokens,
                     pring, mu) = _insert_prefilled(
                        k_cache, v_cache, lengths, counts, last_tokens,
                        pring, mu, logits_i, ks_i, vs_i,
                        tokens[i:i + 1], slots[i],
                        n_valids[i],
                        jax.tree_util.tree_map(lambda a: a[i:i + 1],
                                               sp_rows),
                        keys_m[i], mask_row, jnp.int32(0), rlns[i],
                        None if table_rows is None else table_rows[i])
                    toks.append(tok)
                return (jnp.stack(toks), k_cache, v_cache, lengths,
                        counts, last_tokens, pring, mu)
            return _admit_many

        def _admit_embeds(params, k_cache, v_cache, lengths, counts,
                          last_tokens, pring, mu, tokens, embeds, slot,
                          n_valid, sp_row, key, mask_row, cflag, rln,
                          table_row=None):
            """Multimodal admission: like _admit but prefilling from a
            precomputed [1, T, D] embedding sequence (image tokens spliced
            into text embeddings); ``tokens`` feeds the penalty counts with
            id == vocab_size at image positions (dropped by the scatter).
            The embedding lookup never sees ``tokens``."""
            logits, ks, vs = prefill_impl(params, tokens=tokens,
                                          inputs_embeds=embeds,
                                          **real(n_valid))
            return _insert_prefilled(k_cache, v_cache, lengths, counts,
                                     last_tokens, pring, mu, logits, ks, vs,
                                     tokens, slot, n_valid, sp_row, key,
                                     mask_row, cflag, rln, table_row)

        def _decode_body(params, k_cache, v_cache, lengths, counts,
                         last_tokens, pring, mu, sp, keys, active,
                         mask_bits, constrained, rln, gstate, gmask,
                         gtrans, attn_len=None, tables=None):
            # escaped slots (gstate == -2) freeze in place: the host has
            # to re-derive their mask before they may advance again
            active = active * (gstate != -2).astype(active.dtype)
            if self.paged:
                ps = self.ecfg.page_size
                nblk = -(-(attn_len or self.max_seq) // ps)
                logits, k_cache, v_cache, *load = \
                    decoder.forward_with_cache_paged(
                        params, cfg, last_tokens[:, None], k_cache, v_cache,
                        tables, lengths, nblk, mesh=self.mesh,
                        **routes(active))
            else:
                kw = {"attn_len": attn_len} if (attn_len is not None
                                                and self._bucketed_attn) \
                    else {}
                # a slot parked between prefill pieces, or freed, sits in
                # every step's batch: its keys may be written over, its
                # recurrent state may not
                logits, k_cache, v_cache, *load = step_impl(
                    params, tokens=last_tokens[:, None], k_cache=k_cache,
                    v_cache=v_cache, lengths=lengths, **kw, **real(active),
                    **routes(active))
            with device_scope("sample"):
                step_keys = jax.vmap(jax.random.fold_in)(keys, lengths)
                last = logits[:, 0]
                # device-table slots read their mask straight off the
                # precomputed grammar table (host rows for everyone else)
                gdev = gstate >= 0
                gi = jnp.clip(gstate, 0, gmask.shape[0] - 1)
                eff_bits = jnp.where(gdev[:, None], gmask[gi], mask_bits)
                allowed = unpack_mask(eff_bits, cfg.vocab_size)
                last = jnp.where((constrained == 1)[:, None] & ~allowed,
                                 sampling.NEG_INF, last)
                toks, mu_new = sampling.sample(last, counts, sp, step_keys,
                                               mu, live=active)
                # advance the device automaton by the sampled token; a -1
                # transition (walk left the precomputed table) escapes to -2
                ns = gtrans[gi, toks]
                ns = jnp.where(ns < 0, jnp.int32(-2), ns)
                gstate = jnp.where(gdev & (active == 1), ns, gstate)
                mu = jnp.where(active == 1, mu_new, mu)
                B = toks.shape[0]
                bi = jnp.arange(B)
                # penalty window: the NEW token's absolute position is
                # lengths + 1 (last_tokens sits at lengths); evict whatever
                # occupied that ring slot rln[i] tokens ago, then admit the
                # new token. Per-slot rln picks each request's effective
                # window inside the static-W ring via the modulus — inactive
                # or rln==0 slots write the OOB sentinel.
                rmod = jnp.maximum(rln, 1)
                slot_pos = (lengths + 1) % rmod
                evict = pring[bi, slot_pos]
                evict = jnp.where(active == 1, evict,
                                  jnp.int32(cfg.vocab_size))
                live = (active == 1) & (rln > 0)
                new = jnp.where(live, toks, jnp.int32(cfg.vocab_size))
                counts = counts.at[bi, evict].add(-1, mode="drop")
                counts = counts.at[bi, new].add(1, mode="drop")
                pring = jnp.where(live[:, None],
                                  pring.at[bi, slot_pos].set(toks), pring)
                lengths = lengths + active
                last_tokens = jnp.where(active == 1, toks, last_tokens)
            if slot_sh is not None:
                gstate = jax.lax.with_sharding_constraint(gstate, slot_sh)
            return (toks, *pin(k_cache, v_cache, lengths, counts,
                               last_tokens, pring, mu), gstate,
                    load[0] if load else None)

        def _decode(params, k_cache, v_cache, lengths, counts, last_tokens,
                    pring, mu, sp, keys, active, mask_bits, constrained,
                    rln, gstate, gmask, gtrans, tables=None):
            (toks, k_cache, v_cache, lengths, counts, last_tokens,
             pring, mu, gstate, _load) = _decode_body(
                 params, k_cache, v_cache, lengths, counts, last_tokens,
                 pring, mu, sp, keys, active, mask_bits, constrained, rln,
                 gstate, gmask, gtrans, tables=tables)
            return (toks, k_cache, v_cache, lengths, counts, last_tokens,
                    pring, mu, keys, gstate)

        def _decode_n(params, k_cache, v_cache, lengths, counts, last_tokens,
                      pring, mu, sp, keys, active, mask_bits, constrained,
                      rln, gstate, gmask, gtrans, n, attn_len, tables=None,
                      budgets=None):
            """n decode steps as ONE device program (lax.scan) — a single
            dispatch + host sync per n tokens per slot. ``attn_len`` is the
            static attended-cache prefix (decode traffic scales with it,
            not with max_seq_len; in paged mode it only bounds the kernel
            grid — page DMAs clamp to each slot's own length). ``tables``
            [B, NBLK] (paged): the host grows them to cover lengths + n
            before dispatch.

            ``budgets`` [B] int32 — per-slot step budget: a slot freezes
            (no length advance, no state change) once the step index
            reaches its budget. HOST-masked grammar slots get budget 1 —
            they need a fresh host-side PDA mask per token — while the
            rest of the batch keeps the full chunk (round-1 weak #5: one
            format:"json" request used to collapse everyone to n=1).
            Device-table grammar slots (gstate >= 0) keep the full chunk:
            their mask refreshes on device from gmask/gtrans.

            The last value is a routed model's [E] int32, how many of the
            chunk's (step, active slot) pairs each expert of the router
            was kept for, over all layers (None for a model without a
            router): the host takes it beside the tokens, once a chunk."""
            def step(carry, t):
                (k_cache, v_cache, lengths, counts, last_tokens,
                 pring, mu, gstate, load) = carry
                act = active if budgets is None else active * (t < budgets)
                (toks, k_cache, v_cache, lengths, counts, last_tokens,
                 pring, mu, gstate, picks) = _decode_body(
                     params, k_cache, v_cache, lengths, counts,
                     last_tokens, pring, mu, sp, keys, act, mask_bits,
                     constrained, rln, gstate, gmask, gtrans,
                     attn_len=attn_len, tables=tables)
                if picks is not None:
                    load = load + picks
                return (k_cache, v_cache, lengths, counts, last_tokens,
                        pring, mu, gstate, load), toks

            carry = (k_cache, v_cache, lengths, counts, last_tokens, pring,
                     mu, gstate, jnp.zeros((cfg.n_experts,), jnp.int32)
                     if count_routes else None)
            carry, toks_n = jax.lax.scan(
                step, carry, jnp.arange(n, dtype=jnp.int32))
            (k_cache, v_cache, lengths, counts, last_tokens, pring,
             mu, gstate, load) = carry
            return (toks_n, k_cache, v_cache, lengths, counts, last_tokens,
                    pring, mu, keys, gstate, load)

        def _make_extend_paged(A):
            """Paged prefix-cache continuation, attending only the first
            ``A`` positions (the live-prefix bucket): the reused prefix
            stays in its pages untouched; the tail prefills through the
            paged forward (B=1 view, positions offset by ``start``),
            writing into pages from ``table_row`` — no cache
            slice/unslice copies, and quantized pools work the same (the
            paged forward quantizes fresh K/V per layer). Tail
            bucket-padding beyond n_new lands on unowned table entries,
            i.e. the trash page. On dp meshes the table argument is the
            [dp, NBLK] owner-real/others-trash rows plus the owning
            shard's index, and the forward is the dp-manual twin
            (decoder.paged_extend_dp)."""
            nblk_a = -(-A // self.ecfg.page_size)

            def _extend_paged(params, k_cache, v_cache, lengths, counts,
                              last_tokens, pring, mu, tokens, ring_row,
                              counts_row, slot, start, n_new, table_row,
                              sp_row, key, mask_row, cflag, rln):
                logits, k_cache, v_cache = \
                    decoder.forward_with_cache_paged(
                        params, cfg, tokens, k_cache, v_cache,
                        table_row[None], start[None], nblk_a,
                        mesh=self.mesh)
                last = jax.lax.dynamic_index_in_dim(
                    logits[0], n_new - 1, axis=0, keepdims=False)
                (tok, lengths, counts, last_tokens, pring,
                 mu) = _sample_install(
                    lengths, counts, last_tokens, pring, mu, last,
                    ring_row, counts_row, slot, start + n_new, sp_row, key,
                    mask_row, cflag, rln)
                return (tok, *pin(k_cache, v_cache, lengths, counts,
                                  last_tokens, pring, mu))

            def _extend_paged_dp(params, k_cache, v_cache, lengths,
                                 counts, last_tokens, pring, mu, tokens,
                                 ring_row, counts_row, slot, start, n_new,
                                 table_rows, owner, sp_row, key, mask_row,
                                 cflag, rln):
                logits, k_cache, v_cache = decoder.paged_extend_dp(
                    params, cfg, tokens, k_cache, v_cache, table_rows,
                    start[None], nblk_a, owner, self.mesh)
                last = jax.lax.dynamic_index_in_dim(
                    logits[0], n_new - 1, axis=0, keepdims=False)
                (tok, lengths, counts, last_tokens, pring,
                 mu) = _sample_install(
                    lengths, counts, last_tokens, pring, mu, last,
                    ring_row, counts_row, slot, start + n_new, sp_row, key,
                    mask_row, cflag, rln)
                return (tok, *pin(k_cache, v_cache, lengths, counts,
                                  last_tokens, pring, mu))
            return (_extend_paged_dp if self._paged_dp > 1
                    else _extend_paged)

        def _make_extend_sp(A):
            """sp twin of ``_make_extend``: the slot's cache stays
            sequence-sharded end to end. The tail chunk's compute is
            replicated across sp — ``forward_with_cache_sp`` is built for
            T>1 continuation (per-query absolute positions mask the chunk
            causally against the cache AND itself; ``sp_cache_write``
            scatters each fresh key to its owning shard) — so the only
            sp-specific engine work is skipping the attended-prefix
            bucketing: the sp path always attends its full local chunk,
            and ``extend()`` passes A = max_seq (closing round-2 weak #5:
            sp caches used to forfeit prefix caching entirely)."""
            from ..parallel.long_context import forward_with_cache_sp
            return _make_extend(A, forward=forward_with_cache_sp)

        def _make_extend(A, forward=None):
            """Prefix-cache continuation: prefill only the tail of a
            prompt whose first ``start`` tokens are already in ``slot``'s
            KV cache (a parked conversation), slicing AND attending only
            the first ``A`` cache positions — the live-prefix bucket
            (programs are keyed by (tail, attn) bucket pairs, so the
            admission's HBM traffic scales with the conversation, not
            max_seq_len). ``ring_row``/``counts_row`` are the penalty
            window over the FULL continuation prompt, prebuilt on the
            host (the parked window may belong to a divergent suffix).
            sp caches extend through ``_make_extend_sp`` (same body,
            ``forward`` swapped, A = max_seq so the slice is the whole
            sequence axis); int8 caches slice both the entries and their
            scales — the cached forward quantizes the tail in place
            (round-1 weak #4: int8 and prefix caching used to be
            mutually exclusive)."""
            fwd = forward if forward is not None \
                else decoder.forward_with_cache

            def _extend(params, k_cache, v_cache, lengths, counts,
                        last_tokens, pring, mu, tokens, ring_row,
                        counts_row, slot, start, n_new, sp_row, key,
                        mask_row, cflag, rln):
                dsl = jax.lax.dynamic_slice
                dus = jax.lax.dynamic_update_slice
                k_cache, v_cache, state = decoder.split_state(k_cache,
                                                              v_cache)
                # one slot's first A positions of every leaf [L, B, KvH,
                # S, ...]: codes and scales alike, keys' and values' rows
                # each at their own width
                def slice5(c):
                    return jax.tree_util.tree_map(
                        lambda a: dsl(a, (0, slot) + (0,) * (a.ndim - 2),
                                      (a.shape[0], 1, a.shape[2], A)
                                      + a.shape[4:]), c)

                def write5(c, cs):
                    return jax.tree_util.tree_map(
                        lambda a, x: dus(a, x,
                                         (0, slot) + (0,) * (a.ndim - 2)),
                        c, cs)
                kc_s, vc_s = slice5(k_cache), slice5(v_cache)
                if state is not None:
                    # the slot's state, read where the last piece left it
                    # and advanced over the tail's real positions only
                    kc_s, vc_s = decoder.join_state(
                        kc_s, vc_s, slot_rows(state, slot))
                logits, kc_s, vc_s = fwd(
                    params, cfg, tokens, kc_s, vc_s, start[None],
                    mesh=self.mesh, **real(n_new[None]))
                if state is not None:
                    kc_s, vc_s, state_s = decoder.split_state(kc_s, vc_s)
                    state = put_rows(state, state_s, slot)
                k_cache = write5(k_cache, kc_s)
                v_cache = write5(v_cache, vc_s)
                k_cache, v_cache = decoder.join_state(k_cache, v_cache,
                                                      state)
                last = last_row(logits, n_new)
                (tok, lengths, counts, last_tokens, pring,
                 mu) = _sample_install(
                    lengths, counts, last_tokens, pring, mu, last,
                    ring_row, counts_row, slot, start + n_new, sp_row, key,
                    mask_row, cflag, rln)
                return (tok, *pin(k_cache, v_cache, lengths, counts,
                                  last_tokens, pring, mu))
            return _extend

        def _release(lengths, counts, last_tokens, pring, mu, slot):
            lengths = lengths.at[slot].set(0)
            counts = counts.at[slot].set(0)
            last_tokens = last_tokens.at[slot].set(0)
            pring = pring.at[slot].set(cfg.vocab_size)
            mu = mu.at[slot].set(0.0)
            return lengths, counts, last_tokens, pring, mu

        def _set_mask(mask_bits, constr, gstate, slot, row, flag, gval):
            mask_bits = mask_bits.at[slot].set(row)
            constr = constr.at[slot].set(flag)
            gstate = gstate.at[slot].set(gval)
            if slot_sh is not None:
                wsc = jax.lax.with_sharding_constraint
                mask_bits = wsc(mask_bits, slot_sh2)
                constr = wsc(constr, slot_sh)
                gstate = wsc(gstate, slot_sh)
            return mask_bits, constr, gstate

        # Explicit out_shardings on every state-returning program: wsc
        # inside the trace guides internals, but the JIT BOUNDARY sharding
        # of unannotated outputs is GSPMD's choice — on a dp×tp mesh it
        # happily re-shards counts [B, V] over tp in one program, and the
        # AOT execs (compiled against the canonical state shardings) then
        # reject their own prior outputs.
        state_outs = None
        if slot_sh is not None:
            state_outs = (cache_sh, cache_sh, slot_sh, slot_sh2, slot_sh,
                          slot_sh2, slot_sh)

        def _jit(fn, donate, static=None, outs=None):
            kw = {"donate_argnums": donate}
            if static is not None:
                kw["static_argnums"] = static
            if outs is not None and slot_sh is not None:
                kw["out_shardings"] = outs
            return jax.jit(fn, **kw)

        if state_outs:
            # every output gets a CONCRETE sharding (a None leaf in an
            # out_shardings tree reads as an empty pytree node, not
            # "unspecified"): sampled tokens ride the batch axis, the
            # first admission token is a replicated scalar
            b_ax = slot_sh.spec[0] if slot_sh.spec else None
            repl_sh = NamedSharding(self.mesh, P())
            toksn_sh = NamedSharding(self.mesh, P(None, b_ax))
            tok_outs = (repl_sh,) + state_outs
            dec_outs = (slot_sh,) + state_outs + (slot_sh, slot_sh)
            # the last is a routed model's picks per expert (None, an
            # empty node, for a model without a router)
            decn_outs = (toksn_sh,) + state_outs + (
                slot_sh, slot_sh, repl_sh if count_routes else None)
        else:
            tok_outs = dec_outs = decn_outs = None
        self._admit_fn = _jit(_admit, (1, 2, 3, 4, 5, 6, 7),
                              outs=tok_outs)
        self._admit_embeds_fn = _jit(_admit_embeds, (1, 2, 3, 4, 5, 6, 7),
                                     outs=tok_outs)
        self._admit_execs: Dict[int, Any] = {}
        if state_outs:
            toksm_sh = repl_sh  # stacked replicated scalars stay replicated
            many_outs = (toksm_sh,) + state_outs
        else:
            many_outs = None
        self._admit_many_make = lambda m: _jit(
            _make_admit_many(m), (1, 2, 3, 4, 5, 6, 7), outs=many_outs)
        self._admit_many_jits: Dict[int, Any] = {}
        self._admit_many_execs: Dict[Any, Any] = {}
        make_ext = (_make_extend_paged if self.paged
                    else _make_extend_sp if self.sp_size > 1
                    else _make_extend)
        self._extend_make = lambda A: _jit(make_ext(A),
                                           (1, 2, 3, 4, 5, 6, 7),
                                           outs=tok_outs)
        self._extend_jits: Dict[int, Any] = {}
        self._extend_execs: Dict[Any, Any] = {}
        self._decode_fn = _jit(_decode, (1, 2, 3, 4, 5, 6, 7, 9, 14),
                               outs=dec_outs)
        self._decode_n_fn = _jit(_decode_n, (1, 2, 3, 4, 5, 6, 7, 9, 14),
                                 static=(17, 18), outs=decn_outs)
        self._release_fn = _jit(
            _release, (0, 1, 2, 3, 4),
            outs=((slot_sh, slot_sh2, slot_sh, slot_sh2, slot_sh)
                  if slot_sh else None))

        if self.paged:
            def _copy_page(k_cache, v_cache, src, dst):
                """Copy-on-write: physical page ``src`` → ``dst`` across
                all layers. The page axis is axis 1 in both the code
                pools and the quant scale arrays, so one tree_map'd
                slice covers the plain and {"q","s"} layouts."""
                def cp(c):
                    page = jax.lax.dynamic_slice_in_dim(c, src, 1, axis=1)
                    return jax.lax.dynamic_update_slice_in_dim(
                        c, page, dst, axis=1)
                k_cache = jax.tree_util.tree_map(cp, k_cache)
                v_cache = jax.tree_util.tree_map(cp, v_cache)
                if slot_sh is not None:
                    wsc = jax.lax.with_sharding_constraint
                    k_cache = wsc(k_cache, cache_sh)
                    v_cache = wsc(v_cache, cache_sh)
                return k_cache, v_cache
            self._copy_page_fn = _jit(_copy_page, (0, 1),
                                      outs=(cache_sh, cache_sh))

            # tiered KV cache (ISSUE 18): gather slices one page out of
            # the pool for the host-tier spill (REPLICATED output, so on
            # a multi-host mesh every host can device_get identical
            # bytes); upload writes a spilled page's bytes back into a
            # freshly grown page — an async enqueue that overlaps the
            # tail prefill, never a host sync.
            page_repl = (tuple(
                jax.tree_util.tree_map(lambda _s: self._repl_sh, cache_sh)
                for _ in range(2)) if slot_sh is not None else None)

            def _gather_page(k_cache, v_cache, src):
                def g(c):
                    return jax.lax.dynamic_slice_in_dim(c, src, 1, axis=1)
                return (jax.tree_util.tree_map(g, k_cache),
                        jax.tree_util.tree_map(g, v_cache))
            self._gather_page_fn = _jit(_gather_page, (), outs=page_repl)

            def _upload_page(k_cache, v_cache, kp, vp, dst):
                def up(c, page):
                    return jax.lax.dynamic_update_slice_in_dim(
                        c, page, dst, axis=1)
                k_cache = jax.tree_util.tree_map(up, k_cache, kp)
                v_cache = jax.tree_util.tree_map(up, v_cache, vp)
                if slot_sh is not None:
                    wsc = jax.lax.with_sharding_constraint
                    k_cache = wsc(k_cache, cache_sh)
                    v_cache = wsc(v_cache, cache_sh)
                return k_cache, v_cache
            self._upload_page_fn = _jit(_upload_page, (0, 1),
                                        outs=(cache_sh, cache_sh))

        def _install_key(keys, slot, seed):
            k = jax.random.key(seed)
            return keys.at[slot].set(k), k
        self._install_key_fn = _jit(
            _install_key, (0,),
            outs=(slot_sh, self._repl_sh) if slot_sh is not None else None)
        self._set_mask_fn = _jit(
            _set_mask, (0, 1, 2),
            outs=(slot_sh2, slot_sh, slot_sh) if slot_sh else None)
        # AOT-compiled decode_n executables keyed by (n, attn_bucket) — a
        # bucket crossing must swap programs, never recompile mid-serving
        self._decode_execs: Dict[Any, Any] = {}

    # ------------------------------------------------------------------
    # host API
    # ------------------------------------------------------------------
    def free_slots(self):
        return [i for i in range(self.n_slots) if not self.active[i]]

    def bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt of {n} tokens exceeds max_seq_len "
                         f"{self.max_seq}")

    def _sp_row(self, o: SlotOptions):
        g = self._gr
        return sampling.SamplingParams(
            temperature=g(np.array([o.temperature], np.float32)),
            top_k=g(np.array([o.top_k], np.int32)),
            top_p=g(np.array([o.top_p], np.float32)),
            min_p=g(np.array([o.min_p], np.float32)),
            typical_p=g(np.array([o.typical_p], np.float32)),
            repeat_penalty=g(np.array([o.repeat_penalty], np.float32)),
            presence_penalty=g(np.array([o.presence_penalty], np.float32)),
            frequency_penalty=g(np.array([o.frequency_penalty],
                                         np.float32)),
            mirostat=g(np.array([o.mirostat], np.int32)),
            mirostat_tau=g(np.array([o.mirostat_tau], np.float32)),
            mirostat_eta=g(np.array([o.mirostat_eta], np.float32)))

    def _rebuild_sp(self):
        opts = [self._opts.get(i, SlotOptions()) for i in range(self.n_slots)]
        g = lambda a: self._g(a, self._slot_sh)  # noqa: E731
        self.sp = sampling.SamplingParams(
            temperature=g(np.array([o.temperature for o in opts],
                                   np.float32)),
            top_k=g(np.array([o.top_k for o in opts], np.int32)),
            top_p=g(np.array([o.top_p for o in opts], np.float32)),
            min_p=g(np.array([o.min_p for o in opts], np.float32)),
            typical_p=g(np.array([o.typical_p for o in opts], np.float32)),
            repeat_penalty=g(np.array(
                [o.repeat_penalty for o in opts], np.float32)),
            presence_penalty=g(np.array(
                [o.presence_penalty for o in opts], np.float32)),
            frequency_penalty=g(np.array(
                [o.frequency_penalty for o in opts], np.float32)),
            mirostat=g(np.array([o.mirostat for o in opts], np.int32)),
            mirostat_tau=g(np.array(
                [o.mirostat_tau for o in opts], np.float32)),
            mirostat_eta=g(np.array(
                [o.mirostat_eta for o in opts], np.float32)))

    def _prep_slot(self, slot: int, opts: SlotOptions, seq_len: int,
                   mask_row: Optional[np.ndarray]):
        """Shared admission setup: install the slot PRNG key, resolve the
        optional grammar mask. Returns (key, mask_row_dev, cflag)."""
        # deterministic mix (NOT hash(): Python salts it per process, and
        # multi-host followers must derive byte-identical keys or the
        # replicated sampling inputs diverge across the SPMD world)
        seed = (opts.seed if opts.seed >= 0
                else (slot * 1000003 + seq_len * 7919 + 12345)
                & 0x7FFFFFFF)
        with span("engine.install_key"):
            self.keys, key = self._enqueue(
                "install_key", self._install_key_fn,
                self.keys, self._gr(np.int32(slot)),
                self._gr(np.int32(seed)))
        if mask_row is not None:
            return key, self._gr(self._pad_mask_row(mask_row)), \
                self._gr(np.int32(1))
        return key, self._mask_ones, self._gr(np.int32(0))

    def _resolve_rln(self, opts: SlotOptions) -> int:
        """Request window → effective window: -1 = engine max, clamp to
        the static ring capacity W."""
        W = max(1, self.ecfg.repeat_last_n)
        r = opts.repeat_last_n
        return W if r < 0 else min(r, W)

    def _commit_slot(self, slot: int, n_total: int, opts: SlotOptions):
        """Shared admission tail: mark the slot live and rebuild batched
        sampling params."""
        self.active[slot] = True
        self._host_lengths[slot] = n_total
        self._opts[slot] = opts
        self._repeat_n[slot] = self._resolve_rln(opts)
        if self.paged:
            self._admit_seq += 1
            self._admit_order[slot] = self._admit_seq
        self._upload_slot_state()

    def _upload_slot_state(self):
        """Re-stage the per-slot host state an admission changed: penalty
        windows, batched sampling params, the active mask."""
        with span("engine.upload"):
            self._rln_dev = self._g(self._repeat_n, self._slot_sh)
            self._rebuild_sp()
            self._active_dev = self._g(self.active.astype(np.int32),
                                       self._slot_sh)

    def admit(self, slot: int, prompt: np.ndarray,
              opts: SlotOptions = SlotOptions(),
              embeds: Optional[np.ndarray] = None,
              mask_row: Optional[np.ndarray] = None) -> int:
        """Prefill ``prompt`` into ``slot``; returns the first sampled token.

        ``embeds`` [n, D] — optional precomputed embedding sequence for the
        prompt (multimodal); must match len(prompt), where image positions
        in ``prompt`` carry a pad token id for the penalty counts.

        ``mask_row`` [mask_words] uint32 — optional packed allowed-token
        mask applied to the FIRST sampled token (grammar-constrained
        requests); the caller then keeps per-step masks flowing via
        ``set_mask``.
        """
        return self.admit_launch(slot, prompt, opts, embeds,
                                 mask_row).wait()[0]

    def admit_launch(self, slot: int, prompt: np.ndarray,
                     opts: SlotOptions = SlotOptions(),
                     embeds: Optional[np.ndarray] = None,
                     mask_row: Optional[np.ndarray] = None) -> AdmitHandle:
        """``admit`` without its wait: the same program with the same
        arguments is dispatched and the slot is live when this returns;
        the handle's ``wait()`` yields the first token. Pool exhaustion
        and an armed ``engine.admit`` fault raise here, before any
        dispatch; a device error surfaces at ``wait()``."""
        FAULTS.check("engine.admit")
        with span("engine.admit") as sp:
            return self._do_admit(slot, prompt, opts, embeds, mask_row,
                                  sp.t0)

    def _do_admit(self, slot, prompt, opts, embeds, mask_row,
                  t0: float) -> AdmitHandle:
        assert not self.active[slot], f"slot {slot} busy"
        n = int(prompt.shape[0])
        if n >= self.max_seq:
            raise ValueError(f"prompt too long: {n} >= {self.max_seq}")
        bucket = self.bucket_for(n)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n] = prompt
        key, mrow, cflag = self._prep_slot(slot, opts, n, mask_row)
        table_row = self._grow_for_admit(slot, n)
        if embeds is not None:
            assert embeds.shape[0] == n, "embeds must cover the prompt"
            emb = np.zeros((1, bucket, embeds.shape[1]), np.float32)
            emb[0, :n] = embeds
            (tok, self.k_cache, self.v_cache, self.lengths, self.counts,
             self.last_tokens, self.pring,
             self.mu) = self._enqueue(
                "admit", self._admit_embeds_fn,
                self.params, self.k_cache, self.v_cache, self.lengths,
                self.counts, self.last_tokens, self.pring, self.mu,
                self._gr(tokens), self._gr(emb), self._gr(np.int32(slot)),
                self._gr(np.int32(n)), self._sp_row(opts), key, mrow,
                cflag, self._gr(np.int32(self._resolve_rln(opts))),
                table_row)
        else:
            (tok, self.k_cache, self.v_cache, self.lengths, self.counts,
             self.last_tokens, self.pring,
             self.mu) = self._enqueue(
                "admit", self._admit_exec(bucket),
                self.params, self.k_cache, self.v_cache, self.lengths,
                self.counts, self.last_tokens, self.pring, self.mu,
                self._gr(tokens), self._gr(np.int32(slot)),
                self._gr(np.int32(n)), self._sp_row(opts), key, mrow,
                cflag, self._gr(np.int32(self._resolve_rln(opts))),
                table_row)
        self._commit_slot(slot, n, opts)
        return AdmitHandle(self, tok, t0, "admit", (slot,))

    def _grow_for_admit(self, slot: int, n: int):
        """Paged admission bookkeeping: drop any pages the slot still owns
        (a parked prefix being overwritten), allocate pages for the prompt,
        return the device table row. None in dense mode."""
        if not self.paged:
            return None
        from .paged import PagesExhausted
        self._pt.release(slot)
        # availability check includes one decode chunk of headroom (not
        # allocated — prepare_decode claims it): admitting a request the
        # very next chunk must preempt would thrash prefill work.
        # free_for(slot): on a dp mesh each slot allocates only from its
        # own shard's sub-pool
        need = self._pt.blocks_for(
            min(n + self.ecfg.decode_chunk, self.max_seq))
        self._make_room(slot, need)
        if (need > self._pt.free_for(slot)
                or not self._pt.grow(slot, n)):
            raise PagesExhausted(
                f"prompt of {n} tokens (+1 chunk headroom) needs "
                f"{need} pages; {self._pt.free_for(slot)} free")
        return self._table_row_dev(slot)

    def _make_room(self, slot: int, pages: int):
        """Before an allocation of ``pages`` for ``slot`` declares the
        pool dry: evict, oldest first, as many cached leaves as are
        missing, of those the fence lets go AT ONCE (no slot maps them
        and the last one that did went at a retired epoch:
        runtime/paged.py). A leaf that would only move to the quarantine
        stays in the tree: the stall that would free it unfences it as
        well. Pure function of mirrored state, inside mirrored calls."""
        short = pages - self._pt.free_for(slot)
        if short > 0 and self._radix is not None:
            self.radix_evict(short, at_once=True)

    def _table_row_dev(self, slot: int):
        """The admission program's table argument: the slot's row [NBLK]
        (local == global indices without dp), or [dp, NBLK] per-shard rows
        where only the owning shard carries real (LOCAL) pages — the
        others get all-trash rows so their replicated writes self-discard
        (decoder.paged_insert_dp)."""
        if self._paged_dp == 1:
            return self._gr(self._pt.tables[slot])
        from .paged import TRASH_PAGE
        rows = np.full((self._paged_dp, self._nblk), TRASH_PAGE, np.int32)
        rows[self._pt.shard_of(slot)] = self._pt.tables[slot]
        # [dp, NBLK]: each dp shard reads its own row inside the insert's
        # manual region
        return self._g(rows, NamedSharding(self.mesh, P("dp", None))
                       if self.mesh is not None else None)

    @property
    def supports_admit_many(self) -> bool:
        """Batched fresh admission (admit_many): single-controller
        bucketed caches only — sp shards the prefill chunk over sequence
        (rows are not independent there), paged×dp needs per-slot
        owner/trash table routing the batched insert doesn't carry, and
        multi-host replay keeps to the single-admit programs."""
        return (self.sp_size == 1 and not self._multi
                and not (self.paged and self._paged_dp > 1))

    def _stack_keys(self, keys: List[Any]):
        """Stack per-slot replicated PRNG keys into one [m] key array
        (typed key arrays can't ride np.stack; a jitted stack with a
        replicated out-sharding can)."""
        fn = getattr(self, "_stack_keys_fn", None)
        if fn is None:
            if self._slot_sh is not None:
                fn = jax.jit(lambda *ks: jnp.stack(ks),
                             out_shardings=self._repl_sh)
            else:
                fn = jax.jit(lambda *ks: jnp.stack(ks))
            self._stack_keys_fn = fn
        return fn(*keys)

    def _sp_many(self, opts_list: Sequence[SlotOptions]):
        """[m]-row replicated SamplingParams (the batched twin of
        _sp_row)."""
        g = self._gr

        def arr(f, dt):
            return g(np.array([f(o) for o in opts_list], dt))
        return sampling.SamplingParams(
            temperature=arr(lambda o: o.temperature, np.float32),
            top_k=arr(lambda o: o.top_k, np.int32),
            top_p=arr(lambda o: o.top_p, np.float32),
            min_p=arr(lambda o: o.min_p, np.float32),
            typical_p=arr(lambda o: o.typical_p, np.float32),
            repeat_penalty=arr(lambda o: o.repeat_penalty, np.float32),
            presence_penalty=arr(lambda o: o.presence_penalty,
                                 np.float32),
            frequency_penalty=arr(lambda o: o.frequency_penalty,
                                  np.float32),
            mirostat=arr(lambda o: o.mirostat, np.int32),
            mirostat_tau=arr(lambda o: o.mirostat_tau, np.float32),
            mirostat_eta=arr(lambda o: o.mirostat_eta, np.float32))

    def _admit_many_jit(self, m: int):
        fn = self._admit_many_jits.get(m)
        if fn is None:
            fn = self._admit_many_make(m)
            self._admit_many_jits[m] = fn
        return fn

    def _admit_many_exec(self, m: int, bucket: int):
        exe = self._admit_many_execs.get((m, bucket))
        if exe is None:
            tokens = self._gr(np.zeros((m, bucket), np.int32))
            table_rows = (self._gr(np.zeros((m, self._nblk), np.int32))
                          if self.paged else None)
            gi = lambda a: self._gr(np.asarray(a, np.int32))  # noqa: E731
            exe = self._compile(
                "admit_many", (m, bucket), self._admit_many_jit(m),
                self.params, self.k_cache, self.v_cache, self.lengths,
                self.counts, self.last_tokens, self.pring, self.mu,
                tokens, gi(list(range(m))), gi([1] * m),
                self._sp_many([SlotOptions()] * m),
                self._stack_keys([self._dummy_key()] * m),
                self._mask_ones, gi([1] * m), table_rows)
            self._admit_many_execs[(m, bucket)] = exe
        return exe

    def admit_many(self, slots: Sequence[int], prompts: Sequence[Any],
                   opts_list: Optional[Sequence[SlotOptions]] = None
                   ) -> List[int]:
        """Admit several prompts padding to the SAME prefill bucket in one
        batched dispatch; returns each slot's first sampled token, in
        order. Token-stream-identical to m sequential admit() calls: the
        per-slot PRNG seeds derive from (slot, seq_len) exactly as in
        _prep_slot, and causal masking keeps each row's prefill
        independent of its batch mates. Grammar-constrained and
        multimodal requests take the single-admit path (the caller
        routes them there)."""
        return self.admit_many_launch(slots, prompts, opts_list).wait()

    def admit_many_launch(self, slots: Sequence[int],
                          prompts: Sequence[Any],
                          opts_list: Optional[Sequence[SlotOptions]] = None
                          ) -> AdmitHandle:
        """``admit_many`` without its wait (see ``admit_launch``): the
        handle's ``wait()`` yields each slot's first token, in order."""
        m = len(slots)
        assert m == len(prompts) >= 2, "admit_many wants >= 2 prompts"
        assert self.supports_admit_many, "unsupported engine mode"
        if opts_list is None:
            opts_list = [SlotOptions()] * m
        FAULTS.check("engine.admit")
        with span("engine.admit_many", m=m) as sp:
            return self._do_admit_many(slots, prompts, opts_list, sp.t0)

    def _do_admit_many(self, slots, prompts, opts_list,
                       t0: float) -> AdmitHandle:
        m = len(slots)
        ns = [int(np.asarray(p).shape[0]) for p in prompts]
        for s, n in zip(slots, ns):
            assert not self.active[s], f"slot {s} busy"
            if n >= self.max_seq:
                raise ValueError(f"prompt too long: {n} >= {self.max_seq}")
        bucket = self.bucket_for(max(ns))
        assert all(self.bucket_for(n) == bucket for n in ns), \
            "admit_many is per-bucket (caller groups by bucket)"
        tokens = np.zeros((m, bucket), np.int32)
        for i, (p, n) in enumerate(zip(prompts, ns)):
            tokens[i, :n] = np.asarray(p, np.int32)
        table_rows = None
        if self.paged:
            from .paged import PagesExhausted
            grown: List[int] = []
            try:
                for s, n in zip(slots, ns):
                    self._grow_for_admit(s, n)
                    grown.append(s)
            except PagesExhausted:
                # roll back so a sequential-fallback pass sees the pool
                # unchanged (the parked prefixes these slots may have
                # held are gone either way — the caller already popped
                # them from its reuse map)
                for s in grown:
                    self._pt.release(s)
                raise
            table_rows = self._gr(
                np.stack([self._pt.tables[s] for s in slots]))
        keys = []
        for s, o, n in zip(slots, opts_list, ns):
            key, _, _ = self._prep_slot(s, o, n, None)
            keys.append(key)
        gi = lambda a: self._gr(np.asarray(a, np.int32))  # noqa: E731
        with span("engine.upload"):
            sp_rows, keys_m = self._sp_many(opts_list), self._stack_keys(keys)
        (toks, self.k_cache, self.v_cache, self.lengths, self.counts,
         self.last_tokens, self.pring, self.mu) = \
            self._enqueue(
                "admit_many", self._admit_many_exec(m, bucket),
                self.params, self.k_cache, self.v_cache, self.lengths,
                self.counts, self.last_tokens, self.pring, self.mu,
                self._gr(tokens), gi(list(slots)), gi(ns),
                sp_rows, keys_m,
                self._mask_ones,
                gi([self._resolve_rln(o) for o in opts_list]), table_rows)
        for s, n, o in zip(slots, ns, opts_list):
            self.active[s] = True
            self._host_lengths[s] = n
            self._opts[s] = o
            self._repeat_n[s] = self._resolve_rln(o)
            if self.paged:
                self._admit_seq += 1
                self._admit_order[s] = self._admit_seq
        self._upload_slot_state()
        return AdmitHandle(self, toks, t0, "admit", slots)

    @property
    def supports_extend(self) -> bool:
        """Prefix-cache continuation: EVERY cache mode since round 3 —
        dense (incl. int8), sp sequence-sharded (tail compute replicates,
        writes scatter to the owning shard — _make_extend_sp), paged, and
        paged×dp (tail replicates across shards with owner-real/
        others-trash table rows and an owner-select psum —
        decoder.paged_extend_dp)."""
        return True

    def _canon_attn(self, A: int) -> int:
        """Paged extend programs depend only on ceil(A / page_size):
        canonicalize so byte-identical programs share one compile."""
        if not self.paged:
            return A
        ps = self.ecfg.page_size
        return -(-A // ps) * ps

    def _extend_jit(self, A: int):
        fn = self._extend_jits.get(A)
        if fn is None:
            fn = self._extend_make(A)
            self._extend_jits[A] = fn
        return fn

    def _extend_exec(self, bucket: int, A: int):
        A = self._canon_attn(A)
        exe = self._extend_execs.get((bucket, A))
        if exe is None:
            tokens = self._gr(np.zeros((1, bucket), np.int32))
            W = max(1, self.ecfg.repeat_last_n)
            zi = lambda v: self._gr(np.int32(v))  # noqa: E731
            args = [self.params, self.k_cache, self.v_cache, self.lengths,
                    self.counts, self.last_tokens, self.pring, self.mu,
                    tokens,
                    self._gr(np.zeros((W,), np.int32)), self._gr(
                        np.zeros((self.cfg.vocab_size,), np.int32)),
                    zi(0), zi(1), zi(1)]
            if self.paged and self._paged_dp > 1:
                rows = np.zeros((self._paged_dp, self._nblk), np.int32)
                args.append(self._g(rows, NamedSharding(
                    self.mesh, P("dp", None))))
                args.append(zi(0))            # owning shard index
            elif self.paged:
                args.append(self._gr(np.zeros((self._nblk,), np.int32)))
            args += [self._sp_row(SlotOptions()), self._dummy_key(),
                     self._mask_ones, zi(0), zi(W)]
            exe = self._compile("extend", (bucket, A),
                                self._extend_jit(A), *args)
            self._extend_execs[(bucket, A)] = exe
        return exe

    def extend(self, slot: int, full_ids: np.ndarray, start: int,
               opts: SlotOptions = SlotOptions(),
               mask_row: Optional[np.ndarray] = None) -> int:
        """Admit ``full_ids`` into ``slot`` reusing its cached first
        ``start`` positions (prefix cache); prefills only the tail.
        Returns the first sampled token. The caller guarantees the slot's
        cache holds K/V for ``full_ids[:start]`` (a parked sequence whose
        ids share that prefix — stale entries at positions >= start are
        never attended: masking is position-based and the tail overwrites
        them)."""
        return self.extend_launch(slot, full_ids, start, opts,
                                  mask_row).wait()[0]

    def extend_launch(self, slot: int, full_ids: np.ndarray, start: int,
                      opts: SlotOptions = SlotOptions(),
                      mask_row: Optional[np.ndarray] = None) -> AdmitHandle:
        """``extend`` without its wait (see ``admit_launch``)."""
        # same fault point as admit(): an extend IS an admission (prefix
        # reuse or a chunked-prefill piece), and chaos drills must reach
        # the chunked path through it
        FAULTS.check("engine.admit")
        with span("engine.extend") as sp:
            return self._do_extend(slot, full_ids, start, opts, mask_row,
                                   sp.t0)

    def _do_extend(self, slot, full_ids, start, opts, mask_row,
                   t0: float) -> AdmitHandle:
        assert not self.active[slot], f"slot {slot} busy"
        full_ids = np.asarray(full_ids, np.int32)
        n_total = int(full_ids.shape[0])
        n_new = n_total - start
        assert 0 < n_new, f"nothing to prefill (start={start})"
        if self.recurrent and start != self._host_lengths[slot]:
            raise ValueError(
                f"slot {slot} holds the state of "
                f"{int(self._host_lengths[slot])} positions and a "
                f"recurrent state cannot be cut back to {start}: prefill "
                "from the start instead")
        if n_total >= self.max_seq:
            raise ValueError(f"prompt too long: {n_total} >= {self.max_seq}")
        bucket = self.bucket_for(n_new)
        if start + bucket > self.max_seq:
            # tail positions run to start+bucket: dense writes there
            # directly; paged padding past the table would clamp into the
            # slot's LAST live page and corrupt the prefix (the forward
            # also trash-redirects out-of-table blocks as a second line
            # of defence)
            raise ValueError(
                f"tail bucket {bucket} does not fit above {start}")
        # attended-prefix bucket: the program slices/attends only the
        # first A cache positions, so continuation cost scales with the
        # conversation, not max_seq_len (sp always attends its full local
        # chunk — one program per tail bucket)
        attn_a = (self.bucket_for(start + bucket) if self._bucketed_attn
                  else self.max_seq)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n_new] = full_ids[start:]
        # penalty window over the full continuation prompt (host-built:
        # the parked ring may describe a divergent suffix), at the
        # REQUEST's effective window inside the static-W ring
        W = max(1, self.ecfg.repeat_last_n)
        rln = self._resolve_rln(opts)
        rmod = max(rln, 1)
        V = self.cfg.vocab_size
        ring = np.full((W,), V, np.int32)
        window = full_ids[max(0, n_total - rln):] if rln > 0 \
            else full_ids[:0]
        pos = np.arange(n_total - len(window), n_total)
        ring[pos % rmod] = window
        counts_row = np.zeros((V,), np.int32)
        np.add.at(counts_row, window, 1)
        key, mrow, cflag = self._prep_slot(slot, opts, n_total, mask_row)
        args = [self.params, self.k_cache, self.v_cache, self.lengths,
                self.counts, self.last_tokens, self.pring, self.mu,
                self._gr(tokens), self._gr(ring),
                self._gr(counts_row), self._gr(np.int32(slot)),
                self._gr(np.int32(start)), self._gr(np.int32(n_new))]
        if self.paged:
            from .paged import PagesExhausted
            ahead = min(n_total + self.ecfg.decode_chunk, self.max_seq)
            deficit = (self._pt.blocks_for(ahead)
                       - self._pt.owned_blocks(slot))
            self._make_room(slot, deficit)
            if deficit > self._pt.free_for(slot) \
                    or not self._pt.grow(slot, n_total):
                # the scheduler already popped this slot from its parked
                # map, so nothing will ever reuse or evict the prefix —
                # return its pages now or they leak until a fresh admit
                # happens to land on this slot (ADVICE r2)
                self._pt.release(slot)
                raise PagesExhausted(
                    f"extend to {n_total} tokens (+1 chunk headroom): "
                    f"{self._pt.n_free} pages free")
            if self._paged_dp > 1:
                # [dp, NBLK] owner-real/others-trash rows + owner index
                # (decoder.paged_extend_dp)
                args.append(self._table_row_dev(slot))
                args.append(self._gr(np.int32(self._pt.shard_of(slot))))
            else:
                args.append(self._gr(self._pt.tables[slot]))
        args += [self._sp_row(opts), key, mrow, cflag,
                 self._gr(np.int32(rln))]
        (tok, self.k_cache, self.v_cache, self.lengths, self.counts,
         self.last_tokens, self.pring, self.mu) = \
            self._enqueue("extend", self._extend_exec(bucket, attn_a),
                          *args)
        self._commit_slot(slot, n_total, opts)
        return AdmitHandle(self, tok, t0, "extend", (slot,))

    def _attn_bucket(self, n: int) -> int:
        """Static attended-prefix length covering every active slot for the
        next ``n`` steps: smallest bucket >= max(lengths) + n. Decode cache
        traffic scales with this, not with max_seq_len."""
        if not self._bucketed_attn:
            return self.max_seq
        need = int(self._host_lengths[self.active].max(initial=0)) + n
        for b in self._buckets:
            if need <= b:
                return b
        return self.max_seq

    def _pad_mask_row(self, row) -> np.ndarray:
        """Zero-pad a packed mask to the engine's width — ids beyond the
        grammar's token table are unknown to it and stay disallowed."""
        # lint: allow(host-sync-hot-path): grammar masks are host numpy state — no device transfer
        row = np.asarray(row, np.uint32)
        if row.shape[0] == self.mask_words:
            return row
        assert row.shape[0] < self.mask_words, (
            f"mask row of {row.shape[0]} words exceeds vocab "
            f"({self.mask_words} words)")
        out = np.zeros((self.mask_words,), np.uint32)
        out[:row.shape[0]] = row
        return out

    def set_mask(self, slot: int, row: np.ndarray, gid: int = -1):
        """Install the packed allowed-token mask for ``slot`` (applies from
        the next decode step; constrained until release/clear_mask).

        ``gid`` >= 0 additionally places the slot in DEVICE-grammar mode:
        its mask is read from the installed grammar table row ``gid`` and
        the automaton advances on device every sampled token, so the slot
        keeps the full decode_n chunk instead of one token per dispatch.
        The host row still installs as the fallback the device escapes
        to."""
        self._constrained[slot] = True
        self._gdev_mode[slot] = gid >= 0
        (self.mask_bits, self._constr_dev,
         self._gstate) = self._set_mask_fn(
            self.mask_bits, self._constr_dev, self._gstate,
            self._gr(np.int32(slot)), self._gr(self._pad_mask_row(row)),
            self._gr(np.int32(1)), self._gr(np.int32(gid)))

    def clear_mask(self, slot: int):
        if not self._constrained[slot]:
            return
        self._constrained[slot] = False
        self._gdev_mode[slot] = False
        (self.mask_bits, self._constr_dev,
         self._gstate) = self._set_mask_fn(
            self.mask_bits, self._constr_dev, self._gstate,
            self._gr(np.int32(slot)), self._mask_ones,
            self._gr(np.int32(0)), self._gr(np.int32(-1)))

    def install_grammar(self, key: Any, mask: np.ndarray,
                        trans: np.ndarray) -> bool:
        """Upload a precomputed grammar program (ops/constrain.py
        GrammarTable.mask/.trans) to the device tables. ``key`` identifies
        the table; a matching key is a no-op. Returns False — scheduler
        falls back to host masks — when a DIFFERENT table is live while
        any slot is still in device mode (swapping it under them would
        corrupt their automata). Rows/cols beyond the static
        [TPU_GRAMMAR_STATES, vocab] capacity truncate; transitions into
        truncated states were already -1 (escape) in the table."""
        if not self._grammar_device:
            return False
        if self._gtable_key == key:
            return True
        if self._gdev_mode.any():
            return False
        G, V = self._gstates_cap, self.cfg.vocab_size
        # lint: allow(host-sync-hot-path): grammar tables arrive as host numpy; upload is once per grammar, not per dispatch
        mask = np.asarray(mask, np.uint32)[:G]
        trans = np.asarray(trans, np.int32)[:G]  # lint: allow(host-sync-hot-path): host numpy staging for device_put
        m = np.zeros((G, self.mask_words), np.uint32)
        m[:mask.shape[0], :min(mask.shape[1], self.mask_words)] = \
            mask[:, :self.mask_words]
        t = np.full((G, V), -1, np.int32)
        t[:trans.shape[0], :min(trans.shape[1], V)] = trans[:, :V]
        # a transition into a state id beyond capacity escapes
        t[t >= G] = -1
        self._gmask_dev = self._gr(m)
        self._gtrans_dev = self._gr(t)
        self._gtable_key = key
        return True

    def _tables_dev(self):
        if not self.paged:
            return None
        return self._g(self._pt.tables,
                       self._slot_sh2 if self.mesh is not None else None)

    def decode(self) -> np.ndarray:
        """One decode step for every slot; returns sampled tokens [B] (only
        entries where self.active were valid at call time)."""
        if self.paged:
            victims = self.prepare_decode(1)
            if victims:
                from .paged import PagesExhausted
                raise PagesExhausted(f"pool dry; victims {victims}")
        (toks, self.k_cache, self.v_cache, self.lengths, self.counts,
         self.last_tokens, self.pring, self.mu, self.keys,
         self._gstate) = self._decode_fn(
            self.params, self.k_cache, self.v_cache, self.lengths,
            self.counts, self.last_tokens, self.pring, self.mu, self.sp,
            self.keys, self._active_dev, self.mask_bits, self._constr_dev,
            self._rln_dev, self._gstate, self._gmask_dev,
            self._gtrans_dev, self._tables_dev())
        self._host_lengths[self.active] += 1
        self._count_sampler_steps(1)
        return self._fetch(toks)

    def _count_sampler_steps(self, n: int,
                             budgets: Optional[np.ndarray] = None) -> str:
        """Count a launched dispatch's ``n`` decode steps in
        ``tpu_model_decode_steps_total{sampler=...}`` by the branch
        ``sampling.sample`` takes in each: the device's predicate over the
        host's mirror of its two arrays, the active slots' temperatures
        and, past the first step, the slots whose budget runs on. (A slot
        the device grammar froze mid-chunk is the one thing the mirror
        cannot see.) Returns the first step's label: the costlier, since
        the later steps' live slots are among the first's."""
        temps = np.zeros((self.n_slots,), np.float32)
        for s, o in self._opts.items():
            temps[s] = o.temperature
        first = sampling.needs_candidates(temps, self.active)
        rest = first if budgets is None else sampling.needs_candidates(
            temps, self.active & (budgets > 1))
        for steps, needed in ((1, first), (n - 1, rest)):
            if steps:
                METRICS.inc("tpu_model_decode_steps_total", float(steps),
                            '{sampler="%s"}' % (
                                "candidates" if needed else "argmax"))
        return "candidates" if first else "argmax"

    def _count_index_positions(self, budgets: np.ndarray) -> None:
        """A launched chunk's decode steps into
        ``tpu_model_index_positions_total``: step j of an active slot of
        n cached positions has n + j + 1 before it (its own counted) and
        its attention reads at most ``index_topk`` of them; the same in
        every layer, so a layer is counted. From the host's lengths, before
        the launch advances them."""
        n0 = self._host_lengths[self.active].astype(np.int64)
        b = budgets[self.active]
        steps = np.arange(b.max(initial=0))[None, :]
        n = np.where(steps < b[:, None], n0[:, None] + 1 + steps, 0)
        seen, kept = n.sum(), np.minimum(n, self.cfg.index_topk).sum()
        METRICS.inc("tpu_model_index_positions_total", float(seen),
                    '{what="seen"}')
        METRICS.inc("tpu_model_index_positions_total", float(kept),
                    '{what="kept"}')

    @staticmethod
    def _count_expert_tokens(load: np.ndarray) -> None:
        """A decode chunk's picks per expert of the router, over all
        layers, into ``tpu_model_moe_expert_tokens_total{expert=...}``."""
        for e, n in enumerate(load):
            if n:
                METRICS.inc("tpu_model_moe_expert_tokens_total", float(n),
                            f'{{expert="{e}"}}')

    def _note_compile(self, kind: str, key: Any) -> None:
        """Called from every executable-cache miss. While warm_buckets is
        running the signature is merely registered; outside it, a miss is
        a mid-serving XLA compile paid inside a timed dispatch — count it
        (once per signature) and drop a flight-recorder event."""
        sig = (kind, key)
        if self._warming:
            self._warmed_sigs.add(sig)
            return
        if sig in self._warmed_sigs:
            return
        self._warmed_sigs.add(sig)
        self.recompiles[kind] = self.recompiles.get(kind, 0) + 1
        METRICS.inc("tpu_model_recompiles_total", 1.0, f'{{kind="{kind}"}}')
        FLIGHT.record("recompile", program=kind, key=str(key))

    def _compile(self, kind: str, key: Any, jit_fn, *args):
        """Lower and compile one program of the warm plan, keeping which
        kernel each attention/matmul dispatcher picked while it traced
        (``program_kernels``). A dispatcher that wanted a pallas kernel
        and gave way because the shapes do not tile is reported once for
        the program: a line on stderr and a flight-recorder event."""
        self._note_compile(kind, key)
        with record_kernels() as picked:
            lowered = jit_fn.lower(*args)
        exe = lowered.compile()
        self.program_kernels[(kind, key)] = tuple(sorted(
            {f"{site}={kernel}" for site, kernel, _fb in picked}))
        for site, kernel, fell_back in picked:
            if fell_back:
                print(f"engine: program {kind}{key}: {site} fell back to "
                      f"{kernel} (shapes do not tile for the pallas "
                      f"kernel)", file=sys.stderr)
                FLIGHT.record("kernel_fallback", program=kind,
                              key=str(key), site=site, took=kernel)
        return exe

    def kernels_by_kind(self) -> Dict[str, List[str]]:
        """{program kind: sorted "site=kernel" choices over every compiled
        program of that kind} — what the warm-plan flight event
        (GET /debug/events?kind=warm_plan) reports, so the kernels a pod
        serves with can be read, not inferred."""
        out: Dict[str, set] = {}
        for (kind, _key), picks in self.program_kernels.items():
            out.setdefault(kind, set()).update(picks)
        return {kind: sorted(picks) for kind, picks in sorted(out.items())}

    def _decode_n_exec(self, n: int, attn_len: int):
        key = (n, attn_len)
        exe = self._decode_execs.get(key)
        if exe is None:
            budgets = self._g(np.full((self.n_slots,), n, np.int32),
                              self._slot_sh)
            exe = self._compile(
                "decode", key, self._decode_n_fn,
                self.params, self.k_cache, self.v_cache, self.lengths,
                self.counts, self.last_tokens, self.pring, self.mu,
                self.sp, self.keys, self._active_dev, self.mask_bits,
                self._constr_dev, self._rln_dev, self._gstate,
                self._gmask_dev, self._gtrans_dev, n, attn_len,
                self._tables_dev(), budgets)
            self._decode_execs[key] = exe
        return exe

    def _admit_exec(self, bucket: int):
        exe = self._admit_execs.get(bucket)
        if exe is None:
            tokens = self._gr(np.zeros((1, bucket), np.int32))
            if not self.paged:
                table_row = None
            elif self._paged_dp > 1:
                table_row = self._g(
                    np.zeros((self._paged_dp, self._nblk), np.int32),
                    NamedSharding(self.mesh, P("dp", None))
                    if self.mesh is not None else None)
            else:
                table_row = self._gr(np.zeros((self._nblk,), np.int32))
            zi = lambda v: self._gr(np.int32(v))  # noqa: E731
            exe = self._compile(
                "admit", bucket, self._admit_fn,
                self.params, self.k_cache, self.v_cache, self.lengths,
                self.counts, self.last_tokens, self.pring, self.mu,
                tokens, zi(0), zi(1),
                self._sp_row(SlotOptions()), self._dummy_key(),
                self._mask_ones, zi(0), zi(1),
                table_row)
            self._admit_execs[bucket] = exe
        return exe

    def warm_buckets(self, n: Optional[int] = None, *,
                     ctx_lo: Optional[int] = None,
                     ctx_hi: Optional[int] = None,
                     full: bool = True):
        """Public warm entry: every executable compiled inside is
        registered as an AOT-warmed signature (not a recompile) — the
        recompile detector only counts cache misses OUTSIDE this scope.
        See _warm_buckets for the warm plan itself."""
        from .compile_cache import watch
        prev = self._warming
        self._warming = True
        n_before = len(self.program_kernels)
        t0 = time.perf_counter()
        try:
            with watch() as cache:
                self._warm_buckets(n, ctx_lo=ctx_lo, ctx_hi=ctx_hi,
                                   full=full)
        finally:
            self._warming = prev
        FLIGHT.record(
            "warm_plan", programs=len(self.program_kernels) - n_before,
            seconds=round(time.perf_counter() - t0, 3),
            cache_hits=cache["hits"], cache_misses=cache["misses"],
            kernels=self.kernels_by_kind())

    def _warm_buckets(self, n: Optional[int] = None, *,
                      ctx_lo: Optional[int] = None,
                      ctx_hi: Optional[int] = None,
                      full: bool = True):
        """AOT-compile the chunked decode program for every attention
        bucket AND the admission program for every prefill bucket, so
        serving never pays an XLA compile mid-request. Non-bucketed paths
        (sp meshes) only ever decode at max_seq — one program, not a
        duplicate per bucket.

        ``ctx_lo``/``ctx_hi`` bound the context lengths the caller will
        actually reach, restricting the decode warm to the reachable
        attention buckets (smallest covering ctx_lo+n .. smallest covering
        ctx_hi) — the bench uses this so a capture doesn't pay compiles for
        buckets it never decodes in. ``full=False`` additionally skips the
        single-step, admission and extend warms (lazy compile covers
        a first use; a server must never take that hit mid-request, a bench
        capture may)."""
        n = n or self.ecfg.decode_chunk
        buckets = self._buckets if self._bucketed_attn else [self.max_seq]
        if self._bucketed_attn and (ctx_lo is not None
                                    or ctx_hi is not None):
            lo = self.bucket_for(min((ctx_lo or 0) + n, self.max_seq))
            hi = self.bucket_for(min(ctx_hi, self.max_seq)) \
                if ctx_hi else self.max_seq
            buckets = [b for b in buckets if lo <= b <= hi] or [hi]
        for b in buckets:
            self._decode_n_exec(n, b)
            if n != 1 and full:
                # grammar-constrained serving steps one token per dispatch
                # (scheduler drops to decode_n(1)) — warm those too
                self._decode_n_exec(1, b)
        if not full:
            return
        for b in self._buckets:
            self._admit_exec(b)
        if self.supports_admit_many:
            # batched-admission programs for the group sizes the
            # scheduler forms (see Scheduler._admit_waiting)
            for b in self._buckets:
                for m in (2, 4):
                    if m <= self.n_slots:
                        self._admit_many_exec(m, b)
        if self.supports_extend:
            # (tail, attended) bucket pairs; the max_seq tail bucket is
            # unreachable (extend requires start >= 1 and start + bucket
            # <= max_seq), and the attended bucket covers start + tail so
            # A >= the tail bucket — O(log² max_seq) programs. sp extends
            # ignore A entirely (extend() always passes max_seq there):
            # one program per tail bucket, not a pair matrix.
            for b in self._buckets:
                if b >= self.max_seq:
                    continue
                attns = ([a for a in self._buckets if a > b]
                         if self._bucketed_attn else [self.max_seq])
                for a in attns:
                    self._extend_exec(b, a)

    # --- warm-snapshot (scale-to-zero fast cold-start) -----------------
    def _exec_cache_items(self):
        """Yield ((kind, key), executable) over every AOT exec cache —
        the same (kind, key) vocabulary _note_compile registers."""
        for key, exe in self._decode_execs.items():
            yield ("decode", key), exe
        for b, exe in self._admit_execs.items():
            yield ("admit", b), exe
        for k, exe in self._admit_many_execs.items():
            yield ("admit_many", k), exe
        for k, exe in self._extend_execs.items():
            yield ("extend", k), exe

    def _install_exec(self, sig, exe) -> bool:
        kind, key = sig
        if kind == "decode":
            self._decode_execs[key] = exe
        elif kind == "admit":
            self._admit_execs[key] = exe
        elif kind == "admit_many":
            self._admit_many_execs[key] = exe
        elif kind == "extend":
            self._extend_execs[key] = exe
        else:
            return False
        return True

    def _compile_sig(self, sig) -> bool:
        """Recompile one recorded warm signature through its normal
        cache-miss path. Only ever called inside the warming scope, so
        the recompile counter stays untouched by construction."""
        kind, key = sig
        if kind == "decode":
            self._decode_n_exec(*key)
        elif kind == "admit":
            self._admit_exec(key)
        elif kind == "admit_many" and self.supports_admit_many:
            self._admit_many_exec(*key)
        elif kind == "extend":
            self._extend_exec(*key)
        else:
            # a signature this configuration disallows (batched admission
            # on a mesh that has none) or a kind this build no longer has
            # (an older snapshot's) is skipped; a compile that FAILS is
            # not caught here
            return False
        return True

    def warm_snapshot(self) -> bytes:
        """Serialize the AOT warm state: every warmed (kind, key)
        signature plus — where the backend supports it — the compiled
        executables themselves (jax.experimental.serialize_executable).
        Saved to the image-store PVC at drain time so a scale-to-zero
        wake restores warmth instead of recompiling the warm plan.

        Executable payloads are per-entry best-effort: an entry that
        fails to serialize is covered by its recorded signature (restore
        recompiles it inside the warming scope — slower wake, identical
        recompile-counter outcome of zero).

        Payloads default to accelerator backends only: the XLA CPU
        executable-deserialization path miscompiles on some hosts (the
        same instability that keeps the persistent compile cache opt-in
        for tests), and on CPU a sig replay is cheap anyway.
        TPU_WARM_SNAPSHOT_EXECS=1 forces payloads on, =0 forces off."""
        import os as _os
        import pickle
        execs = {}
        if self._snapshot_execs_ok():
            try:
                from jax.experimental import serialize_executable as _se
            except ImportError:
                _se = None
            if _se is not None:
                for sig, exe in self._exec_cache_items():
                    try:
                        payload, in_tree, out_tree = _se.serialize(exe)
                        execs[sig] = (payload,
                                      pickle.dumps((in_tree, out_tree)))
                    except Exception:  # lint: allow(exception-hygiene): sig replay covers a lost executable
                        continue
        return pickle.dumps(
            {"version": 1,
             "jax": jax.__version__,
             "backend": jax.default_backend(),
             "sigs": sorted(self._warmed_sigs, key=repr),
             "execs": execs},
            protocol=pickle.HIGHEST_PROTOCOL)

    @staticmethod
    def _snapshot_execs_ok() -> bool:
        """Tri-state TPU_WARM_SNAPSHOT_EXECS: unset = executable
        payloads on accelerator backends only (CPU deserialization is
        unstable on some hosts), "1" forces on, "0" forces off."""
        import os as _os
        want = _os.environ.get("TPU_WARM_SNAPSHOT_EXECS", "")
        if want == "0":
            return False
        return want == "1" or jax.default_backend() != "cpu"

    def restore_warm(self, blob: bytes) -> Dict[str, int]:
        """Install a warm_snapshot() blob into a fresh engine: load
        serialized executables where the backend/version still match,
        then recompile any remaining signatures inside the warming scope.
        Either way the engine comes up with the full warm plan registered
        and `tpu_model_recompiles_total` untouched — the scale-to-zero
        wake contract. Returns {"restored": n, "compiled": n}."""
        import pickle
        snap = pickle.loads(blob)
        if int(snap.get("version") or 0) != 1:
            raise ValueError("unknown warm snapshot version")
        restored = compiled = 0
        prev = self._warming
        self._warming = True
        try:
            execs = snap.get("execs") or {}
            compat = (snap.get("jax") == jax.__version__
                      and snap.get("backend") == jax.default_backend())
            # same tri-state as the save side: a CPU wake never
            # deserializes executables unless explicitly forced — the
            # sigs below cover every entry either way
            if execs and compat and self._snapshot_execs_ok():
                try:
                    from jax.experimental import serialize_executable as _se
                except ImportError:
                    _se = None
                if _se is not None:
                    for sig, (payload, trees) in execs.items():
                        try:
                            in_tree, out_tree = pickle.loads(trees)
                            exe = _se.deserialize_and_load(
                                payload, in_tree, out_tree)
                        except Exception:  # lint: allow(exception-hygiene): falls through to the recompile path
                            continue       # to the recompile path below
                        if self._install_exec(sig, exe):
                            self._warmed_sigs.add(sig)
                            restored += 1
            for sig in snap.get("sigs") or []:
                sig = (sig[0], tuple(sig[1]) if isinstance(sig[1], list)
                       else sig[1])
                if sig in self._warmed_sigs:
                    continue
                if self._compile_sig(sig):
                    compiled += 1
        finally:
            self._warming = prev
        FLIGHT.record("warm_restore", restored=restored, compiled=compiled)
        return {"restored": restored, "compiled": compiled}

    def prepare_decode(self, n: Optional[int] = None) -> list:
        """Paged mode: grow every active slot's block table to cover
        lengths + n upcoming tokens (pages must exist BEFORE the chunk —
        steps advance device-side with no host round-trip). Grows in
        admission order, so when the pool runs dry the NEWEST slots fail;
        returns them (newest first) for the scheduler to preempt/requeue.
        Engine state is untouched for victims. [] in dense mode."""
        if not self.paged:
            return []
        n = n or self.ecfg.decode_chunk
        order = sorted((s for s in range(self.n_slots) if self.active[s]),
                       key=lambda s: self._admit_order[s])
        # clamp at max_seq: a slot finishing its context within the chunk
        # over-decodes into its last page (same as the dense cache's
        # over-decode-then-release semantics), never past the table
        victims = []
        for s in order:
            upto = min(int(self._host_lengths[s]) + n,  # lint: allow(host-sync-hot-path): host shadow of slot lengths
                       self.max_seq)
            # a dry pool first takes the cached pages that are free at
            # once (_make_room); only then is the slot a victim
            self._make_room(s, self._pt.blocks_for(upto)
                            - self._pt.owned_blocks(s))
            if not self._pt.grow(s, upto):
                victims.append(s)
        victims.reverse()
        return victims

    def can_admit(self, slot: int, n_tokens: int) -> bool:
        """Would admitting ``n_tokens`` into ``slot`` find enough pages in
        its allocation domain (the slot's dp-shard sub-pool)? Admission
        releases the slot's own parked pages first, so they count as
        available. Dense mode: always True (the scheduler uses this to
        steer admissions toward dp shards that still have pages)."""
        if not self.paged:
            return True
        ahead = min(n_tokens + self.ecfg.decode_chunk, self.max_seq)
        return (self._pt.blocks_for(ahead)
                <= self._pt.free_for(slot) + self._pt.owned_blocks(slot))

    def admissible(self, n_tokens: int) -> bool:
        """Could a prompt of n_tokens EVER be admitted (whole pool free)?
        Dense mode always True — length limits are checked elsewhere."""
        if not self.paged:
            return True
        ahead = min(n_tokens + self.ecfg.decode_chunk, self.max_seq)
        return self._pt.blocks_for(ahead) <= self._pt.data_pages

    def free_slot_pages(self, slot: int):
        """Drop a PARKED (inactive) slot's pages back to the pool — the
        scheduler evicts prefix caches with this when admissions or decode
        growth run out of pages."""
        if self.paged:
            assert not self.active[slot], "freeing pages of an active slot"
            self._pt.release(slot)

    @property
    def free_pages(self) -> int:
        return self._pt.n_free if self.paged else -1

    # ------------------------------------------------------------------
    # radix prefix cache (paged, single sub-pool)
    # ------------------------------------------------------------------
    @property
    def radix_enabled(self) -> bool:
        return self._radix is not None

    @property
    def radix_nodes(self) -> int:
        """Resident radix-tree nodes (0 when the cache is off)."""
        return self._radix.n_nodes if self._radix is not None else 0

    @property
    def radix_pages(self) -> int:
        """Physical pages pinned by the radix tree (tier-0 nodes only —
        tier-1 nodes hold host bytes, not pool pages)."""
        return self._radix.n_pages if self._radix is not None else 0

    @property
    def radix_hosted(self) -> int:
        """Radix nodes whose KV lives in the host arena (tier 1)."""
        return self._radix.n_hosted if self._radix is not None else 0

    # -- tier-1 host arena occupancy (0 everywhere when the tier is off)
    @property
    def host_cache_enabled(self) -> bool:
        return self._arena is not None

    @property
    def host_cache_used_bytes(self) -> int:
        return self._arena.used_bytes if self._arena is not None else 0

    @property
    def host_cache_capacity_bytes(self) -> int:
        return self._arena.capacity_bytes if self._arena is not None else 0

    @property
    def host_cache_pages(self) -> int:
        return self._arena.n_entries if self._arena is not None else 0

    @property
    def host_page_bytes(self) -> int:
        """Nominal host bytes per spilled page (0 when the tier is off)."""
        return self._host_page_bytes

    def prefix_probe(self, full_ids) -> int:
        """Non-mutating: how many leading tokens of ``full_ids`` the radix
        cache could serve (full pages + one partial boundary page), capped
        at len-1 so at least one tail token remains to prefill. The
        scheduler uses this to apply its reuse floor and bucket-fit checks
        BEFORE committing to a stitch. 0 when the cache is off or cold.
        Tier-1 (host-spilled) pages count as servable — ``stitch`` may
        still choose to recompute them if the break-even model says the
        copy is dearer than the prefill."""
        return self.prefix_probe_tier(full_ids)[0]

    def prefix_probe_tier(self, full_ids):
        """Tier-aware probe: ``(servable_tokens, tier)`` where ``tier``
        is the WORST tier on the matched path — 0 = fully HBM-hot,
        1 = needs a host-arena restitch, 2 = needs a restitch of
        fleet-snapshot pages. The gateway prefers lower tiers on
        matched-length ties so affinity stays truthful across replica
        wake (a just-woken replica answers 2, a hot one 0)."""
        if self._radix is None:
            return 0, 0
        ids = np.asarray(full_ids)
        full, part, q = self._radix.match(ids, int(ids.shape[0]) - 1,
                                          bump=False)
        tier = 0
        for n in full:
            if n.tier != 0:
                tier = max(tier, 2 if (n.host is not None
                                       and n.host.snapshot) else 1)
        if part is not None and q > 0 and part.tier != 0:
            tier = max(tier, 2 if (part.host is not None
                                   and part.host.snapshot) else 1)
        return len(full) * self.ecfg.page_size + q, tier

    def stitch(self, slot: int, full_ids, max_reuse: int) -> int:
        """Map the radix cache's longest prefix of ``full_ids`` (at most
        ``max_reuse`` tokens) into ``slot``'s block table ahead of an
        extend(): whole-page hits are shared READ-ONLY (refcount bump, no
        copy, no compute); a partially-matched boundary page is copied
        into a private page first (copy-on-write) because the tail
        prefill will write the remaining positions of that very page.
        Any pages the slot still held (stale parked prefix) are dropped
        first. Returns the reuse length actually stitched (0 = cold).
        Raises PagesExhausted when a page cannot be allocated — the
        slot is left with NO pages so the caller can fall back cleanly.
        Deterministic from call order, so follower replay stays in step.

        Tiered KV cache (ISSUE 18): the matched path splits into a
        leading tier-0 run (shared read-only, as before) and a tier-1
        run of host-spilled pages. When the break-even model says the
        host→HBM copy beats recomputing the run, each tier-1 page is
        RESTITCHED — a private page is grown, the upload is enqueued
        (async, overlapping the tail prefill) and the node is promoted
        back to tier 0 so later requests share the fresh page. Short
        runs recompute (counted as tiered misses). An armed
        ``pages.restitch`` fault aborts the stitch into the same clean
        pageless state as pool exhaustion; already-promoted nodes stay
        valid because their uploads were already enqueued.
        ``last_stitch`` records the per-tier token breakdown for the
        scheduler's metrics."""
        assert self._radix is not None, "radix cache disabled"
        assert not self.active[slot], f"slot {slot} busy"
        from .faults import InjectedFault
        from .paged import PagesExhausted
        self._pt.release(slot)
        ls = self.last_stitch = {"t0": 0, "t1": 0, "t2": 0,
                                 "skip1": 0, "skip2": 0}
        ids = np.asarray(full_ids, np.int32)
        cap = min(int(max_reuse), int(ids.shape[0]) - 1)
        if cap <= 0:
            return 0
        full, part, q = self._radix.match(ids, cap, bump=True)
        if not full and q == 0:
            return 0
        ps = self.ecfg.page_size
        # split the matched path: shareable tier-0 run, then the
        # restitchable tier-1 run (paths are tier0* then tier1*)
        t0run, t1run = [], []
        for n in full:
            if n.tier == 0 and not t1run:
                t0run.append(n)
            elif n.tier != 0:
                t1run.append(n)
            else:     # tier-0 below tier-1: unreachable by invariant
                break
        self._pt.map_shared(slot, [n.page for n in t0run])
        reuse = len(t0run) * ps
        ls["t0"] = reuse
        restitch = False
        if t1run and self._arena is not None:
            from .host_cache import worth_restitch
            restitch = worth_restitch(
                self.cfg, reuse, len(t1run) * ps,
                sum(n.host.nbytes for n in t1run))
        skipped = bool(t1run) and not restitch
        if skipped:
            # break-even says recompute: the run stays spilled, the tail
            # prefill regenerates those positions (a tiered miss)
            for n in t1run:
                ls["skip2" if n.host.snapshot else "skip1"] += ps
            t1run = []
        # make room for the planned uploads and the copy-on-write page
        # BEFORE enqueuing any of them: at this point no restitch program
        # is in flight, so eviction can still spill victims to the host
        # tier (mid-stitch the epoch has advanced and a dry pool would
        # plainly free them instead). Only leaves that are free at once
        # (_make_room). The probe just bumped the matched path MRU, so
        # LRU victims are other prefixes — never the run being
        # restitched.
        self._make_room(slot, len(t1run) + (1 if part is not None and q > 0
                                            and not skipped else 0))
        try:
            for node in t1run:
                was_snap = node.host.snapshot
                dst = self._upload_host(slot, node.host.kv, reuse + ps)
                self._pt.pin(dst)
                self._arena.free(self._radix.mark_promoted(node, dst))
                reuse += ps
                ls["t2" if was_snap else "t1"] += ps
            # boundary page: COW from a tier-0 partial, or a PRIVATE
            # host upload from a tier-1 partial (no promotion — the tail
            # prefill writes this page's remaining positions, so the
            # tree keeps its spilled copy). A skipped tier-1 run makes
            # the boundary unreachable (its prefix wasn't stitched).
            if part is not None and q > 0 and not skipped:
                if part.tier == 0:
                    if not self._pt.grow(slot, reuse + q):
                        self._pt.release(slot)
                        raise PagesExhausted(
                            f"no page for the copy-on-write boundary "
                            f"({self._pt.n_free} free)")
                    dst = self._pt.slot_pages(slot)[-1]
                    self.k_cache, self.v_cache = self._copy_page_fn(
                        self.k_cache, self.v_cache,
                        self._gr(np.int32(part.page)),
                        self._gr(np.int32(dst)))
                    reuse += q
                    ls["t0"] += q
                elif self._arena is not None:
                    from .host_cache import worth_restitch
                    if worth_restitch(self.cfg, reuse, q,
                                      part.host.nbytes):
                        self._upload_host(slot, part.host.kv, reuse + q)
                        reuse += q
                        ls["t2" if part.host.snapshot else "t1"] += q
                    else:
                        ls["skip2" if part.host.snapshot
                           else "skip1"] += q
        except InjectedFault as e:
            # chaos (pages.restitch): abort into the same pageless state
            # as pool exhaustion — the caller cold-admits cleanly
            self._pt.release(slot)
            raise PagesExhausted(f"restitch aborted: {e}")
        return reuse

    def _upload_host(self, slot: int, kv, n_tokens: int) -> int:
        """Grow one private page for ``slot`` and enqueue the host→HBM
        upload of a spilled page's bytes into it. The jitted update is
        async — it overlaps the tail prefill's host-side work and the
        donated-cache dependency chain orders it before any program
        that reads the page. Returns the page id."""
        from .paged import PagesExhausted
        FAULTS.check("pages.restitch")
        if not self._pt.grow(slot, n_tokens):
            self._pt.release(slot)
            raise PagesExhausted(
                f"no page for tier-1 restitch ({self._pt.n_free} free)")
        dst = self._pt.slot_pages(slot)[-1]
        kp = jax.tree_util.tree_map(self._gr, kv[0])
        vp = jax.tree_util.tree_map(self._gr, kv[1])
        self.k_cache, self.v_cache = self._upload_page_fn(
            self.k_cache, self.v_cache, kp, vp, self._gr(np.int32(dst)))
        return dst

    def donate_prefix(self, slot: int, token_ids) -> int:
        """Insert ``slot``'s full-page-aligned KV prefix for ``token_ids``
        into the radix tree, then release the slot. Chunks the tree did
        not yet hold adopt the slot's physical pages (pinned — they
        survive the release); chunks already cached keep the tree's
        existing page and the slot's duplicate goes back to the pool.
        Replaces slot-parking in radix mode: any number of later requests
        can stitch the prefix concurrently. Returns tokens donated."""
        if self._radix is None:
            self.release(slot)
            return 0
        # lint: allow(host-sync-hot-path): token ids arrive as host lists
        ids = np.asarray(token_ids, np.int32)
        ps = self.ecfg.page_size
        # lint: allow(host-sync-hot-path): shape read of a host array
        k = min(int(ids.shape[0]) // ps, self._pt.owned_blocks(slot))
        if k > 0:
            adopted = self._radix.insert(ids[:k * ps],
                                         self._pt.slot_pages(slot)[:k])
            for node in adopted:
                self._pt.pin(node.page)
            if self._arena is not None:
                # chunks the donor re-materialised while spilled got
                # promoted back to tier 0: retire their host bytes
                self._arena.free_all(self._radix.take_dropped_hosts())
        self.release(slot)
        return k * ps

    def radix_evict(self, n_pages: int = 1, at_once: bool = False) -> int:
        """Evict up to ``n_pages`` least-recently-used radix leaves whose
        pages no slot currently maps, page-by-page (children before
        parents), returning their pages to the pool. Replaces the
        all-or-nothing parked-slot eviction. Returns pages evicted.

        A page whose last slot mapping went at a retired epoch reaches
        the free list at once, with a chunk in flight too: no program in
        flight holds it in a block table (runtime/paged.py: the fence's
        stamp is taken at the unmap, not at this unpin). Any other goes
        to the quarantine. ``at_once`` passes those over: what an
        allocation asks for that needs its pages now (_make_room).
        tpu_model_radix_evicted_pages_total counts a page either way
        (fence=free|fenced).

        With the host arena on (TPU_HOST_CACHE_GB > 0) an evicted page
        is SPILLED to the host tier first — but only while the epoch
        fence is quiescent (no launched dispatch un-retired: a host copy
        must never race in-flight device writes) and the arena has room
        after dropping LRU tier-1 entries. Otherwise the page is plainly
        freed, pruning any tier-1 descendants with it so every resident
        path stays rooted. Spill decisions are pure functions of
        mirrored state, so follower replay spills identically."""
        if self._radix is None:
            return 0

        def evictable(pg):
            return (self._pt.shared_refs(pg) == 0
                    and not (at_once and self._pt.fenced(pg)))

        if self._arena is None:
            pages = self._radix.evict(n_pages, evictable)
            self._unpin_evicted(pages)
            return len(pages)
        freed = 0
        while freed < n_pages:
            node = self._radix.spill_lru(evictable)
            if node is None:
                break
            if self._pt.quiescent and self._spill_node(node):
                freed += 1
                continue
            pages, hosts = self._radix.remove(node)
            self._arena.free_all(hosts)
            self._unpin_evicted(pages)
            freed += len(pages)
        return freed

    def _unpin_evicted(self, pages):
        """Drop the tree's pin on pages it just evicted, counting each by
        whether the fence let it reach the free list at once."""
        for pg in pages:
            METRICS.inc("tpu_model_radix_evicted_pages_total", 1.0,
                        _EVICT_FENCE[self._pt.unpin(pg)])

    def _spill_node(self, node) -> bool:
        """Move one radix node's page into the host arena (tier 0 → 1).
        False = the caller falls back to a plain eviction (arena full
        even after an LRU drop, or the ``pages.spill`` chaos point
        fired). Caller guarantees the fence is quiescent, so the
        ``device_get`` here captures stable bytes; it runs on the
        admission/eviction path only, never the dispatch hot loop."""
        from .faults import InjectedFault
        if not self._arena.room_for(1):
            self._arena.free_all(self._radix.drop_host_lru(1))
        if not self._arena.room_for(1):
            return False
        try:
            FAULTS.check("pages.spill")
        except InjectedFault:
            return False
        kp, vp = self._gather_page_fn(self.k_cache, self.v_cache,
                                      self._gr(np.int32(node.page)))
        # lint: allow(host-sync-hot-path): only while the fence is quiescent — no dispatch is in flight for the copy to stall
        kv = jax.device_get((kp, vp))
        pg = self._radix.mark_spilled(node, self._arena.store(kv))
        self._unpin_evicted([pg])
        self.n_spilled_pages += 1
        METRICS.inc("tpu_model_spilled_pages_total")
        return True

    def radix_reset(self):
        """Drop the whole radix tree (supervised restart: cache contents
        are unknown after a failed step, so nothing may be reused).
        Tier-1 state dies with the tree — a restarted engine never
        restitches bytes whose provenance it can no longer trust."""
        if self._radix is None:
            return
        for pg in self._radix.reset():
            self._pt.unpin(pg)
        if self._arena is not None:
            self._arena.clear()

    # ------------------------------------------------------------------
    # tier-2 fleet prefix snapshots (gguf/store.py persistence)
    # ------------------------------------------------------------------
    def export_prefixes(self, max_bytes: int = 64 << 20):
        """Serialize the hottest radix prefixes (any tier) into a
        self-contained snapshot blob, most-recently-used first within
        ``max_bytes`` (a child only ships if its parent made the cut,
        so every shipped path is rooted). Tier-0 pages are gathered
        from the pool — the ``device_get`` waits out pending programs,
        so call this at drain/idle, never on the dispatch path.
        Read-only and leader-side (NOT mirrored). None when empty."""
        if self._radix is None or self._radix.n_nodes == 0:
            return None
        from . import kv_wire
        nodes = self._radix.walk()     # parents before children (BFS)
        # parent.stamp >= child.stamp (bumps touch whole paths), and the
        # stable sort keeps BFS order on ties — parents stay first
        nodes.sort(key=lambda n: -n.stamp)
        idx: Dict[int, int] = {}
        recs: List[Dict[str, Any]] = []
        budget = int(max_bytes)
        for node in nodes:
            at_root = not node.parent.chunk
            pidx = -1 if at_root else idx.get(id(node.parent), -1)
            if not at_root and pidx < 0:
                continue              # parent missed the budget
            kv = self._page_kv(node)
            nbytes = kv_wire.kv_nbytes(kv)
            if nbytes > budget:
                continue
            budget -= nbytes
            idx[id(node)] = len(recs)
            recs.append(kv_wire.record(pidx, node.chunk, kv))
        if not recs:
            return None
        return kv_wire.encode(recs, self.ecfg.page_size)

    def _page_kv(self, node):
        """One radix node's KV bytes on host: tier-0 pages are gathered
        from the pool (the ``device_get`` waits out pending programs —
        callers fence or run at drain/idle), spilled tiers already hold
        host bytes."""
        if node.tier == 0:
            kp, vp = self._gather_page_fn(
                self.k_cache, self.v_cache,
                self._gr(np.int32(node.page)))
            return jax.device_get((kp, vp))
        return node.host.kv

    def import_prefixes(self, blob) -> int:
        """Install a tier-2 fleet snapshot as tier-1 nodes backed by the
        host arena, stopping at arena capacity. Existing nodes are kept
        (never downgraded) and reused as parents. MIRRORED: the import
        mutates replay-relevant tree state, so followers install the
        identical blob at the identical call-stream position. Returns
        pages imported (0 when radix/arena off, bad blob, or geometry
        mismatch — a snapshot is a warm start, never a failure)."""
        if self._radix is None or self._arena is None or not blob:
            return 0
        from . import kv_wire
        try:
            recs = kv_wire.decode(blob, self.ecfg.page_size)
        except kv_wire.WireError:
            return 0
        want = kv_wire.cache_spec(self.k_cache, self.v_cache)
        imported = 0
        by_idx: List[Any] = []
        for rec in recs:
            p = int(rec.get("p", -1))
            parent = None
            if p >= 0:
                parent = by_idx[p]    # decode guarantees p < this index
                if parent is None:
                    by_idx.append(None)
                    continue
            chunk = tuple(int(t) for t in rec["c"])
            node = self._radix.child(parent, chunk)
            if node is None:
                kv = (rec["k"], rec["v"])
                if (kv_wire.kv_spec(kv) != want
                        or not self._arena.room_for(1)):
                    by_idx.append(None)
                    continue
                node = self._radix.insert_host(
                    parent, chunk, self._arena.store(kv, snapshot=True))
                imported += 1
            by_idx.append(node)
        return imported

    # ------------------------------------------------------------------
    # disaggregated prefill→decode KV transfer (ISSUE 20)
    # ------------------------------------------------------------------
    def export_request_kv(self, full_ids,
                          max_bytes: int = 64 << 20) -> Optional[bytes]:
        """Serialize the radix-cached KV chain for one request's token
        ids (the prefill side of a disagg handoff). Only FULL quiescent
        pages ship — the epoch fence runs first so the gathers can never
        race an in-flight program; the partial boundary page travels as
        a token tail the decode side re-extends through chunked prefill
        (bit-identical by construction). A byte-budget cut stops at the
        cut (never skips) so the shipped chain stays rooted. None when
        the radix cache is off or holds nothing for these ids.
        Leader-side only — callers gate on single-host serving."""
        self._refuse_for_latent_rows("export_request_kv")
        if self._radix is None:
            return None
        FAULTS.check("pages.export")
        from . import kv_wire
        # lint: allow(host-sync-hot-path): token ids arrive as host lists
        ids = np.asarray(full_ids, np.int32)
        if int(ids.shape[0]) < 2:
            return None
        full, _part, _q = self._radix.match(ids, int(ids.shape[0]) - 1,
                                            bump=False)
        if not full:
            return None
        self.fence_quiesce()
        budget = int(max_bytes)
        recs: List[Dict[str, Any]] = []
        for i, node in enumerate(full):
            kv = self._page_kv(node)
            nbytes = kv_wire.kv_nbytes(kv)
            if nbytes > budget:
                break
            budget -= nbytes
            recs.append(kv_wire.record(i - 1, node.chunk, kv))
        if not recs:
            return None
        return kv_wire.encode(recs, self.ecfg.page_size)

    def import_request_kv(self, blob) -> int:
        """Install a transferred request chain into the LIVE pool and
        radix tree at tier 0 (the decode side of a disagg handoff):
        each page is uploaded into a freshly pinned pool page and
        grafted via ``insert_page``, so the very next stitch serves the
        prefix HBM-hot. Chunks already resident at tier 0 are kept;
        spilled chunks are promoted onto the transferred bytes. Stops
        (keeping the rooted prefix) at a geometry mismatch or a dry
        pool after one eviction attempt per page. Returns pages
        imported/promoted; 0 on a bad blob — a transfer is a warm
        start, never a failure (the caller re-prefills the miss)."""
        if self._radix is None or not blob:
            return 0
        FAULTS.check("pages.import")
        from . import kv_wire
        try:
            recs = kv_wire.decode(blob, self.ecfg.page_size)
        except kv_wire.WireError:
            return 0
        want = kv_wire.cache_spec(self.k_cache, self.v_cache)
        parent = None
        imported = 0
        for i, rec in enumerate(recs):
            if int(rec.get("p", -1)) != i - 1:
                break         # a request transfer is ONE rooted chain
            chunk = tuple(int(t) for t in rec["c"])
            node = self._radix.child(parent, chunk)
            if node is not None and node.tier == 0:
                parent = node
                continue      # already HBM-hot here: nothing to upload
            kv = (rec["k"], rec["v"])
            if kv_wire.kv_spec(kv) != want:
                break
            if not self._pt.n_free:
                self.radix_evict(1)
            pg = self._pt.alloc_pinned()
            if pg is None:
                break         # pool dry: keep the rooted prefix we got
            kp = jax.tree_util.tree_map(self._gr, kv[0])
            vp = jax.tree_util.tree_map(self._gr, kv[1])
            self.k_cache, self.v_cache = self._upload_page_fn(
                self.k_cache, self.v_cache, kp, vp, self._gr(np.int32(pg)))
            parent = self._radix.insert_page(parent, chunk, pg)
            imported += 1
        if imported and self._arena is not None:
            # promotions over spilled chunks retired their host bytes
            self._arena.free_all(self._radix.take_dropped_hosts())
        return imported

    @property
    def quarantined_pages(self) -> int:
        """Pages fenced in the page-table quarantine (0 when dense)."""
        return self._pt.quarantined if self.paged else 0

    def fence_quiesce(self) -> int:
        """Materialise every launched device program, then drain the page
        quarantine entirely; returns the number of pages reclaimed.
        Dense engines: no-op. Device programs are serialized by their
        donated cache data dependencies, so blocking on the latest
        ``lengths`` output proves no in-flight program can still read any
        quarantined page through a captured block table. MIRRORED across
        hosts (each blocks on its OWN devices), so callers must invoke it
        only at deterministic call-stream positions guarded by
        deterministic state — e.g. ``quarantined_pages > 0`` — never from
        timing-dependent branches."""
        if not self.paged:
            return 0
        jax.block_until_ready(self.lengths)
        return self._pt.drain_quarantine()

    def fence_retire(self, epoch: int):
        """Tell the page table that the decode dispatch launched at
        ``epoch`` has been materialised, as ``decode_n_launch(retire=)``
        does, without launching anything: the scheduler calls it at the
        beginning of a pass, so the pass's allocations find unfenced
        what the handle it waited a step ago lets go. Host state only;
        MIRRORED (followers never wait a handle), so call it at a fixed
        place of the call stream. Dense engines: no-op."""
        if self.paged:
            self._pt.retire_epoch(epoch)

    def decode_n(self, n: Optional[int] = None) -> np.ndarray:
        """n decode steps in one device program; returns tokens [n, B].

        One dispatch + one host sync per call — the per-step host
        round-trip amortises over the chunk. For UNCONSTRAINED slots chunk semantics are identical
        to n decode() calls; grammar-constrained slots freeze after the
        first step (see ``step_budgets``) — only row 0 of their toks_n
        column is real, rows >= 1 are stale-mask resamples the caller
        must discard (the scheduler does).
        Paged mode: callers that want preemption-on-pool-dry run
        ``prepare_decode`` themselves first and requeue the victims; here
        a dry pool raises (tests/bench size their pools adequately)."""
        handle = self.decode_n_launch(n)
        toks = handle.wait()
        if self.paged:
            # synchronous flow self-retires: the program just
            # materialised, so its quarantined pages are reclaimable NOW
            # and epoch == retired at every free point — sync paged mode
            # keeps exactly its pre-fence free-list order (and followers
            # replay this call, waiting on their own devices, so the
            # retirement is lockstep across hosts)
            self._pt.retire_epoch(handle.epoch)
        return toks

    def decode_n_launch(self, n: Optional[int] = None,
                        retire: Optional[int] = None) -> DecodeHandle:
        """Launch one chunk of ``n`` decode steps (``ecfg.decode_chunk``
        where None) WITHOUT materialising its tokens: slot state (host
        lengths included) advances immediately; the returned handle's
        wait() fetches [n, B]. Double-buffering callers launch dispatch
        N+1 before waiting on N so fan-out work overlaps device compute
        (see DecodeHandle).

        Paged mode: each successful launch advances the page-table
        dispatch epoch; ``retire`` (the ``.epoch`` of the newest handle
        the caller has ALREADY waited on) first unfences pages
        quarantined at or before that epoch, making them allocatable for
        this very launch. The kwarg rides the multi-host mirror
        broadcast, so followers retire at the identical call-stream
        position without ever waiting on a handle themselves."""
        FAULTS.check("engine.step")
        with span("engine.decode_n") as sp:
            handle = self._launch(n, retire, sp.t0)
            sp.set(sampler=handle.sampler)
            return handle

    def _launch(self, n: Optional[int], retire: Optional[int],
                t0: float) -> DecodeHandle:
        n = n or self.ecfg.decode_chunk
        if self.paged and retire is not None:
            self._pt.retire_epoch(retire)
        victims = self.prepare_decode(n)
        if victims:
            from .paged import PagesExhausted
            raise PagesExhausted(f"pool dry; victims {victims}")
        exe = self._decode_n_exec(n, self._attn_bucket(n))
        budgets = self.step_budgets(n)
        (toks_n, self.k_cache, self.v_cache, self.lengths, self.counts,
         self.last_tokens, self.pring, self.mu, self.keys,
         self._gstate, load) = self._enqueue(
            "decode", exe,
            self.params, self.k_cache, self.v_cache, self.lengths,
            self.counts, self.last_tokens, self.pring, self.mu, self.sp,
            self.keys, self._active_dev, self.mask_bits, self._constr_dev,
            self._rln_dev, self._gstate, self._gmask_dev,
            self._gtrans_dev, self._tables_dev(),
            self._g(budgets, self._slot_sh))
        if self.cfg.index_topk:
            self._count_index_positions(budgets)
        self._host_lengths[self.active] += budgets[self.active]
        # stamp AFTER the successful launch: a raise above leaves the
        # epoch untouched, so later frees aren't fenced behind a program
        # that never existed
        epoch = self._pt.advance_epoch() if self.paged else 0
        return DecodeHandle(self, toks_n, t0, epoch,
                            sampler=self._count_sampler_steps(n, budgets),
                            load=load)

    def rollback_lengths(self, rollback: np.ndarray) -> None:
        """Subtract a per-slot overshoot [B] from the host lengths: the
        steps a launch advanced them by (``step_budgets``) that the
        device did not take, because a device-grammar slot's automaton
        left its table mid-chunk and froze. Called by the scheduler as
        it fans the chunk out (``Scheduler._grammar_ack``); MIRRORED, so
        followers roll back at the same call-stream position without
        ever waiting themselves. Slots released since launch are masked
        out (their lengths were already reset), and the clamp keeps a
        stale rollback from ever driving a length negative."""
        rb = np.asarray(rollback, np.int64)  # lint: allow(host-sync-hot-path): rollback vector is host numpy
        rb = np.minimum(np.where(self.active, rb, 0), self._host_lengths)
        self._host_lengths -= rb

    def step_budgets(self, n: int) -> np.ndarray:
        """Per-slot decode-step budget for a chunk of ``n``: HOST-masked
        constrained slots advance one token per dispatch (their PDA mask
        refreshes on the host between dispatches); device-grammar slots
        and everyone else take the full chunk — the device table refreshes
        their mask per step, and an on-device escape freezes the slot so
        the overshoot rolls back through rollback_lengths."""
        host_masked = self._constrained & ~self._gdev_mode
        return np.where(host_masked, 1, n).astype(np.int32)

    def release(self, slot: int, park: bool = False):
        """Free ``slot``. With ``park=True`` the KV cache and slot state
        are left in place so a later ``extend`` can reuse the prefix (the
        slot still counts as free and may be overwritten by any admit)."""
        with span("engine.release", park=park):
            self._do_release(slot, park)

    def _do_release(self, slot: int, park: bool):
        self.clear_mask(slot)
        self.active[slot] = False
        self._opts.pop(slot, None)
        self._active_dev = self._g(self.active.astype(np.int32),
                                   self._slot_sh)
        if park and self.supports_extend:
            # paged: the parked prefix keeps its pages until an admit
            # overwrites the slot or the scheduler evicts via
            # free_slot_pages under pool pressure
            return
        if self.paged:
            self._pt.release(slot)
        self._host_lengths[slot] = 0
        self._repeat_n[slot] = max(1, self.ecfg.repeat_last_n)
        self._rln_dev = self._g(self._repeat_n, self._slot_sh)
        (self.lengths, self.counts, self.last_tokens, self.pring,
         self.mu) = self._enqueue(
            "release", self._release_fn,
            self.lengths, self.counts, self.last_tokens, self.pring,
            self.mu, self._gr(np.int32(slot)))

    def slot_length(self, slot: int) -> int:
        return int(self._fetch(self.lengths)[slot])

    def state_position(self, slot: int) -> int:
        """Positions the slot's recurrent state has run over: the host's
        mirror of its length, every launched step counted (no sync)."""
        return int(self._host_lengths[slot])

    @property
    def kv_bytes(self) -> int:
        leaves = jax.tree_util.tree_leaves((self.k_cache, self.v_cache))
        return sum(l.size * l.dtype.itemsize for l in leaves)

    @property
    def ring_positions(self) -> Dict[str, int]:
        """{"live", "allocated"}: positions the window layers' rings hold
        against what they could (tpu_model_ring_positions{what}); empty for
        a model without window layers. A slot's ring holds min(its
        length, W) positions a window layer; from the host's mirror of the
        lengths, every launched step counted: no device work."""
        Lw, W = self.cfg.n_window_layers, self.cfg.sliding_window
        if not Lw:
            return {}
        return {"live": Lw * int(np.minimum(self._host_lengths, W).sum()),
                "allocated": Lw * self.n_slots * W}

    @property
    def latent_positions(self) -> Dict[str, int]:
        """{"live", "allocated"}: cached positions latent attention's rows
        hold against what they were allocated
        (tpu_model_latent_positions{what}); empty for a model without
        latent attention. An active slot holds its length a latent layer (a
        parked one's rows lie there and no decode step reads them); from the
        host's mirror of the lengths, every launched step counted: no
        device work."""
        if not self.cfg.kv_latent_dim:
            return {}
        La = self.cfg.n_full_layers
        return {"live": La * int(self._host_lengths[self.active].sum()),
                "allocated": La * self.n_slots * self.max_seq}

    @property
    def state_bytes(self) -> int:
        """Bytes of what the slots carry beside full-length keys and
        values, recurrent state and rings (0 for a stack that has
        neither); part of ``kv_bytes``, whose trees it rides in."""
        return self.cache_bytes["state"] + self.cache_bytes["window"]
