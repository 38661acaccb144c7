"""Decoder-only transformer family, pure functional JAX.

This is the TPU-native replacement for the inference engine the reference
operator delegates to the `ollama/ollama` image (llama.cpp/GGML, see
/root/reference/pkg/model/pod.go:11 and SURVEY.md §2.2). Design choices are
XLA-first, not a translation:

- Layer params are **stacked** along a leading ``n_layers`` axis and the
  forward pass runs ``lax.scan`` over layers → the block is traced/compiled
  once regardless of depth (fast compiles for 80-layer 70B models).
- Static shapes everywhere; prefill lengths are bucketed by the engine.
- GQA is a grouped einsum (ops/attention.py) — K/V are never repeated in HBM.
- fp32 for softmax/norm accumulation, bf16 (or int8-dequant) for matmuls so
  the MXU stays fed.
- KV cache updates are functional; the engine donates cache buffers so XLA
  aliases them in-place.

Params pytree layout (all leaves jnp arrays; layer leaves stacked on axis 0):

  tok_emb   [V, D]
  out_norm_w [D] (+ out_norm_b for layernorm archs)
  lm_head   [D, V]       (absent when cfg.tie_embeddings)
  lm_head_b [V]          (phi-2 only)
  layers/
    attn_norm_w [L, D] (+ attn_norm_b)
    wq [L, D, H*hd]  wk [L, D, KvH*hd]  wv [L, D, KvH*hd]  wo [L, H*hd, D]
    (bq, bk, bv, bo optional)
    mlp_norm_w [L, D] (+ mlp_norm_b; absent when cfg.parallel_block)
    w_gate [L, D, F] (gated only)  w_up [L, D, F]  w_down [L, F, D]
    (b_up [L, F], b_down [L, D] optional)
    q_norm_w / k_norm_w [L, hd] (qk_norm archs)
    MoE archs (cfg.n_experts > 0, mixtral family) replace w_gate/w_up/w_down:
    router [L, D, E]
    we_gate [L, E, D, F]  we_up [L, E, D, F]  we_down [L, E, F, D]
    (E = the experts this chip holds, cfg.experts_held; router keeps all)
    Hybrid stacks (cfg.layer_kinds, "m" Mamba-2 / "A" attention a layer):
    the leaves above that every layer has keep their leading L; the two
    mixers' leaves are stacked over their OWN layers only, still flat in
    this dict: wq/wk/wv/wo [La, ...] and
    ssm_in [Lm, D, 2*di + 2*N + H]  ssm_conv_w [Lm, K, C]  ssm_conv_b [Lm, C]
    ssm_dt_bias / ssm_a_log / ssm_d [Lm, H]  ssm_norm_w [Lm, di]
    ssm_out [Lm, di, D]     (di = H * P, C = di + 2 * N)
    or, where the recurrent mixer is a gated short convolution ("c", lfm2),
    conv_in [Lc, D, 3D]  conv_w [Lc, K, D]  conv_out [Lc, D, D]
    Window layers ("w", exaone_moe) have attention's projections: La counts
    them with the "A" layers, in layer order
    Latent attention (cfg.kv_latent_dim, glm_moe_dsa) has, in place of
    wq/wk/wv, over its La layers
    wq_a [La, D, Rq]  q_a_norm_w [La, Rq]  wq_b [La, Rq, H*(dn+dr)]
    wkv_a [La, D, C+dr]  kv_a_norm_w [La, C]
    w_uk [La, H, dn, C]  w_uv [La, H, C, dv]  wo [La, H*dv, D]
    (the published kv_b_proj [C, H*(dn+dv)] a head at a time, keys' part
    transposed: the layout the absorbed decode step multiplies by) and the
    indexer's idx_wq [La, Rq, Hi*di]  idx_wk [La, D, di]
    idx_k_norm_w / idx_k_norm_b [La, di]  idx_w [La, D, Hi]
    cfg.n_dense_layers leading layers of such a stack have one dense MLP,
    w_gate/w_up [Ld, D, Fd]  w_down [Ld, Fd, D], and the router's leaves
    are stacked over the Lr = L - Ld layers after them (router_bias
    [Lr, E] float32 where cfg.moe_select_bias)
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import quant as Q
from ..ops.attention import (attend_hf, cached_attention, causal_mask,
                             chunk_attention, note_kernel)
from ..ops.norms import layer_norm, rms_norm
from ..ops.rope import (apply_rope, rope_angles, rope_angles_cfg,
                        yarn_softmax_factor)
from ..runtime.trace import device_scope
from .config import ModelConfig

Params = Dict[str, Any]


def _mm(cfg: ModelConfig, x, w, out_dtype=None):
    """Linear against a dense array or a quantized dict leaf
    (ops/quant.py). cfg.mm_kernels picks the quantized matmul's path:
    the engine's constructor resolves "auto" (ops/quant.resolve_mm_kernels:
    "pallas" on a single-device TPU, "xla" elsewhere), and under "pallas"
    ops/quant.matmul routes by row count — int8 up to 16 rows keeps the
    grouped XLA form (one stream measured 147 tok/s on it against the fused
    kernel's 137 on phi: N = 1, not a batch), above that the fused kernel,
    int4 the fused kernel throughout. An "auto" that nobody resolved (a
    forward pass outside any engine) is XLA, and an explicit
    kernels="pallas"/"interpret" config still routes everything through
    kernels.

    A float32 activation against a bfloat16 matrix (the residual stream and
    the sublayers of a delta stack, ``_hybrid_layers``) goes into the MXU
    as bfloat16 and comes out float32, unrounded: ``x @ w`` would promote
    the MATRIX to float32 first."""
    if (not Q.is_quantized(w) and x.dtype == jnp.float32
            and w.dtype == jnp.bfloat16):
        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=out_dtype or jnp.float32)
    return Q.matmul(x, w, out_dtype, kernels=_mm_mode(cfg))


def _mm_mode(cfg: ModelConfig) -> str:
    if cfg.kernels in ("pallas", "interpret"):
        return cfg.kernels
    if cfg.mm_kernels in ("pallas", "interpret"):
        return cfg.mm_kernels
    return "xla"


def _scan_layers(cfg: ModelConfig, body, init, layers: Params):
    """``lax.scan`` of ``body(carry, (lp, i))`` over the stacked layers.

    A scan hands its body a slice of every stacked leaf. An XLA consumer
    fuses that slice into its own read; a pallas_call cannot, so the slice
    is a copy: for starcoder2's w_up 51 us to copy where the fused matmul
    then needs 43 to read it, and the copies of one decode step outlasted
    its matmuls (my chip run, PR 25). So where the quantized matmuls go to
    the fused kernel, their leaves stay out of the scan's slices: every
    step's ``lp`` carries the whole stack and this layer's index
    (``{"q": [L, K, O], "s": [L, K/g, O], "layer": i}``), and the kernel
    reads its layer where it lies (ops/quant.matmul)."""
    in_place = ({k: v for k, v in layers.items() if Q.is_quantized(v)}
                if _mm_mode(cfg) != "xla" else {})
    sliced = {k: v for k, v in layers.items() if k not in in_place}

    def step(carry, layer_in):
        lp, i = layer_in
        lp = {**lp, **{k: {**v, "layer": i} for k, v in in_place.items()}}
        return body(carry, (lp, i))

    n = jax.tree_util.tree_leaves(layers)[0].shape[0]
    return lax.scan(step, init, (sliced, jnp.arange(n, dtype=jnp.int32)))


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random init (for tests/benchmarks; real weights come from gguf/)."""
    L, D, F, V = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size
    keys = iter(jax.random.split(key, 32))

    def w(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    La = cfg.n_attn_layers          # L unless the stack is a hybrid one
    layers: Dict[str, jax.Array] = {"attn_norm_w": jnp.ones((L, D), dtype)}
    if cfg.kv_latent_dim:
        H, C, Rq = cfg.n_heads, cfg.kv_latent_dim, cfg.q_latent_dim
        dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        Hi, di = cfg.index_heads, cfg.index_head_dim
        layers.update(
            wq_a=w(next(keys), (La, D, Rq)),
            q_a_norm_w=jnp.ones((La, Rq), dtype),
            wq_b=w(next(keys), (La, Rq, H * (dn + dr))),
            wkv_a=w(next(keys), (La, D, C + dr)),
            kv_a_norm_w=jnp.ones((La, C), dtype),
            w_uk=w(next(keys), (La, H, dn, C)),
            w_uv=w(next(keys), (La, H, C, dv)),
            wo=w(next(keys), (La, H * dv, D)))
        if Hi:
            layers.update(
                idx_wq=w(next(keys), (La, Rq, Hi * di)),
                idx_wk=w(next(keys), (La, D, di)),
                idx_k_norm_w=jnp.ones((La, di), dtype),
                idx_k_norm_b=jnp.zeros((La, di), dtype),
                idx_w=w(next(keys), (La, D, Hi)))
    else:
        layers.update(
            wq=w(next(keys), (La, D, cfg.q_dim)),
            wk=w(next(keys), (La, D, cfg.kv_dim)),
            wv=w(next(keys), (La, D, cfg.kv_dim)),
            wo=w(next(keys), (La, cfg.q_dim, D)))
    if cfg.n_conv_layers:
        Lc = cfg.n_conv_layers
        layers["conv_in"] = w(next(keys), (Lc, D, 3 * D))
        layers["conv_w"] = w(next(keys), (Lc, cfg.conv_kernel, D), 0.4)
        layers["conv_out"] = w(next(keys), (Lc, D, D))
    if cfg.n_ssm_layers:
        Lm, H, di = cfg.n_ssm_layers, cfg.ssm_heads, cfg.ssm_inner
        C = cfg.ssm_conv_dim
        layers["ssm_in"] = w(next(keys), (Lm, D, di + C + H))
        layers["ssm_conv_w"] = w(next(keys), (Lm, cfg.ssm_conv, C), 0.2)
        layers["ssm_conv_b"] = w(next(keys), (Lm, C))
        layers["ssm_dt_bias"] = w(next(keys), (Lm, H))
        # A = -exp(a_log): decays from 1 to 16 a unit of dt, as Mamba-2
        # draws them
        layers["ssm_a_log"] = jnp.broadcast_to(
            jnp.log(1.0 + jnp.arange(H, dtype=jnp.float32) % 16),
            (Lm, H)).astype(dtype)
        layers["ssm_d"] = jnp.ones((Lm, H), dtype)
        layers["ssm_norm_w"] = jnp.ones((Lm, di), dtype)
        layers["ssm_out"] = w(next(keys), (Lm, di, D))
    if cfg.n_delta_layers:
        Ld, H = cfg.n_delta_layers, cfg.delta_heads
        C, dv = cfg.delta_conv_dim, cfg.delta_heads * cfg.delta_value_dim
        layers["delta_qkv"] = w(next(keys), (Ld, D, C))
        layers["delta_ab"] = w(next(keys), (Ld, D, 2 * H), 0.2)
        layers["delta_z"] = w(next(keys), (Ld, D, dv))
        layers["delta_conv_w"] = w(next(keys), (Ld, cfg.delta_conv, C), 0.4)
        layers["delta_dt_bias"] = w(next(keys), (Ld, H), 0.5)
        # alpha = exp(-exp(a_log) softplus(a + dt_bias)): decays of 1 to
        # 16 a unit of softplus, as Gated DeltaNet draws them
        layers["delta_a_log"] = jnp.broadcast_to(
            jnp.log(1.0 + jnp.arange(H, dtype=jnp.float32) % 16),
            (Ld, H)).astype(dtype)
        layers["delta_norm_w"] = jnp.ones((Ld, cfg.delta_value_dim), dtype)
        layers["delta_out"] = w(next(keys), (Ld, dv, D))
    if cfg.n_dense_layers:
        # the leading dense layers' MLP; the routed leaves below are stacked
        # over the layers after them
        Ld, Fd = cfg.n_dense_layers, cfg.dense_ffn_dim
        layers["w_gate"] = w(next(keys), (Ld, D, Fd))
        layers["w_up"] = w(next(keys), (Ld, D, Fd))
        layers["w_down"] = w(next(keys), (Ld, Fd, D))
    if cfg.n_experts:
        E, Lr = cfg.experts_held, cfg.n_routed_layers
        layers["router"] = w(next(keys), (Lr, D, cfg.n_experts))
        if cfg.moe_select_bias:
            # wide enough beside sigmoid scores around 1/2 that it changes
            # some kept sets; float32 whatever the weights' type
            layers["router_bias"] = jax.random.normal(
                next(keys), (Lr, cfg.n_experts), jnp.float32) * 0.1
        layers["we_gate"] = w(next(keys), (Lr, E, D, F))
        layers["we_up"] = w(next(keys), (Lr, E, D, F))
        layers["we_down"] = w(next(keys), (Lr, E, F, D))
        if cfg.n_shared_ffn:
            Fs = cfg.n_shared_ffn
            layers["we_sh_gate"] = w(next(keys), (Lr, D, Fs))
            layers["we_sh_up"] = w(next(keys), (Lr, D, Fs))
            layers["we_sh_down"] = w(next(keys), (Lr, Fs, D))
            if cfg.shared_gate:
                layers["sh_gate"] = w(next(keys), (Lr, D, 1))
    else:
        layers["w_up"] = w(next(keys), (L, D, F))
        layers["w_down"] = w(next(keys), (L, F, D))
    if cfg.norm_type == "layernorm" and cfg.norm_bias:
        layers["attn_norm_b"] = jnp.zeros((L, D), dtype)
    if not cfg.parallel_block:
        layers["mlp_norm_w"] = jnp.ones((L, D), dtype)
        if cfg.norm_type == "layernorm" and cfg.norm_bias:
            layers["mlp_norm_b"] = jnp.zeros((L, D), dtype)
    if cfg.mlp_type == "gated" and not cfg.n_experts:
        layers["w_gate"] = w(next(keys), (L, D, F))
    if cfg.attn_bias:
        layers["bq"] = jnp.zeros((L, cfg.q_dim), dtype)
        layers["bk"] = jnp.zeros((L, cfg.kv_dim), dtype)
        layers["bv"] = jnp.zeros((L, cfg.kv_dim), dtype)
    if cfg.out_bias:
        layers["bo"] = jnp.zeros((L, D), dtype)
        layers["b_up"] = jnp.zeros((L, F), dtype)
        layers["b_down"] = jnp.zeros((L, D), dtype)
    if cfg.qk_norm:
        layers["q_norm_w"] = jnp.ones((La, cfg.head_dim), dtype)
        layers["k_norm_w"] = jnp.ones((La, cfg.head_dim), dtype)
    if cfg.post_norms:
        layers["post_attn_norm_w"] = jnp.ones((L, D), dtype)
        layers["post_ffw_norm_w"] = jnp.ones((L, D), dtype)

    params: Params = {
        "tok_emb": w(next(keys), (V, D)),
        "out_norm_w": jnp.ones((D,), dtype),
        "layers": layers,
    }
    if cfg.norm_type == "layernorm" and cfg.norm_bias:
        params["out_norm_b"] = jnp.zeros((D,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = w(next(keys), (D, V))
    if cfg.out_bias:
        params["lm_head_b"] = jnp.zeros((V,), dtype)
    return params


# --------------------------------------------------------------------------
# building blocks
# --------------------------------------------------------------------------

def _causal_window_mask(k_pos, q_pos, window: int):
    """Additive [B,1,T,A] mask for cache attention: keys at absolute slot
    k_pos visible to queries at q_pos iff k <= q (within ``window`` when
    set). Shared by the dense and paged cached forwards."""
    ok = k_pos <= q_pos
    if window:
        ok = ok & (k_pos > q_pos - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[:, None, :, :]


def _is_full_layer(cfg: ModelConfig, i):
    """Alternating-attention pattern: layer i runs FULL attention iff
    i % sliding_pattern == sliding_pattern - 1 (gemma2: odd layers;
    gemma3: every 6th layer). ``i`` may be traced (scan index)."""
    return (i % cfg.sliding_pattern) == (cfg.sliding_pattern - 1)


def _layer_mask(cfg: ModelConfig, i, mask, m_full):
    """Per-layer attention mask: alternating archs (gemma2/gemma3) pick
    sliding vs full per layer; everything else uses ``mask`` as-is."""
    if not cfg.altern_sliding:
        return mask
    return jnp.where(_is_full_layer(cfg, i), m_full, mask)


def _layer_rope(cfg: ModelConfig, i, cos, sin, cos_l, sin_l):
    """Per-layer rope (gemma3): SLIDING layers rotate at the local theta
    (cos_l/sin_l, unscaled), FULL layers at the global theta incl. any
    context-extension scaling. Single-rope archs pass cos_l=None."""
    if cos_l is None:
        return cos, sin
    full = _is_full_layer(cfg, i)
    return jnp.where(full, cos, cos_l), jnp.where(full, sin, sin_l)


def _rope_pair(positions, cfg: ModelConfig):
    """(cos, sin, cos_l, sin_l): the global rope table plus, for dual-rope
    archs (cfg.rope_local_theta — gemma3), the local-theta table."""
    cos, sin = rope_angles_cfg(positions, cfg)
    if not cfg.rope_local_theta:
        return cos, sin, None, None
    cos_l, sin_l = rope_angles(positions, cfg.rotary_dim,
                               cfg.rope_local_theta)
    return cos, sin, cos_l, sin_l


def _attn_scale(cfg: ModelConfig) -> float:
    """Score scale: 1/sqrt(head_dim), gemma2/gemma3's
    1/sqrt(query_pre_attn_scalar), or granite's exact attention
    multiplier when the config sets one."""
    if cfg.attn_scale_mult:
        return cfg.attn_scale_mult
    return 1.0 / math.sqrt(cfg.attn_scale or cfg.head_dim)


def _norm(cfg: ModelConfig, x, w, b=None):
    if cfg.norm_type == "layernorm":
        return layer_norm(x, w, b, cfg.norm_eps)
    return rms_norm(x, w, cfg.norm_eps, cfg.norm_weight_offset)


def _act(cfg: ModelConfig, x):
    if cfg.act == "silu":
        return jax.nn.silu(x)
    if cfg.act == "relu":
        return jax.nn.relu(x)
    if cfg.act == "gelu":
        return jax.nn.gelu(x, approximate=False)
    return jax.nn.gelu(x, approximate=True)


def _moe_gates(cfg: ModelConfig, lp, xf):
    """Router: top-k gates scattered to a dense [N, E] fp32 matrix (zeros
    for unselected experts). ``moe_score`` "softmax": with ``moe_renorm``
    (mixtral, qwen3moe) the softmax runs over the SELECTED logits — equal
    to the full softmax renormalised over the top-k; without it (qwen2moe,
    norm_topk_prob=false) the full-softmax probabilities are kept
    un-renormalised. "sigmoid" (lfm2_moe): scores s = sigmoid(logits); the
    kept are the top-k of s + router_bias, a bias that takes part in the
    selection ONLY; the gates are the kept experts' s (never s + b), over
    their sum + 1e-6 with ``moe_renorm``, times ``moe_scale``."""
    logits = jnp.einsum("nd,de->ne", xf, lp["router"],
                        preferred_element_type=jnp.float32)  # [N, E] fp32
    if cfg.moe_score == "sigmoid":
        score = jax.nn.sigmoid(logits)
        pick = (score + lp["router_bias"].astype(jnp.float32)
                if cfg.moe_select_bias else score)
        topi = lax.top_k(pick, cfg.n_experts_used)[1]
        topw = jnp.take_along_axis(score, topi, axis=1)
        if cfg.moe_renorm:
            topw = topw / (topw.sum(axis=-1, keepdims=True) + 1e-6)
        topw = topw * cfg.moe_scale
    elif cfg.moe_renorm:
        topw, topi = lax.top_k(logits, cfg.n_experts_used)  # [N, k]
        topw = jax.nn.softmax(topw, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = lax.top_k(probs, cfg.n_experts_used)
    N = xf.shape[0]
    gates = jnp.zeros((N, cfg.n_experts), jnp.float32)
    return gates.at[jnp.arange(N)[:, None], topi].set(topw)


def _moe_mlp(cfg: ModelConfig, lp, x, tap=None, gates=None):
    """Sparse-MoE gated MLP (mixtral family), exact (no token dropping).
    ``tap``: a list the router's gates [N, E] are appended to, for a
    caller that counts what was routed where (``_expert_load``).
    ``gates``: the router's gates where the caller already has them (a
    router that reads the layer's input, ``cfg.moe_router_input``
    "block": ``_hybrid_layers`` routes before it mixes); routed here from
    ``x`` otherwise.

    Every expert computes over all tokens and the combine applies the gate
    (zero for unselected) — on TPU decode this costs nothing extra where it
    matters: the step is weights-bandwidth-bound and all E experts' weights
    stream from HBM regardless once the batch covers them. Two layouts:

    - "einsum": experts batched on a leading E axis. Under GSPMD with
      we_* sharded on the "ep" mesh axis (parallel/sharding.py) each device
      computes only its resident experts and XLA reduces the combine over
      ep — expert parallelism with no hand-written collective.
    - "scan": lax.scan over experts, [N, F] working set — memory-light for
      long single-device prefill where the einsum's [E, N, F] intermediate
      would spike HBM.

    "auto" picks einsum for small token counts (decode / short chunks) and
    scan beyond that.
    """
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    if gates is None:
        with device_scope("moe.route"):
            gates = _moe_gates(cfg, lp, xf)                  # [N, E] fp32
    if tap is not None:
        tap.append(gates)
    with device_scope("moe.experts"):
        y = _moe_experts(cfg, lp, xf, gates)
    return y.astype(x.dtype).reshape(B, T, D)


def _moe_experts(cfg: ModelConfig, lp, xf, gates):
    """The experts' matmuls and the gated combine of ``_moe_mlp``:
    [N, D] tokens and [N, E] gates to [N, D] fp32."""
    N, D = xf.shape
    if cfg.experts_held != cfg.n_experts:
        # this chip's share of an expert-parallel layer: the gates of the
        # experts it holds, as the router over all of them gave them; what
        # a token's other kept experts would add is another chip's to add
        gates = lax.slice_in_dim(gates, cfg.expert_first,
                                 cfg.expert_first + cfg.experts_held, axis=1)
    if (xf.dtype == jnp.float32 and not Q.is_quantized(lp["we_gate"])
            and lp["we_gate"].dtype == jnp.bfloat16):
        # a stack that carries its stream float32 (``_hybrid_layers``): into
        # the MXU as bfloat16, as ``_mm`` does; the router read it unrounded
        xf = xf.astype(jnp.bfloat16)
    impl = cfg.moe_impl
    if impl == "auto":
        impl = "einsum" if N <= 256 else "scan"
    if impl == "einsum":
        h = jnp.einsum("nd,edf->enf", xf, lp["we_gate"])
        u = jnp.einsum("nd,edf->enf", xf, lp["we_up"])
        o = jnp.einsum("enf,efd->end", _act(cfg, h) * u, lp["we_down"])
        y = jnp.einsum("ne,end->nd", gates, o.astype(jnp.float32))
    else:
        def body(acc, ew):
            wg, wu, wd, g = ew                   # [D,F] [D,F] [F,D] [N]
            he = _act(cfg, xf @ wg) * (xf @ wu)
            return acc + g[:, None] * (he @ wd).astype(jnp.float32), None
        acc0 = jnp.zeros((N, D), jnp.float32)
        y, _ = lax.scan(body, acc0, (lp["we_gate"], lp["we_up"],
                                     lp["we_down"], gates.T))
    if "we_sh_gate" in lp:
        # qwen2moe shared expert: a gated MLP every token runs, its
        # output scaled by a per-token sigmoid gate (shared_expert_gate);
        # granite's is added whole (no sh_gate leaf)
        hs = _act(cfg, xf @ lp["we_sh_gate"]) * (xf @ lp["we_sh_up"])
        sh = (hs @ lp["we_sh_down"]).astype(jnp.float32)
        if "sh_gate" in lp:
            sg = jax.nn.sigmoid(
                (xf @ lp["sh_gate"]).astype(jnp.float32))  # [N, 1]
            sh = sg * sh
        y = y + sh
    return y


def _expert_load(gates, live):
    """[E] int32: per expert of the router, how many of the rows that
    ``live`` [B] marks kept it. gates [B * T, E], zero where not kept."""
    kept = (gates > 0).reshape(live.shape[0], -1, gates.shape[-1])
    return (kept & (live != 0)[:, None, None]).sum((0, 1), dtype=jnp.int32)


@device_scope("mlp")
def _mlp(cfg: ModelConfig, lp, x, tap=None, gates=None):
    if cfg.n_experts:
        return _moe_mlp(cfg, lp, x, tap, gates)
    if cfg.mlp_type == "gated":
        g = _act(cfg, _mm(cfg, x, lp["w_gate"]))
        u = _mm(cfg, x, lp["w_up"])
        return _mm(cfg, g * u, lp["w_down"])
    u = _mm(cfg, x, lp["w_up"])
    if "b_up" in lp:
        u = u + lp["b_up"]
    d = _mm(cfg, _act(cfg, u), lp["w_down"])
    if "b_down" in lp:
        d = d + lp["b_down"]
    return d


@device_scope("attn.qkv")
def _qkv(cfg: ModelConfig, lp, h, cos, sin):
    B, T, _ = h.shape
    q = _mm(cfg, h, lp["wq"])
    k = _mm(cfg, h, lp["wk"])
    v = _mm(cfg, h, lp["wv"])
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        # gemma3 stores the norm weight gemma-style as (w − 1); qwen3's
        # offset is 0, so the shared call is exact for both
        q = rms_norm(q, lp["q_norm_w"], cfg.norm_eps,
                     cfg.norm_weight_offset)
        k = rms_norm(k, lp["k_norm_w"], cfg.norm_eps,
                     cfg.norm_weight_offset)
    if cfg.rope:
        q = apply_rope(q, cos, sin, cfg.rotary_dim)
        k = apply_rope(k, cos, sin, cfg.rotary_dim)
    return q, k, v


@device_scope("attn.out")
def _proj_out(cfg, lp, attn_out, B, T):
    o = _mm(cfg, attn_out.reshape(B, T, -1), lp["wo"])
    if "bo" in lp:
        o = o + lp["bo"]
    return o


def _residual(cfg: ModelConfig, lp, x, h, attn, tap=None, gates=None):
    rm = cfg.residual_multiplier or 1.0   # granite: scaled residual adds
    if cfg.post_norms:
        # gemma2 sandwich norms: attn/mlp OUTPUTS normed before the adds
        attn = _norm(cfg, attn, lp["post_attn_norm_w"])
    # the residual adds carry their branch's scope: XLA fuses each into the
    # matmul that feeds it and names the fusion after its root, the add
    if cfg.parallel_block:
        with device_scope("attn.out"):
            x = x + attn
        m = _mlp(cfg, lp, h, tap)
        with device_scope("mlp"):
            return x + m
    with device_scope("attn.out"):
        x = x + rm * attn
    h2 = _norm(cfg, x, lp["mlp_norm_w"], lp.get("mlp_norm_b"))
    m = _mlp(cfg, lp, h2, tap, gates)
    if cfg.post_norms:
        m = _norm(cfg, m, lp["post_ffw_norm_w"])
    with device_scope("mlp"):
        return x + rm * m


def _block_chunk(cfg: ModelConfig, lp, x, cos, sin, mask, scale,
                 attn_fn=None, mesh=None):
    """One layer over a fresh chunk (no prior cache). Returns
    (x, (k, v)) with K/V head-first [B, KvH, T, hd] — the cache layout.
    ``attn_fn(q, k, v)`` overrides the attention core (the sequence-parallel
    path injects ring attention here; mask is unused then)."""
    B, T, _ = x.shape
    h = _norm(cfg, x, lp["attn_norm_w"], lp.get("attn_norm_b"))
    q, k, v = _qkv(cfg, lp, h, cos, sin)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    with device_scope("attn.core"):
        if attn_fn is not None:
            attn = attn_fn(q, k, v)
        elif cfg.altern_sliding:
            # per-layer window rides the mask (traced); kernel dispatch
            # needs a static window, so alternating archs stay on the
            # einsum path
            attn = attend_hf(q, k, v, mask, scale, cfg.attn_softcap)
        else:
            attn = chunk_attention(cfg, q, k, v, mask, scale, mesh=mesh)
    attn = _proj_out(cfg, lp, attn, B, T)
    return _residual(cfg, lp, x, h, attn), (k, v)


def _block_cached(cfg: ModelConfig, lp, x, cos, sin, k_cache, v_cache,
                  write_pos, mask, scale, attn_fn=None, write_fn=None,
                  attn_len: Optional[int] = None, mesh=None):
    """One layer with a head-first KV cache [B, KvH, S, hd]. ``write_pos``
    [B, T] are absolute slots for the new tokens' K/V. Returns
    (x, k_cache, v_cache) updated. ``write_fn(kc, vc, k, v, pos)`` /
    ``attn_fn(q, kc, vc, pos)`` override the cache write and attention core
    (the sequence-parallel path injects shard-local variants). ``attn_len``
    statically truncates the attended cache prefix (see forward_with_cache)
    — the slice fuses into the attention reads, so slots beyond it cost no
    HBM traffic."""
    B, T, _ = x.shape
    h = _norm(cfg, x, lp["attn_norm_w"], lp.get("attn_norm_b"))
    q, k, v = _qkv(cfg, lp, h, cos, sin)
    k = k.transpose(0, 2, 1, 3)                       # [B, KvH, T, hd]
    v = v.transpose(0, 2, 1, 3)
    with device_scope("attn.kv_write"):
        if write_fn is None:
            KvH = k.shape[1]
            bidx = jnp.arange(B)[:, None, None]
            hidx = jnp.arange(KvH)[None, :, None]
            pidx = write_pos[:, None, :]
            k_cache = k_cache.at[bidx, hidx, pidx].set(
                k.astype(k_cache.dtype))
            v_cache = v_cache.at[bidx, hidx, pidx].set(
                v.astype(v_cache.dtype))
        else:
            k_cache, v_cache = write_fn(k_cache, v_cache, k, v, write_pos)
    with device_scope("attn.core"):
        if attn_fn is None:
            attn = cached_attention(cfg, q, k_cache, v_cache, mask,
                                    write_pos, scale, attn_len=attn_len,
                                    mesh=mesh)
        else:
            attn = attn_fn(q, k_cache, v_cache, write_pos)
    attn = _proj_out(cfg, lp, attn, B, T)
    return _residual(cfg, lp, x, h, attn), k_cache, v_cache


@device_scope("embed")
def _embed(cfg: ModelConfig, params: Params, tokens):
    x = params["tok_emb"][tokens]
    if cfg.emb_scale:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.dim)).astype(x.dtype)
    if cfg.emb_multiplier:
        x = (x.astype(jnp.float32) * cfg.emb_multiplier).astype(x.dtype)
    return x


@device_scope("lm_head")
def _unembed(cfg: ModelConfig, params: Params, x):
    x = _norm(cfg, x, params["out_norm_w"], params.get("out_norm_b"))
    if not cfg.tie_embeddings and Q.is_quantized(params["lm_head"]):
        logits = _mm(cfg, x, params["lm_head"], out_dtype=jnp.float32)
    else:
        head = params["tok_emb"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("btd,dv->btv", x.astype(head.dtype), head,
                            preferred_element_type=jnp.float32)
    if "lm_head_b" in params:
        logits = logits + params["lm_head_b"].astype(jnp.float32)
    if cfg.logit_scale:
        logits = logits / cfg.logit_scale   # granite logits_scaling
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------

def prefill_chunk(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  n_valid: Optional[jax.Array] = None,
                  inputs_embeds: Optional[jax.Array] = None,
                  mesh=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Process a fresh chunk at positions [0, T) with no prior cache.

    tokens  [B, T] int32 (right-padded; padding is masked out of attention by
            the causal structure for queries < n_valid — callers only read
            logits at n_valid-1).
    inputs_embeds — optional [B, T, D] pre-computed embedding sequence
            (multimodal prompts: image tokens from models/vision.py spliced
            between text embeddings); replaces the tok_emb lookup.
    Returns (logits [B, T, V] fp32, k [L, B, KvH, T, hd], v [...]) — K/V
    head-first, matching the cache layout.

    A hybrid stack (cfg.layer_kinds) returns trees in their place:
    ({"kv": k [La, ...], "ssm": [Lm, B, H, P, N]}, {"kv": v, "conv":
    [Lm, B, K-1, C]}), the recurrent state as position ``n_valid - 1``
    left it (``n_valid`` scalar or [B]; None = every position is real),
    and, told ``n_valid``, logits [B, 1, V] of that position alone.
    """
    if cfg.layer_kinds:
        return _hybrid_prefill(params, cfg, tokens, n_valid, inputs_embeds,
                               mesh)
    B, T = tokens.shape
    scale = _attn_scale(cfg)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    cos, sin, cos_l, sin_l = _rope_pair(positions, cfg)
    mask = causal_mask(T, T, 0, sliding_window=cfg.sliding_window)
    mask = jnp.broadcast_to(mask, (B, 1, T, T))

    if inputs_embeds is not None:
        x = inputs_embeds.astype(params["tok_emb"].dtype)
    else:
        x = _embed(cfg, params, tokens)

    if cfg.altern_sliding:
        # gemma2/gemma3: per-layer sliding vs full attention (and, for
        # gemma3, per-layer local vs global rope)
        m_full = jnp.broadcast_to(causal_mask(T, T, 0), (B, 1, T, T))

        def body_a(x, layer_in):
            lp, i = layer_in
            mask_l = _layer_mask(cfg, i, mask, m_full)
            cos_i, sin_i = _layer_rope(cfg, i, cos, sin, cos_l, sin_l)
            x, (k, v) = _block_chunk(cfg, lp, x, cos_i, sin_i, mask_l,
                                     scale, mesh=mesh)
            return x, (k, v)

        x, (ks, vs) = _scan_layers(cfg, body_a, x, params["layers"])
    else:
        def body(x, layer_in):
            x, (k, v) = _block_chunk(cfg, layer_in[0], x, cos, sin, mask,
                                     scale, mesh=mesh)
            return x, (k, v)

        x, (ks, vs) = _scan_layers(cfg, body, x, params["layers"])
    logits = _unembed(cfg, params, x)
    return logits, ks, vs


def forward_with_cache(params: Params, cfg: ModelConfig, tokens: jax.Array,
                       k_cache: jax.Array, v_cache: jax.Array,
                       lengths: jax.Array,
                       attn_len: Optional[int] = None,
                       mesh=None, n_valid: Optional[jax.Array] = None,
                       route_live: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Extend sequences that already have ``lengths`` cached tokens.

    tokens   [B, T] — T=1 is the decode step; T>1 is chunked prefill
             continuation.
    k_cache  [L, B, KvH, S, hd] head-first (donate for in-place update)
    lengths  [B] int32 — number of valid cached tokens per slot.
    attn_len — static attention window: keys are read only from cache
             slots [0, attn_len). Decode is cache-bandwidth-bound, so the
             engine buckets this to the live prefix instead of streaming
             all S slots every step. Requires max(lengths) + T <= attn_len
             (new K/V land below it); None = S.
    n_valid  [B] int32, hybrid stacks only — how many of a row's T new
             positions are real: the recurrent state (which rides in the
             two cache trees, ``join_state``) advances over those alone.
             A decode step passes the active mask; an extend its tail's
             length (and gets logits [B, 1, V] of the tail's last real
             position alone); None = all T.
    route_live [B], routed models only — where given, a fourth value
             comes back: [E] int32, per expert of the router the number of
             rows with route_live != 0 that kept it, over all layers.
    Returns (logits [B, T, V], k_cache, v_cache).
    """
    from ..ops.quant_cache import is_quantized_cache
    B, T = tokens.shape
    k_cache, v_cache, state = split_state(k_cache, v_cache)
    kc_arr = k_cache["q"] if is_quantized_cache(k_cache) else k_cache
    L, _, _, S, _ = kc_arr.shape
    A = S if attn_len is None else min(attn_len, S)
    scale = _attn_scale(cfg)
    positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    cos, sin, cos_l, sin_l = _rope_pair(positions, cfg)
    # key j (absolute slot) is visible to query at absolute pos p iff j <= p,
    # within the sliding window; slots beyond the written region are garbage
    # but satisfy j > p so they are masked.
    k_pos = jnp.arange(A, dtype=jnp.int32)[None, None, :]
    q_pos = positions[:, :, None]

    # a hybrid stack's window belongs to its "w" layers, which attend over
    # rings of their own (``_ring_attend``); its "A" layers see every key
    mask = _causal_window_mask(
        k_pos, q_pos, 0 if cfg.layer_kinds else cfg.sliding_window)
    m_full = (_causal_window_mask(k_pos, q_pos, 0)
              if cfg.altern_sliding else None)

    x = _embed(cfg, params, tokens)

    # The caches ride in the scan CARRY (not xs/ys): scanning over stacked
    # caches makes XLA re-stack the whole [L, B, KvH, S, hd] buffers into
    # fresh ys every step (a multi-GB copy per decode step, measured ~25%
    # of the step on v5e) — the carry aliases in place, and each layer
    # touches only its own scatter-write plus an A-sized window read.
    quant = is_quantized_cache(k_cache)
    KvH, hd = cfg.n_kv_heads, cfg.head_dim
    bidx = jnp.arange(B)[:, None, None]
    hidx = jnp.arange(KvH)[None, :, None]
    pidx = positions[:, None, :]

    def window(c, i, sizes):
        return lax.dynamic_slice(c, (i,) + (0,) * (len(sizes) - 1),
                                 (1,) + sizes[1:])[0]

    def attend(lp, h, kc, vc, i, mask_l, cos_i, sin_i, cfg=cfg):
        """One layer's attention mixer against layer ``i`` of the cache:
        project, write the new keys and values, attend, project out."""
        q, k, v = _qkv(cfg, lp, h, cos_i, sin_i)
        k = k.transpose(0, 2, 1, 3)                   # [B, KvH, T, hd]
        v = v.transpose(0, 2, 1, 3)
        if quant:
            from ..ops import quant_cache as QC
            with device_scope("attn.kv_write"):
                kq, ks = QC.quantize_kv(k)
                vq, vs = QC.quantize_kv(v)
                kc = {"q": kc["q"].at[i, bidx, hidx, pidx].set(kq),
                      "s": kc["s"].at[i, bidx, hidx, pidx].set(ks)}
                vc = {"q": vc["q"].at[i, bidx, hidx, pidx].set(vq),
                      "s": vc["s"].at[i, bidx, hidx, pidx].set(vs)}
            with device_scope("attn.core"):
                kwin = {"q": window(kc["q"], i, (1, B, KvH, A, hd)),
                        "s": window(kc["s"], i, (1, B, KvH, A))}
                vwin = {"q": window(vc["q"], i, (1, B, KvH, A, hd)),
                        "s": window(vc["s"], i, (1, B, KvH, A))}
                attn = QC.attend_hf_q(q, kwin, vwin, mask_l, scale,
                                      cfg.attn_softcap, attn_len=A)
        else:
            with device_scope("attn.kv_write"):
                kc = kc.at[i, bidx, hidx, pidx].set(k.astype(kc.dtype))
                vc = vc.at[i, bidx, hidx, pidx].set(v.astype(vc.dtype))
            with device_scope("attn.core"):
                kwin = window(kc, i, (1, B, KvH, A, hd))
                vwin = window(vc, i, (1, B, KvH, A, hd))
                if cfg.altern_sliding:
                    attn = attend_hf(q, kwin, vwin, mask_l, scale,
                                     cfg.attn_softcap)
                else:
                    attn = cached_attention(cfg, q, kwin, vwin, mask_l,
                                            positions, scale, attn_len=A,
                                            mesh=mesh)
        return _proj_out(cfg, lp, attn, B, T), kc, vc

    load = (None if route_live is None
            else jnp.zeros((cfg.n_experts,), jnp.int32))
    if cfg.layer_kinds:
        nv = _valid_rows(n_valid, B, T)
        cfg_a, cfg_w = _kind_cfgs(cfg)

        def attend_win(ap, h, win, row):
            q, k, v = _qkv(cfg_w, ap, h, cos, sin)
            out, win = _ring_attend(cfg_w, q, k, v, win, row, lengths, nv,
                                    scale, A, mesh)
            return _proj_out(cfg, ap, out, B, T), win

        if cfg.kv_latent_dim:
            cos, sin = _latent_rope(cfg, positions)

            def attend_full(ap, h, kc, vc, row):
                return _latent_cached(cfg, ap, h, kc, vc, row, positions,
                                      nv, A, cos, sin, mesh)
        else:
            def attend_full(ap, h, kc, vc, row):
                return attend(ap, h, kc, vc, row, mask, cos, sin, cfg_a)

        x, k_cache, v_cache, state, load = _hybrid_layers(
            params, cfg, x, k_cache, v_cache, state, nv, attend_full,
            attend_win, route_live, load)
        logits = _unembed(cfg, params, _last_real(x, n_valid))
        return (logits, *join_state(k_cache, v_cache, state), *_given(load))

    def body(carry, layer_in):
        x, kc, vc, load = carry
        lp, i = layer_in
        mask_l = _layer_mask(cfg, i, mask, m_full)
        cos_i, sin_i = _layer_rope(cfg, i, cos, sin, cos_l, sin_l)
        h = _norm(cfg, x, lp["attn_norm_w"], lp.get("attn_norm_b"))
        attn, kc, vc = attend(lp, h, kc, vc, i, mask_l, cos_i, sin_i)
        x, load = _residual_counting(cfg, lp, x, h, attn, route_live, load)
        return (x, kc, vc, load), None

    (x, k_cache, v_cache, load), _ = _scan_layers(
        cfg, body, (x, k_cache, v_cache, load), params["layers"])
    logits = _unembed(cfg, params, x)
    return (logits, k_cache, v_cache, *_given(load))


def _given(load):
    """The trailing value of a forward pass asked for the experts' load."""
    return () if load is None else (load,)


def _residual_counting(cfg: ModelConfig, lp, x, h, attn, live, load,
                       gates=None):
    """``_residual``, and ``load`` [E] advanced by what this layer's router
    kept for the ``live`` rows (``load`` None, or a layer without a router:
    handed back as it came). ``gates``: as ``_moe_mlp`` takes them."""
    if load is None:
        return _residual(cfg, lp, x, h, attn, gates=gates), None
    tap = []
    x = _residual(cfg, lp, x, h, attn, tap, gates)
    return x, (load + _expert_load(tap[0], live) if tap else load)


# --------------------------------------------------------------------------
# hybrid stacks: a recurrent mixer beside attention in one scan
# --------------------------------------------------------------------------
#
# Every layer is  h = x + rm * mixer(norm(x));  h + rm * ffn(norm(h)), and
# the mixer is attention ("A") or the stack's recurrent one by
# cfg.layer_kinds: a Mamba-2 block ("m", granitemoehybrid, whose attention
# has no rotary embedding), a gated short convolution ("c", lfm2, whose
# attention norms q and k and rotates them) or a gated delta-rule
# linear-attention mixer ("d", olmo_hybrid, in a stack without experts whose
# attention has no rotary embedding). The layers run as ONE lax.scan
# whose body traces the shared half (norms, router, experts, residuals)
# once and picks the mixer with lax.cond; each mixer's weights are stacked
# over their own layers only and a layer finds its row through the static
# map of ``_hybrid_rows``. Leading layers with a dense MLP where the rest
# are routed (cfg.n_dense_layers) run as a short scan of their own before
# it, the row map running on across both, so that neither scan carries
# weights it does not use.
#
# What a sequence carries besides keys and values is, per Mamba layer, the
# state S [H, P, N] float32 and the last K-1 inputs of the causal
# convolution [K-1, C] float32; per short-convolution layer the last K-1
# inputs alone [K-1, D]; per delta layer the state S [H, dk, dv] float32 (a
# matrix a head, updated by a rank-one correction of itself) and the last K-1
# inputs of the convolution over [q, k, v], in the same two leaves as a
# Mamba layer's. It cannot be cut back to a prefix, only advanced,
# so every entry point says how many of a row's positions are real
# (``n_valid``): a padded prefill position, a slot that sits inactive in a
# decode batch, leaves it exactly as it was. It travels inside the two
# cache trees (``join_state``), so the engine's programs hand it on as they
# hand on the cache.
#
# A stack of window and full attention ("w" beside "A", exaone_moe) has no
# recurrent mixer: both kinds are attention over ONE stack of projections
# and differ in mask, rotary embedding (cfg.rope_kinds) and cache. An "A"
# layer's keys and values are a full-length row of the cache; a "w" layer's
# are a RING of cfg.sliding_window positions a slot, position p at p % W,
# so slot j holds the newest position congruent to j. The rings are state
# in the sense above: they ride in the cache trees ({"win"} beside the
# keys' and the values' leaves), ``n_valid`` guards every write, and they
# can be advanced and not cut back.

_ATTN_STACK = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
               "q_norm_w", "k_norm_w",
               # latent attention's and its indexer's
               "wq_a", "q_a_norm_w", "wq_b", "wkv_a", "kv_a_norm_w", "w_uk",
               "w_uv", "idx_wq", "idx_wk", "idx_k_norm_w", "idx_k_norm_b",
               "idx_w")
_LAYER_NORMS = ("attn_norm_w", "attn_norm_b", "mlp_norm_w", "mlp_norm_b")
_DENSE_FFN = ("w_gate", "w_up", "w_down", "b_up", "b_down")


def split_state(k_cache, v_cache):
    """(k_cache, v_cache, state): what a slot carries beside its full-length
    keys and values, taken out of the two cache trees. ``state`` is None for
    trees without, else (ssm, conv, win): a Mamba or a delta stack's (ssm,
    conv, None), a short-convolution stack's (None, conv, None), a window
    stack's (None, None, (k rings, v rings)), each ring array [Lw, B, KvH, W,
    hd] or its int8 form {"q", "s" [Lw, B, KvH, W]}."""
    if not (isinstance(v_cache, dict)
            and ("conv" in v_cache or "win" in v_cache)):
        return k_cache, v_cache, None
    kc = {k: v for k, v in k_cache.items() if k not in ("ssm", "win")}
    vc = {k: v for k, v in v_cache.items() if k not in ("conv", "win")}
    if "kv" in kc:
        kc, vc = kc["kv"], vc["kv"]
    win = (k_cache["win"], v_cache["win"]) if "win" in v_cache else None
    return kc, vc, (k_cache.get("ssm"), v_cache.get("conv"), win)


def join_state(k_cache, v_cache, state):
    """Inverse of ``split_state``: {"ssm"} / {"win"} beside the keys'
    leaves, {"conv"} / {"win"} beside the values' (a plain array cache goes
    under "kv")."""
    if state is None or all(x is None for x in state):
        return k_cache, v_cache
    ssm, conv, win = state
    if not isinstance(k_cache, dict):
        k_cache, v_cache = {"kv": k_cache}, {"kv": v_cache}
    if ssm is not None:
        k_cache = {**k_cache, "ssm": ssm}
    if conv is not None:
        v_cache = {**v_cache, "conv": conv}
    if win is not None:
        k_cache, v_cache = ({**k_cache, "win": win[0]},
                            {**v_cache, "win": win[1]})
    return k_cache, v_cache


def empty_state(cfg: ModelConfig, B: int, kv_dtype=jnp.float32):
    """What a sequence carries before its first position, zeros: (ssm [Lm,
    B, H, P, N], conv [Lm, B, K-1, C], None) float32; (ssm [Ld, B, H, dk,
    dv] or, where ``_delta_pack`` says 2, [Ld, B, H/2, dk, 2 dv], conv [Ld,
    B, K-1, 2 H dk + H dv], None) for a stack of delta layers; (None, conv
    [Lc, B, K-1, D], None) for a stack of short
    convolutions; (None, None, (k, v)) for a stack of window layers, rings
    [Lw, B, KvH, W, hd] of ``kv_dtype`` (int8: codes and float32 scales, as
    the cache keeps its rows). None for a stack of attention alone (latent
    attention's: what it keeps, it keeps at every position)."""
    if set(cfg.layer_kinds) == {"A"}:
        return None
    if cfg.n_window_layers:
        shape = (cfg.n_window_layers, B, cfg.n_kv_heads, cfg.sliding_window,
                 cfg.head_dim)

        def ring():
            if jnp.dtype(kv_dtype) == jnp.int8:
                return {"q": jnp.zeros(shape, jnp.int8),
                        "s": jnp.zeros(shape[:-1], jnp.float32)}
            return jnp.zeros(shape, kv_dtype)
        return None, None, (ring(), ring())
    if cfg.n_conv_layers:
        return None, jnp.zeros((cfg.n_conv_layers, B, cfg.conv_kernel - 1,
                                cfg.dim), jnp.float32), None
    if cfg.n_delta_layers:
        Ld, P = cfg.n_delta_layers, _delta_pack(cfg)
        return (jnp.zeros((Ld, B, cfg.delta_heads // P, cfg.delta_key_dim,
                           P * cfg.delta_value_dim), jnp.float32),
                jnp.zeros((Ld, B, cfg.delta_conv - 1, cfg.delta_conv_dim),
                          jnp.float32), None)
    Lm = cfg.n_ssm_layers
    return (jnp.zeros((Lm, B, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state), jnp.float32),
            jnp.zeros((Lm, B, cfg.ssm_conv - 1, cfg.ssm_conv_dim),
                      jnp.float32), None)


def quantize_rings(state):
    """``state`` with a prefill's float rings as the int8 cache keeps them
    (an admission's, before it goes into the slot's rows)."""
    from ..ops.quant_cache import quantize_kv
    ssm, conv, win = state
    if win is not None:
        win = tuple(dict(zip("qs", quantize_kv(r))) for r in win)
    return ssm, conv, win


def _hybrid_rows(cfg: ModelConfig):
    """(is_attn [L] bool, row [L], wrow): each layer's kind ("A" or the
    stack's other one), its row among the layers of its own kind (the
    state's or the cache's row) and its row in its weights' stack; host
    lists, for the scans to cut. ``wrow`` is None where the two are the
    same: only "w" layers, which share the "A" layers' stack of
    projections, count their weights' rows over both kinds."""
    rows, wrows, n = [], [], {"A": 0, "m": 0, "c": 0, "d": 0, "w": 0}
    for c in cfg.layer_kinds:
        rows.append(n[c])
        wrows.append(n["A"] + n["w"] if c in "Aw" else n[c])
        n[c] += 1
    return ([c == "A" for c in cfg.layer_kinds], rows,
            wrows if n["w"] else None)


def _kind_cfgs(cfg: ModelConfig):
    """(cfg of the "A" layers, cfg of the "w" layers): the stack's config
    with the window and the rotary embedding as each kind has them."""
    def rotates(kind):
        return cfg.rope and (not cfg.rope_kinds or kind in cfg.rope_kinds)
    return (dataclasses.replace(cfg, sliding_window=0, rope=rotates("A")),
            dataclasses.replace(cfg, rope=rotates("w")))


def _ring_pos(last, W: int):
    """[B, W] int32: the position slot j of a ring holds once position
    ``last`` [B] is written, the newest one <= last congruent to j mod W;
    negative where no position has reached the slot."""
    j = jnp.arange(W, dtype=jnp.int32)[None, :]
    return last[:, None] - (last[:, None] - j) % W


def _ring_merge(old, new, lengths, n_valid):
    """A ring advanced over a block: old [B, KvH, W, ...] as position
    lengths - 1 left it, new [B, KvH, T, ...] the block's positions, of
    which the first n_valid [B] are real. Slot j takes the block's newest
    real position congruent to j where there is one and keeps its own
    otherwise, so a row with nothing real keeps its very bits."""
    W, T = old.shape[2], new.shape[2]
    t = _ring_pos(lengths + n_valid - 1, W) - lengths[:, None]     # [B, W]
    at = jnp.clip(t, 0, T - 1)[:, None, :]
    fresh = t[:, None, :] >= 0
    if old.ndim == 4:
        at, fresh = at[..., None], fresh[..., None]
    if T > 1:       # one new position broadcasts to the slot that takes it
        new = jnp.take_along_axis(new, at, axis=2)
    return jnp.where(fresh, new, old)


# A decode step puts its one new position into a ring either way. The
# select over the whole ring (``_ring_merge``) reads and writes every slot of
# the layer's ring: 2 x B x KvH x W x (hd + 4) bytes of int8 codes and scales,
# twice. The row write addresses one (slot, head) at a time, as a full
# layer's ``attn.kv_write`` does, whatever W is. A ring of at most this many
# positions takes the select, a longer one the row (PERF.md section 6, PR 50,
# has both forms' times at W = 128 and W = 4,096 on the chip). The line is
# also where the read changes: a short ring is attended whole through the
# einsum form, a long one on a chip through ``ops/pallas/ring.ring_decode``,
# each slot to its own live depth (``_ring_kernel``; PERF.md section 6,
# PR 52).
_RING_SELECT_MAX = 512


def _ring_put(ring, row, new, at, live):
    """The ring stack [Lw, B, KvH, W, ...] with new [B, KvH, 1, ...] at ring
    slot ``at`` [B] of layer ``row``, for the rows ``live`` [B] marks; the
    others' slot is written back as it was read, so they keep their very
    bits."""
    B, KvH = new.shape[:2]
    idx = (row, jnp.arange(B)[:, None, None], jnp.arange(KvH)[None, :, None],
           at[:, None, None])
    keep = (live != 0).reshape((B,) + (1,) * (new.ndim - 1))
    return ring.at[idx].set(jnp.where(keep, new, ring[idx]))


def _ring_kernel(cfg: ModelConfig, mesh, q, row, lengths, n_valid, scale):
    """The one place that decides how a window layer's ring is attended:
    ``ops/pallas/ring.ring_decode`` over layer ``row`` of the rings
    themselves, each slot to its own live depth, for one new position (T ==
    1) against a ring longer than ``_RING_SELECT_MAX``, on one device, where
    ``cfg.kernels`` resolves to a kernel and the shapes tile
    (``ring_decode_tileable``); else None, and the caller reads the ring
    through the einsum form: by design for a short ring and for T > 1 (an
    ``extend`` piece, an admission, the probe's prefill), flagged
    ``kernel_fallback`` where a long ring wanted the kernel. Returns win (the
    rings, the new position written) -> out [B, 1, H, hd]."""
    from ..ops.attention import resolve_kernels
    from ..ops.pallas.ring import ring_decode, ring_decode_tileable
    B, T, H, hd = q.shape
    W = cfg.sliding_window
    mode = resolve_kernels(cfg.kernels)
    wanted = (T == 1 and W > _RING_SELECT_MAX
              and mode in ("pallas", "interpret"))
    interp = mode == "interpret"
    if not (wanted and (mesh is None or mesh.size == 1)
            and ring_decode_tileable(B, H, cfg.n_kv_heads, hd, W, interp)):
        note_kernel("window", "einsum", fell_back=wanted)
        return None
    note_kernel("window", "ring_decode")
    return lambda win: ring_decode(
        win[0], win[1], row, q[:, 0], lengths, n_valid, scale,
        cfg.attn_softcap, interpret=interp)[:, None]


@device_scope("attn.window")
def _ring_attend(cfg: ModelConfig, q, k, v, win, row, lengths, n_valid,
                 scale, depth: Optional[int] = None, mesh=None):
    """Window attention of one layer over its ring, and the ring advanced.
    q [B, T, H, hd], k and v [B, T, KvH, hd] at positions lengths + t (as
    ``_qkv`` leaves them); win = (k rings, v rings) [Lw, B, KvH, W, hd] or
    int8 {"q", "s"}, of which this layer reads and writes row ``row``;
    n_valid [B]: positions at or past it write nothing. A key is visible
    iff its position is >= 0, <= the query's and > the query's - W.

    ``depth`` (static) is the caller's attended prefix, as a full layer's
    ``attn_len``: every row's lengths + T <= depth. The einsum form reads the
    ring that deep and no deeper: below W no ring has wrapped, so slot j IS
    position j and the first ``depth`` slots hold every key there is; at W
    or past it the whole ring is the window.

    One new position (the decode step) is written a row a slot where the
    ring is long (``_ring_put``) and by a select over the whole ring where
    it is short (``_RING_SELECT_MAX``), then attends over the ring with
    itself in it, which IS its window. A long ring on a chip is read by
    ``ring_decode`` (``_ring_kernel``), each slot's own min(lengths + 1, W)
    ring slots where they lie: the bucket, which the longest live context
    sets, no longer bounds what a slot reads, and a step's ring traffic is
    the live positions'. Elsewhere the einsum form reads ``depth`` slots of
    every ring. Several positions (a prefill piece, one slot's) attend over
    the ring as it stood plus the new block, and the ring takes the block's
    last W real positions by the select (``_ring_merge``: a
    block may wrap the ring several times over). Returns (out [B, T, H,
    hd], win)."""
    from ..ops import quant_cache as QC
    T, W = q.shape[1], cfg.sliding_window
    A = W if depth is None else min(depth, W)
    quant = QC.is_quantized_cache(win[0])
    k = k.transpose(0, 2, 1, 3)                       # [B, KvH, T, hd]
    v = v.transpose(0, 2, 1, 3)
    tree_map = jax.tree_util.tree_map

    def stored(x, like):
        if quant:
            return dict(zip("qs", QC.quantize_kv(x)))
        return x.astype(like.dtype)

    def attend(ks, vs, k_pos, q_pos):
        k_pos, q_pos = k_pos[:, None, :], q_pos[:, :, None]
        ok = (k_pos >= 0) & (k_pos <= q_pos) & (k_pos > q_pos - W)
        mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[:, None]
        if quant:
            return QC.attend_hf_q(q, ks, vs, mask, scale, cfg.attn_softcap)
        return attend_hf(q, ks.astype(q.dtype), vs.astype(q.dtype), mask,
                         scale, cfg.attn_softcap)

    def layer(ring, n):
        """The first n slots of this layer's ring, [B, KvH, n, ...] (the
        whole ring as PR 39 took it, so that a short ring's programs stay
        the ones the ledger measured)."""
        if n == W:
            return lax.dynamic_index_in_dim(ring, row, 0, keepdims=False)
        return lax.squeeze(lax.dynamic_slice(
            ring, (row,) + (0,) * (ring.ndim - 1),
            (1,) + ring.shape[1:3] + (n,) + ring.shape[4:]), (0,))

    new = (stored(k, win[0]), stored(v, win[1]))
    q_pos = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    kernel = _ring_kernel(cfg, mesh, q, row, lengths, n_valid, scale)
    if T == 1 and W > _RING_SELECT_MAX:
        win = tree_map(
            lambda ring, x: _ring_put(ring, row, x, lengths % W, n_valid),
            win, new)
        if kernel is not None:
            return kernel(win), win
        cur = tree_map(lambda ring: layer(ring, A), win)
        return attend(*cur, _ring_pos(lengths, W)[:, :A], q_pos), win
    old = tree_map(lambda ring: layer(ring, W), win)
    merged = tree_map(lambda a, b: _ring_merge(a, b, lengths, n_valid),
                      old, new)
    win = tree_map(
        lambda ring, x: lax.dynamic_update_index_in_dim(ring, x, row, 0),
        win, merged)

    def head(x):
        return x if A == W else tree_map(lambda a: a[:, :, :A], x)
    if T == 1:
        return attend(*head(merged), _ring_pos(lengths, W)[:, :A],
                      q_pos), win
    both = tree_map(lambda a, b: jnp.concatenate([a, b], axis=2), head(old),
                    new)
    return attend(*both, jnp.concatenate(
        [_ring_pos(lengths - 1, W)[:, :A], q_pos], axis=1), q_pos), win


def _valid_rows(n_valid, B: int, T: int):
    """n_valid (None, scalar or [B]) -> [B] int32."""
    if n_valid is None:
        return jnp.full((B,), T, jnp.int32)
    return jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))


def _last_real(x, n_valid):
    """x [B, T, D] -> [B, 1, D] at each row's last real position, where the
    caller said how many are real (``n_valid``): an admission samples from
    that position alone, and the head over a whole padded bucket is the
    largest array of a prefill ([4, 4096, 50176] float32 is 3 GB beside
    9.5 GB of weights). Without ``n_valid`` every position is kept."""
    if n_valid is None or x.shape[1] == 1:
        return x
    at = jnp.maximum(_valid_rows(n_valid, *x.shape[:2]) - 1, 0)
    return jnp.take_along_axis(x, at[:, None, None], axis=1)


def _ssm_scan(cfg: ModelConfig, S0, x, dt, a, Bm, Cm):
    """The selective state update over T positions from state S0, exact:
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t.

    S0 [B, H, P, N]; x [B, T, H, P]; dt [B, T, H] (0 where a position is
    not real: then S_t = S_{t-1} exactly); a [H] < 0; Bm, Cm [B, T, N].
    All float32. One position is the recurrence as written (a pass over
    the state on the vector unit). More go block by block (cfg.ssm_chunk,
    Mamba-2's SSD form): inside a block every pair of positions through
    the decays' running sums, between blocks the state. Returns
    (y [B, T, H, P], S_T)."""
    B, T, H, P = x.shape
    hi = lax.Precision.HIGHEST
    if T == 1:
        dA = jnp.exp(dt[:, 0] * a)                              # [B, H]
        dBx = (dt[:, 0, :, None] * x[:, 0])[..., None] \
            * Bm[:, 0, None, None, :]
        S1 = S0 * dA[:, :, None, None] + dBx
        y = (S1 * Cm[:, 0, None, None, :]).sum(-1)              # [B, H, P]
        return y[:, None], S1
    Q = min(cfg.ssm_chunk, T)
    pad = -T % Q
    if pad:
        # dt = 0 there: the state passes through, the outputs are cut off
        x, dt, Bm, Cm = (jnp.pad(v, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (v.ndim - 2))
                         for v in (x, dt, Bm, Cm))
    nC = (T + pad) // Q

    def blocks(v):                       # [B, nC*Q, ...] -> [nC, B, Q, ...]
        return jnp.moveaxis(v.reshape(B, nC, Q, *v.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def block(S, xs):
        xb, dtb, Bb, Cb = xs
        cum = jnp.cumsum(dtb * a, axis=1)                       # [B, Q, H]
        # decay from position s to position t >= s of the block
        seg = cum[:, :, None, :] - cum[:, None, :, :]           # [B, t, s, H]
        seg = jnp.exp(jnp.where(tri[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("btn,bsn->bts", Cb, Bb, precision=hi)
        w = cb[..., None] * seg * dtb[:, None, :, :]            # [B, t, s, H]
        y = jnp.einsum("btsh,bshp->bthp", w, xb, precision=hi)
        y = y + jnp.einsum("btn,bhpn->bthp", Cb, S, precision=hi) \
            * jnp.exp(cum)[..., None]
        rest = jnp.exp(cum[:, -1:, :] - cum) * dtb              # [B, Q, H]
        S = S * jnp.exp(cum[:, -1])[:, :, None, None] + jnp.einsum(
            "bsh,bshp,bsn->bhpn", rest, xb, Bb, precision=hi)
        return S, y

    S, ys = lax.scan(block, S0, tuple(blocks(v) for v in (x, dt, Bm, Cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nC * Q, H, P)
    return y[:, :T], S


def _causal_conv(conv, row, new, w, n_valid, bias=None, act=None):
    """Depthwise causal convolution of one layer over [the K-1 inputs its
    sequence carries, the T new ones]. conv [Lr, B, K-1, C] float32, of
    which this layer reads and writes row ``row``; new [B, T, C]; w [K, C]
    (tap j weighs the input K-1-j positions back); n_valid [B]: the K-1
    inputs kept are those before position n_valid[b], so positions at or
    past it leave the state as it was. Returns (out [B, T, C] float32 =
    act(bias + sum_j w[j] * in[t-K+1+j]), conv)."""
    K, T, f32 = w.shape[0], new.shape[1], jnp.float32
    prev = lax.dynamic_index_in_dim(conv, row, 0, keepdims=False)
    new = new.astype(f32)
    cat = jnp.concatenate([prev, new], axis=1)                  # [B,T+K-1,C]
    w = w.astype(f32)
    out = None if bias is None else bias.astype(f32)
    for j in range(K):
        tap = w[j] * cat[:, j:j + T]
        out = tap if out is None else out + tap
    if act is not None:
        out = act(out)
    # the K-1 inputs before position n_valid: what the next call's
    # first positions look back on
    if T == 1:
        # a decode step: a row that takes the position keeps its inputs
        # shifted by one, a row that sits out keeps them: a select over
        # whole arrays, the same bits as the gather, which the compiler
        # runs as a loop of one-row updates over the slots (2.10 ms of
        # olmo's 17.73 ms step). Written position-major, the order the
        # leaf lies in on the device: as ``where(.., cat[:, 1:], prev)``
        # the compiler carried the leaf in another layout and re-laid a
        # 398 MB weight stack in every delta layer (+11 ms a step: my chip
        # run, PR 47; PERF.md section 6 has the forms tried)
        held = jnp.swapaxes(prev, 0, 1)                         # [K-1, B, C]
        shifted = jnp.concatenate([held[1:], jnp.swapaxes(new, 0, 1)], axis=0)
        prev = jnp.swapaxes(
            jnp.where((n_valid > 0)[None, :, None], shifted, held), 0, 1)
    else:
        prev = jax.vmap(lambda c, n: lax.dynamic_slice_in_dim(c, n, K - 1, 0)
                        )(cat, n_valid)
    return out, lax.dynamic_update_index_in_dim(conv, prev, row, 0)


def _conv_mixer(cfg: ModelConfig, cp, u, conv, row, n_valid):
    """Gated short convolution of one layer (lfm2): [B, C, v] = split3(u
    W_in); out = (C * causal_conv(B * v)) W_out, no bias, no activation.
    u [B, T, D] (normed); conv [Lc, B, K-1, D] float32, row ``row`` this
    layer's. Returns (out [B, T, D], conv)."""
    D = cfg.dim
    with device_scope("conv.in_proj"):
        bcv = _mm(cfg, u, cp["conv_in"])
        gate_b, gate_c, v = bcv[..., :D], bcv[..., D:2 * D], bcv[..., 2 * D:]
    with device_scope("conv.conv"):
        c, conv = _causal_conv(conv, row, gate_b * v, cp["conv_w"], n_valid)
        y = (gate_c.astype(jnp.float32) * c).astype(u.dtype)
    with device_scope("conv.out"):
        out = _mm(cfg, y, cp["conv_out"])
    return out, conv


def _ssm_mixer(cfg: ModelConfig, sp, u, ssm, conv, row, n_valid):
    """Mamba-2 mixer of one layer. u [B, T, D] (normed); ssm [Lm, B, H, P,
    N] and conv [Lm, B, K-1, C] float32, of which this layer reads and
    writes row ``row``; n_valid [B]: positions >= n_valid[b] change
    neither. Returns (out [B, T, D], ssm, conv)."""
    B, T, _ = u.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    di, C = cfg.ssm_inner, cfg.ssm_conv_dim
    f32 = jnp.float32
    valid = jnp.arange(T)[None, :] < n_valid[:, None]           # [B, T]
    with device_scope("ssm.in_proj"):
        zxd = _mm(cfg, u, sp["ssm_in"])
        z, xbc, dt = zxd[..., :di], zxd[..., di:di + C], zxd[..., di + C:]
    with device_scope("ssm.conv"):
        xc, conv = _causal_conv(conv, row, xbc, sp["ssm_conv_w"], n_valid,
                                sp["ssm_conv_b"], jax.nn.silu)
    with device_scope("ssm.scan"):
        x = xc[..., :di].reshape(B, T, H, P)
        Bm, Cm = xc[..., di:di + N], xc[..., di + N:]
        dt = jax.nn.softplus(dt.astype(f32) + sp["ssm_dt_bias"].astype(f32))
        dt = jnp.where(valid[..., None], dt, 0.0)
        a = -jnp.exp(sp["ssm_a_log"].astype(f32))
        S0 = lax.dynamic_index_in_dim(ssm, row, 0, keepdims=False)
        y, S1 = _ssm_scan(cfg, S0, x, dt, a, Bm, Cm)
        # dt = 0 already leaves S where it was up to rounding of 1 * S + 0;
        # a row with nothing real keeps its very bits
        S1 = jnp.where((n_valid > 0)[:, None, None, None], S1, S0)
        ssm = lax.dynamic_update_index_in_dim(ssm, S1, row, 0)
        y = y + sp["ssm_d"].astype(f32)[:, None] * x
    with device_scope("ssm.gate_norm"):
        y = y.reshape(B, T, di) * jax.nn.silu(z.astype(f32))
        y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.norm_eps) * sp["ssm_norm_w"].astype(f32)
    with device_scope("ssm.out"):
        out = _mm(cfg, y.astype(u.dtype), sp["ssm_out"])
    return out, ssm, conv


def _delta_pack(cfg: ModelConfig) -> int:
    """How many heads of a delta layer's state lie side by side along the
    last axis of the ``ssm`` leaf, [Ld, B, H / P, dk, P dv]: 2 where a head's
    row of dv values would lie padded in 128-lane tiles and two heads' rows
    fill whole ones, else 1. The published head (dv 192) takes 256 lanes
    alone and shares 384 with its neighbour: the decode step's pass over
    1.35 GB of state moved 1.80 GB, 2.64 ms for 1.99 (my chip run, PR 45).
    Only ``empty_state``, ``_delta_mixer`` and the kernel know: everything
    else sees a leaf by its first two axes and its bytes."""
    dv = cfg.delta_value_dim
    return 2 if (dv % 128 and not 2 * dv % 128
                 and cfg.delta_heads % 2 == 0) else 1


def _delta_packed(S, P: int):
    """[B, H, dk, dv] -> [B, H / P, dk, P dv]: head P g + p into lanes [p dv,
    (p + 1) dv) of group g."""
    if P == 1:
        return S
    B, H, dk, dv = S.shape
    return S.reshape(B, H // P, P, dk, dv).transpose(0, 1, 3, 2, 4).reshape(
        B, H // P, dk, P * dv)


def _delta_unpacked(S, P: int):
    """``_delta_packed``'s inverse."""
    if P == 1:
        return S
    B, G, dk, W = S.shape
    return S.reshape(B, G, dk, P, W // P).transpose(0, 1, 3, 2, 4).reshape(
        B, G * P, dk, W // P)


def _delta_rule(cfg: ModelConfig, S0, q, k, v, g, beta):
    """The gated delta rule over T positions from state S0, exact:
    S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T;  o_t = S_t^T q_t.

    S0 [B, H, dk, dv]; q, k [B, T, H, dk]; v [B, T, H, dv]; g <= 0 and beta
    [B, T, H], both 0 where a position is not real: then S_t = S_{t-1}. All
    float32. One position is the recurrence as written, on the vector unit:
    the compiler's form, four passes that read the state three times and
    write it once. The decode step takes it only where ``_delta_step``'s
    kernel, which passes over the state once, does not engage (4.87 ms a
    step of nine layers at 32 slots for the kernel's 1.99, where both
    read-outs from S0 in one MXU pass took 3.88 and the bytes alone would
    take 1.65: hack/delta_microbench.py, my chip run, PR 45). More go block
    by block (cfg.delta_chunk,
    Gated DeltaNet's form): with c the running sum of g inside a block, the
    block's u solve (I + A) U = beta (V - exp(c) K S0), A[t, s] = beta_t
    exp(c_t - c_s) (k_t . k_s) for s < t, a unit lower triangular system
    solved by forward substitution (its inverse row by row, every block at
    once: it does not hang on the state); then o_t = exp(c_t) S0^T q_t +
    sum_{s <= t} exp(c_t - c_s) (k_s . q_t) u_s, and between blocks the
    state. Returns (o [B, T, H, dv], S_T)."""
    B, T, H, dk = q.shape
    hi = lax.Precision.HIGHEST
    if T == 1:
        k1, q1 = k[:, 0], q[:, 0]
        Sp = jnp.exp(g[:, 0])[..., None, None] * S0
        u = beta[:, 0, :, None] * (v[:, 0] - (Sp * k1[..., None]).sum(2))
        S1 = Sp + k1[..., None] * u[:, :, None, :]
        return (S1 * q1[..., None]).sum(2)[:, None], S1
    C = min(cfg.delta_chunk, T)
    pad = -T % C
    if pad:
        # g = 0 and beta = 0 there: the state passes through, the outputs
        # are cut off
        q, k, v, g, beta = (jnp.pad(x, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    nC = (T + pad) // C

    def blocks(x):                  # [B, nC*C, H, ...] -> [B, nC, H, C, ...]
        return jnp.moveaxis(x.reshape(B, nC, C, *x.shape[2:]), 3, 2)

    q, k, v, g, beta = (blocks(x) for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-1)                            # [B, nC, H, C]
    tri = jnp.tril(jnp.ones((C, C), bool))
    # decay from position s to position t >= s of the block
    seg = jnp.exp(jnp.where(tri, cum[..., :, None] - cum[..., None, :],
                            -jnp.inf))                      # [.., t, s]
    kk = jnp.einsum("bnhtd,bnhsd->bnhts", k, k, precision=hi)
    A = jnp.where(jnp.tril(tri, -1), beta[..., None] * kk * seg, 0.0)

    def solve_row(t, inv):
        # row t of (I + A)^-1: e_t - sum_{s < t} A[t, s] inv[s], the rows
        # before it final, the rows from it on still the identity's
        a_t = lax.dynamic_index_in_dim(A, t, 3, keepdims=False)
        row = lax.dynamic_index_in_dim(inv, t, 3, keepdims=False) \
            - jnp.einsum("bnhs,bnhsj->bnhj", a_t, inv, precision=hi)
        return lax.dynamic_update_index_in_dim(inv, row, t, 3)

    inv = lax.fori_loop(1, C, solve_row, jnp.broadcast_to(
        jnp.eye(C, dtype=jnp.float32), A.shape))
    W = jnp.einsum("bnhts,bnhsd->bnhtd", inv,
                   (beta * jnp.exp(cum))[..., None] * k, precision=hi)
    Vt = jnp.einsum("bnhts,bnhsd->bnhtd", inv, beta[..., None] * v,
                    precision=hi)
    qk = jnp.einsum("bnhtd,bnhsd->bnhts", q, k, precision=hi) * seg

    def block(S, xs):
        qb, kb, Wb, Vb, qkb, cb = xs
        U = Vb - jnp.einsum("bhtd,bhdv->bhtv", Wb, S, precision=hi)
        o = jnp.exp(cb)[..., None] * jnp.einsum(
            "bhtd,bhdv->bhtv", qb, S, precision=hi) \
            + jnp.einsum("bhts,bhsv->bhtv", qkb, U, precision=hi)
        rest = jnp.exp(cb[..., -1:] - cb)                   # [B, H, C]
        S = jnp.exp(cb[..., -1])[..., None, None] * S + jnp.einsum(
            "bhs,bhsd,bhsv->bhdv", rest, kb, U, precision=hi)
        return S, o

    S, os_ = lax.scan(block, S0, tuple(jnp.moveaxis(x, 1, 0) for x in
                                       (q, k, W, Vt, qk, cum)))
    # [nC, B, H, C, dv] -> [B, nC*C, H, dv]
    o = jnp.transpose(os_, (1, 0, 3, 2, 4)).reshape(B, nC * C, H, -1)
    return o[:, :T], S


def _delta_step(cfg: ModelConfig, ssm, row, q, k, v, g, beta, n_valid):
    """One position of the gated delta rule on row ``row`` of the carried
    leaf ``ssm`` (``empty_state``'s, in either layout), through the kernel
    that passes over the state once (ops/pallas/delta.py), where
    ``cfg.kernels`` resolves to one and the heads tile; q, k, v, g, beta as
    ``_delta_rule`` takes them with T = 1, n_valid [B]. Returns (o [B, 1, H,
    dv], ssm) or None: the caller then takes ``_delta_rule``'s four passes."""
    from ..ops.attention import resolve_kernels
    mode = resolve_kernels(cfg.kernels)
    if mode not in ("pallas", "interpret"):
        note_kernel("delta.update", "xla_recurrence")
        return None
    from ..ops.pallas.delta import delta_update
    out = delta_update(ssm, row, q[:, 0], k[:, 0], v[:, 0],
                       jnp.exp(g[:, 0]), beta[:, 0], n_valid,
                       interpret=mode == "interpret")
    if out is None:
        note_kernel("delta.update", "xla_recurrence", fell_back=True)
        return None
    note_kernel("delta.update", "delta_update")
    o, ssm = out
    return o[:, None], ssm


def _delta_mixer(cfg: ModelConfig, dp, u, ssm, conv, row, n_valid):
    """Gated delta-rule mixer of one layer (olmo_hybrid's linear attention;
    Gated DeltaNet). [q, k, v] = silu(causal_conv(u W_qkv)) (K taps, no
    bias); a head: q = q / |q| / sqrt(dk), k = k / |k| (L2 over dk, eps
    1e-6 under the root); beta = sigmoid(u W_b), doubled where
    cfg.delta_neg_eigval; g = -exp(A_log) softplus(u W_a + dt_bias); the
    state by ``_delta_rule``; y = RMSNorm_dv(o; w) * silu(u W_z) a head;
    out = y W_o. u [B, T, D] (normed); ssm (``empty_state``'s: [Ld, B, H /
    P, dk, P dv], P ``_delta_pack``'s) and conv [Ld, B, K-1, C] float32, of
    which this layer reads and writes row ``row``;
    n_valid [B]: positions >= n_valid[b] change neither. Returns (out [B,
    T, D], ssm, conv)."""
    B, T, _ = u.shape
    H, dk, dv = cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim
    f32 = jnp.float32
    valid = (jnp.arange(T)[None, :] < n_valid[:, None])[..., None]
    with device_scope("delta.in_proj"):
        qkv = _mm(cfg, u, dp["delta_qkv"])
        ab = _mm(cfg, u, dp["delta_ab"]).astype(f32)
        z = _mm(cfg, u, dp["delta_z"])
    with device_scope("delta.conv"):
        qkv, conv = _causal_conv(conv, row, qkv, dp["delta_conv_w"], n_valid,
                                 act=jax.nn.silu)
    with device_scope("delta.update"):
        q = qkv[..., :H * dk].reshape(B, T, H, dk)
        k = qkv[..., H * dk:2 * H * dk].reshape(B, T, H, dk)
        v = qkv[..., 2 * H * dk:].reshape(B, T, H, dv)
        q = q * lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) * dk ** -0.5
        k = k * lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
        beta = jax.nn.sigmoid(ab[..., H:]) * (2.0 if cfg.delta_neg_eigval
                                              else 1.0)
        g = -jnp.exp(dp["delta_a_log"].astype(f32)) * jax.nn.softplus(
            ab[..., :H] + dp["delta_dt_bias"].astype(f32))
        g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
        step = _delta_step(cfg, ssm, row, q, k, v, g, beta, n_valid) \
            if T == 1 else None
        if step is not None:
            o, ssm = step
        else:
            # the rule takes a head's matrix by itself: the leaf's layout
            # ends here
            P = _delta_pack(cfg)
            S0 = lax.dynamic_index_in_dim(ssm, row, 0, keepdims=False)
            o, S1 = _delta_rule(cfg, _delta_unpacked(S0, P), q, k, v, g, beta)
            # g = 0 and beta = 0 already leave S where it was up to
            # rounding; a row with nothing real keeps its very bits
            S1 = jnp.where((n_valid > 0)[:, None, None, None],
                           _delta_packed(S1, P), S0)
            ssm = lax.dynamic_update_index_in_dim(ssm, S1, row, 0)
    with device_scope("delta.gate_norm"):
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps) * dp["delta_norm_w"].astype(f32)
        y = o * jax.nn.silu(z.astype(f32).reshape(B, T, H, dv))
    with device_scope("delta.out"):
        out = _mm(cfg, y.reshape(B, T, H * dv).astype(u.dtype),
                  dp["delta_out"])
    return out, ssm, conv


def _hybrid_layers(params: Params, cfg: ModelConfig, x, kc, vc, state,
                   n_valid, attend, attend_win=None, live=None, load=None):
    """The layer scans of a hybrid stack. ``attend(ap, h, kc, vc, row) ->
    (out, kc, vc)`` is the caller's attention mixer against row ``row`` of
    its keys and values (a fresh chunk's or the cache's), ``attend_win(ap,
    h, win, row) -> (out, win)`` its window mixer against row ``row`` of
    the rings. ``state`` is ``split_state``'s. Everything a layer may write
    rides the carry, and each mixer hands the others' through untouched.
    ``load`` [E] int32 (or None) is advanced by what each router kept for
    the ``live`` rows (``_residual_counting``). Returns (x, kc, vc, state,
    load)."""
    layers = params["layers"]
    # A delta stack carries its residual stream, and what its delta mixers
    # and MLPs hand on between their matmuls, float32: bfloat16 goes into the
    # MXU (``_mm``) and into attention only. Its sublayers have no router's
    # mean and no multiplier under 1 before the residual add: at the
    # published widths each passes a perturbation of its input on LARGER
    # (seeded weights of 0.02 give the MLP a gain of 0.02 sqrt(3840) x 0.02
    # sqrt(11008) = 2.6), so with every handed-on value rounded to bfloat16
    # the logits twelve layers on lay 2.6-3.6% of the largest from a float32
    # reference's (the other stacks: 1.0-2.2%); unrounded, 1.6-2.6% (my chip
    # runs, PR 44, 8 and 20 readings)
    # A latent-attention stack does the same: every layer's attention reads
    # ONE int8 latent for all its heads' keys and values, whose rounding
    # (0.8% of a layer's attention output, the same for every head: it does
    # not average out) comes on top, and with the stream rounded as well the
    # decode step's logits lay 1.9-2.7% from the reference's on six seeds
    # (my chip run, PR 46, call 1)
    act_dtype = x.dtype
    if cfg.n_delta_layers or cfg.kv_latent_dim:
        x = x.astype(jnp.float32)
    # the other mixer's leaves, by their names' prefix; window layers have
    # none of their own
    prefix = ("ssm_" if cfg.n_ssm_layers else
              "conv_" if cfg.n_conv_layers else
              "delta_" if cfg.n_delta_layers else None)
    attn_stack = {k: v for k, v in layers.items() if k in _ATTN_STACK}
    rec_stack = {k: v for k, v in layers.items()
                 if prefix and k.startswith(prefix)}
    shared = {k: v for k, v in layers.items()
              if k not in attn_stack and k not in rec_stack}

    def take(stack, row):
        return jax.tree_util.tree_map(
            lambda w: lax.dynamic_index_in_dim(w, row, 0, keepdims=False),
            stack)

    def body(cfg, carry, layer_in):
        x, kc, vc, ssm, conv, win, load = carry
        lp, is_attn, row, wrow = layer_in
        wrow = row if wrow is None else wrow
        gates = None
        if cfg.n_experts and cfg.moe_router_input == "block":
            # a router that reads the layer's INPUT, un-normed, ahead of
            # the mixer (smallthinker): its gates are ready before
            # attention starts, and the experts take them as given
            with device_scope("moe.route"):
                gates = _moe_gates(cfg, lp, x.reshape(-1, x.shape[-1]))
        h = _norm(cfg, x, lp["attn_norm_w"], lp.get("attn_norm_b"))

        def attn_mixer(h, kc, vc, ssm, conv, win):
            out, kc, vc = attend(take(attn_stack, wrow), h.astype(act_dtype),
                                 kc, vc, row)
            return out.astype(h.dtype), kc, vc, ssm, conv, win

        def other_mixer(h, kc, vc, ssm, conv, win):
            if win is not None:
                out, win = attend_win(take(attn_stack, wrow), h, win, row)
                return out, kc, vc, ssm, conv, win
            rp = take(rec_stack, row)
            if ssm is None:
                out, conv = _conv_mixer(cfg, rp, h, conv, row, n_valid)
            elif cfg.n_delta_layers:
                out, ssm, conv = _delta_mixer(cfg, rp, h, ssm, conv, row,
                                              n_valid)
            else:
                out, ssm, conv = _ssm_mixer(cfg, rp, h, ssm, conv, row,
                                            n_valid)
            return out, kc, vc, ssm, conv, win

        # the stack's other mixer is the TRUE branch on purpose: with the
        # branches the other way round the TPU compiler hands the whole
        # state through the attention layer's branch by a copy (1.24 GB of
        # Mamba state at 32 slots, 2.9 ms of a 24.8 ms decode step: my chip
        # run, PR 29), this way round both branches update or pass their
        # buffers in place
        if prefix is None and win is None:
            # a stack of attention alone (latent attention): no branch
            mixed = attn_mixer(h, kc, vc, ssm, conv, win)
        else:
            mixed = lax.cond(~is_attn, other_mixer, attn_mixer, h, kc, vc,
                             ssm, conv, win)
        out, kc, vc, ssm, conv, win = mixed
        x, load = _residual_counting(cfg, lp, x, h, out, live, load, gates)
        return (x, kc, vc, ssm, conv, win, load), None

    is_attn, rows, wrows = _hybrid_rows(cfg)
    L, Ld = cfg.n_layers, cfg.n_dense_layers
    spans = [(0, L, cfg, shared)]
    if Ld:
        # layers 0..Ld-1 with their dense MLP, then the routed ones: each
        # scan is handed its own feed-forward's leaves and its layers' norms
        norms = {k: v for k, v in shared.items() if k in _LAYER_NORMS}
        dense = {k: v for k, v in shared.items() if k in _DENSE_FFN}
        routed = {k: v for k, v in shared.items()
                  if k not in norms and k not in dense}
        dense_cfg = dataclasses.replace(cfg, n_experts=0,
                                        ffn_dim=cfg.dense_ffn_dim)
        spans = [(0, Ld, dense_cfg,
                  {**{k: v[:Ld] for k, v in norms.items()}, **dense}),
                 (Ld, L, cfg,
                  {**{k: v[Ld:] for k, v in norms.items()}, **routed})]
    carry = (x, kc, vc, *(state or (None, None, None)), load)
    for lo, hi, cfg_l, xs in spans:
        carry, _ = lax.scan(
            functools.partial(body, cfg_l), carry,
            (xs, jnp.asarray(is_attn[lo:hi]),
             jnp.asarray(rows[lo:hi], jnp.int32),
             None if wrows is None else jnp.asarray(wrows[lo:hi],
                                                    jnp.int32)))
    x, kc, vc, *state, load = carry
    return x, kc, vc, tuple(state), load


# --------------------------------------------------------------------------
# latent attention (glm_moe_dsa): one row a position, an indexer beside it
# --------------------------------------------------------------------------
#
# With u_t the normed input of position t:
#   cQ_t = RMSNorm(u_t W_qa);  [q_nope | q_rope] = cQ_t W_qb, a head;
#   [cKV_t | kR_t] = u_t W_kva, cKV_t = RMSNorm(cKV_t);  rotary on q_rope and
#   on the ONE kR_t every head shares. Head i: k_nope = W_uk,i cKV_s,
#   v = W_uv,i^T cKV_s; scores (q_nope . k_nope + q_rope . kR_s) / sqrt(dn +
#   dr); out = [sum_s a v] W_o.
# The cache holds [cKV_s | kR_s] and nothing a head: it rides where a full
# layer's keys do, [La, B, 1, S, C + dr] (int8: one float32 scale for the
# latent part and one for the rotated key, "s" [La, B, 2, S]: a normed latent
# and a raw projection differ in size). A fresh chunk expands keys and values
# a head (``_latent_expanded``); everything that reads the cache runs
# ABSORBED (``_latent_absorbed``): q~ = W_uk,i^T q_nope [C], scores q~ . cKV_s
# + q_rope . kR_s, out = W_uv,i^T (sum_s a cKV_s): the row is read as it
# lies and no key or value a head is ever made. The two are one function.
# The indexer: qI = cQ_t W_iq (Hi heads of di), kI_s = LayerNorm(u_s W_ik),
# rotary on the first dr channels of both, w_t = u_t W_iw / sqrt(Hi di);
# I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI_s); attention reads the
# cfg.index_topk positions s <= t of largest I[t, s], all of them while there
# are no more. kI rides where a full layer's values do, [La, B, 1, S, di].
# The published inference code rotates qI and kI by a Hadamard matrix and
# keeps them float8: a rotation leaves every dot product as it was and the
# storage type here is the cache's, so neither is reproduced. The rows not
# kept are masked, not skipped. The einsum form reads its whole attended
# bucket for every slot; the one-position step on a chip is a kernel that
# walks each slot's own rows in the cache leaf itself (``_latent_kernel``,
# ops/pallas/latent.py).
# A model without an indexer (kimi_k2: cfg.index_topk == 0) has none of the
# indexer's leaves, calls or scope: attention reads every earlier position,
# the cache has the one row and nothing rides where values do (vc is None).
# Its rotary may be YaRN's (``_latent_rope``), whose DeepSeek-V3 convention
# leaves cos / sin alone and scales the scores (``_latent_scale``); under a
# scaled-up softmax an int8 row keeps the rotated key's second code in its
# padding (cfg.latent_key_residual), which the query's [q_rope | q_rope /
# RESIDUAL_STEPS] scores in the same dot.

_LATENT_Q_BLOCK = 128       # queries, over the batch, a pass of a long prefill


def _rope_pairs(cfg: ModelConfig, x, cos, sin):
    """Rotary embedding over the first cfg.qk_rope_dim channels of x [B, T,
    d] or [B, T, H, d]; cos, sin [B, T, dr / 2]. ``cfg.rope_interleave``:
    channels (2i, 2i + 1) are a pair, and come out de-interleaved (first
    halves, then second halves: the same order for queries and keys, so
    every dot product is the interleaved rotation's)."""
    dr = cfg.qk_rope_dim
    if cfg.rope_interleave:
        x = jnp.concatenate([x[..., 0:dr:2], x[..., 1:dr:2], x[..., dr:]],
                            axis=-1)
    if x.ndim == 3:                 # no head axis: lend it one
        return apply_rope(x[:, :, None], cos, sin, dr)[:, :, 0]
    return apply_rope(x, cos, sin, dr)


def _latent_project(cfg: ModelConfig, ap, h, cos, sin):
    """Latent attention's projections of the normed input h [B, T, D]:
    (q_nope [B, T, H, dn], q_rope [B, T, H, dr] rotated, row [B, T, C + dr]
    = [normed latent | rotated shared key], cq [B, T, Rq] the normed query
    latent, which the indexer reads too)."""
    B, T, _ = h.shape
    H, C = cfg.n_heads, cfg.kv_latent_dim
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    with device_scope("attn.qkv"):
        cq = rms_norm(_mm(cfg, h, ap["wq_a"]), ap["q_a_norm_w"],
                      cfg.norm_eps)
        q = _mm(cfg, cq, ap["wq_b"]).reshape(B, T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        kv = _mm(cfg, h, ap["wkv_a"])
        ckv = rms_norm(kv[..., :C], ap["kv_a_norm_w"], cfg.norm_eps)
        q_rope = _rope_pairs(cfg, q_rope, cos, sin)
        kr = _rope_pairs(cfg, kv[..., C:], cos, sin)
        row = jnp.concatenate([ckv, kr.astype(ckv.dtype)], axis=-1)
    return q_nope, q_rope, row, cq


def _index_project(cfg: ModelConfig, ap, h, cq, cos, sin):
    """The indexer's projections: (qi [B, T, Hi, di] and ki [B, T, di],
    their first dr channels rotated, w [B, T, Hi] float32, the heads' weights
    with both scales folded in)."""
    B, T, _ = h.shape
    Hi, di = cfg.index_heads, cfg.index_head_dim
    qi = _mm(cfg, cq, ap["idx_wq"]).reshape(B, T, Hi, di)
    ki = layer_norm(_mm(cfg, h, ap["idx_wk"]), ap["idx_k_norm_w"],
                    ap["idx_k_norm_b"], 1e-6)
    qi = _rope_pairs(cfg, qi, cos, sin)
    ki = _rope_pairs(cfg, ki, cos, sin)
    w = _mm(cfg, h, ap["idx_w"]).astype(jnp.float32) * (Hi * di) ** -0.5
    return qi, ki, w


def _index_keep(cfg: ModelConfig, score, visible):
    """The positions a query's attention may read: ``visible`` [B, T, A]
    where there are at most cfg.index_topk of them, else the index_topk
    visible ones of largest ``score`` [B, T, A] float32, a tie on the last
    place going to the earlier position. bool [B, T, A]. Called only where
    the attended length A passes index_topk: below it nothing is chosen
    and the scores are not computed."""
    score = jnp.where(visible, score, -jnp.inf)
    # lax.top_k puts the lower index first among equal scores, so its last
    # place names both the k-th score and the last tied position kept
    vals, idx = lax.top_k(score, cfg.index_topk)
    kth, last = vals[..., -1:], idx[..., -1:]
    pos = jnp.arange(score.shape[-1], dtype=idx.dtype)
    return ((score > kth) | ((score == kth) & (pos <= last))) & visible


def _index_mask(cfg: ModelConfig, qi, w, ki_rows, q_pos, A: int):
    """bool [B, T, A]: the positions each query at q_pos [B, T] may read of
    the first A: every position up to its own and, where the model has an
    indexer and A passes cfg.index_topk, of those the ones ``_index_keep``
    keeps by the indexer's scores of queries qi [B, T, Hi, di], w [B, T, Hi]
    against the keys ki_rows [B, A, di] (or int8 {"q", "s" [B, A]}: a
    position's scale is positive and comes out of the ReLU)."""
    from ..ops.quant_cache import is_quantized_cache
    visible = (jnp.arange(A, dtype=jnp.int32)[None, None, :]
               <= q_pos[:, :, None])
    if A <= cfg.index_topk or not cfg.index_topk:
        return visible
    quant = is_quantized_cache(ki_rows)
    kq = ki_rows["q"] if quant else ki_rows
    with device_scope("attn.index"):
        dots = jnp.einsum("btjd,bsd->btjs", qi, kq.astype(qi.dtype),
                          preferred_element_type=jnp.float32)
        score = jnp.einsum("btjs,btj->bts", jax.nn.relu(dots), w)
        if quant:
            score = score * ki_rows["s"][:, None, :]
        return _index_keep(cfg, score, visible)


def _by_query_blocks(fn, T: int, *per_query):
    """``fn(*blocks)`` over blocks of queries (axis 1 of every array of
    ``per_query``), results joined along axis 1: a long chunk's scores, [B,
    H, T, A] float32 whole, stay a block's. A block is _LATENT_Q_BLOCK
    queries over the batch, 16 a row at least: a batched admission of four
    rows of 4,096 positions at 256 queries a row needs 4.4 GB of
    temporaries beside 12.4 GB of arguments and does not compile for a chip
    of 15.75 GB; at 32 a row it needs 3.5 (``hack/compile_cell.py``'s way,
    PR 46)."""
    Q = max(_LATENT_Q_BLOCK // per_query[0].shape[0], 16)
    if T <= Q or T % Q:
        return fn(*per_query)
    blocks = tuple(jnp.moveaxis(a.reshape(a.shape[0], T // Q, Q,
                                          *a.shape[2:]), 1, 0)
                   for a in per_query)
    out = lax.map(lambda xs: fn(*xs), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(out.shape[1], T, *out.shape[3:])


def _latent_scale(cfg: ModelConfig) -> float:
    """What latent attention's scores are multiplied by: 1 / sqrt(dn + dr),
    times YaRN's m(mscale_all_dim)^2 where the config states it."""
    return ((cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
            * yarn_softmax_factor(cfg))


def _latent_rope(cfg: ModelConfig, positions):
    """cos, sin [B, T, dr / 2] of latent attention's rotated channels: at
    rope_theta alone (the call there was: float32 frequencies made on the
    device, where ``scaled_inv_freq`` rounds float64 ones, so an unscaled
    stack's programs stay what they were), or with the scaling's
    per-channel frequencies and cos / sin magnitude
    (``ops/rope.scaled_inv_freq``)."""
    if cfg.rope_scaling_type == "none":
        return rope_angles(positions, cfg.qk_rope_dim, cfg.rope_theta)
    return rope_angles_cfg(positions, cfg, cfg.qk_rope_dim)



def _latent_expanded(cfg: ModelConfig, ap, q_nope, q_rope, row, qi, w, ki,
                     q_pos):
    """Attention of a fresh chunk over itself, keys and values expanded a
    head from the chunk's own rows: row [B, T, C + dr], ki [B, T, di], both
    as computed (not yet as the cache stores them); q_pos [B, T]; qi, w and
    ki None for a model without an indexer. -> [B, T, H * dv]."""
    B, T, H, _ = q_nope.shape
    C = cfg.kv_latent_dim
    ckv, kr = row[..., :C], row[..., C:]
    with device_scope("attn.core"):
        k_nope = jnp.einsum("bsc,hnc->bshn", ckv, ap["w_uk"])
        v = jnp.einsum("bsc,hcv->bshv", ckv, ap["w_uv"])

    def block(q_nope, q_rope, *index_and_pos):
        *index, q_pos = index_and_pos
        qi, w = index or (None, None)       # no indexer: nothing to cut
        ok = _index_mask(cfg, qi, w, ki, q_pos, T)
        with device_scope("attn.core"):
            s = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bthr,bsr->bhts", q_rope, kr,
                              preferred_element_type=jnp.float32))
            s = jnp.where(ok[:, None], s * _latent_scale(cfg), -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return jnp.einsum("bhts,bshv->bthv", p, v)

    note_kernel("prefill", "einsum")
    index = (qi, w) if cfg.index_topk else ()
    out = _by_query_blocks(block, T, q_nope, q_rope, *index, q_pos)
    return out.reshape(B, T, -1)


def _latent_absorbed(cfg: ModelConfig, ap, q_nope, q_rope, rows, qi, w,
                     ki_rows, q_pos, kernel=None):
    """Attention over the first A cached positions, absorbed: rows [B, A, C
    + dr] and ki_rows [B, A, di] as the cache keeps them (int8: {"q", "s"},
    the rows' "s" [B, 2, A], latent part and rotated key), the new
    positions already written; q_rope [B, T, H, dr], or 2 dr wide where
    the rows hold the rotated key's second code (``_latent_cached``). q_pos
    [B, T]; qi, w and ki_rows None for a model without an indexer. -> [B, T,
    H * dv]. With ``kernel`` (``_latent_kernel``'s, T == 1) the scores,
    softmax and sum are its walk over the cache itself and ``rows`` is not
    read."""
    from ..ops.quant_cache import is_quantized_cache
    B, T, H, _ = q_nope.shape
    C = cfg.kv_latent_dim
    if kernel is None:
        quant = is_quantized_cache(rows)
        codes = rows["q"] if quant else rows
        A = codes.shape[1]
        dt = q_nope.dtype
        lat = codes[..., :C].astype(dt)
        kr = codes[..., C:C + q_rope.shape[-1]].astype(dt)  # zeros may follow
    with device_scope("attn.core"):
        q_abs = jnp.einsum("bthn,hnc->bthc", q_nope, ap["w_uk"])

    def block(q_abs, q_rope, *index_and_pos):
        *index, q_pos = index_and_pos
        qi, w = index or (None, None)       # no indexer: nothing to cut
        ok = _index_mask(cfg, qi, w, ki_rows, q_pos, A)
        with device_scope("attn.core"):
            s_lat = jnp.einsum("bthc,bsc->bhts", q_abs, lat,
                               preferred_element_type=jnp.float32)
            s_rot = jnp.einsum("bthr,bsr->bhts", q_rope, kr,
                               preferred_element_type=jnp.float32)
            if quant:
                s_lat = s_lat * rows["s"][:, 0, None, None, :]
                s_rot = s_rot * rows["s"][:, 1, None, None, :]
            s = jnp.where(ok[:, None], (s_lat + s_rot) * _latent_scale(cfg),
                          -1e30)
            # the softmax by hand: the latent's scale goes into the
            # normaliser's pass, one pass over [B, H, T, A] fewer
            e = jnp.exp(s - s.max(axis=-1, keepdims=True))
            norm = 1.0 / e.sum(axis=-1, keepdims=True)
            if quant:
                norm = norm * rows["s"][:, 0, None, None, :]
            return jnp.einsum("bhts,bsc->bthc", (e * norm).astype(dt), lat)

    if kernel is not None:
        # without an indexer, and below index_topk positions, nothing is
        # chosen: the kernel's own test of visibility is the whole mask
        keep = None
        if cfg.index_topk:
            A = (ki_rows["q"] if is_quantized_cache(ki_rows)
                 else ki_rows).shape[1]
            if A > cfg.index_topk:
                keep = _index_mask(cfg, qi, w, ki_rows, q_pos, A)[:, 0]
        with device_scope("attn.core"):
            o_lat = kernel(q_abs[:, 0], q_rope[:, 0], keep)[:, None]
    else:
        index = (qi, w) if cfg.index_topk else ()
        o_lat = _by_query_blocks(block, T, q_abs, q_rope, *index, q_pos)
    with device_scope("attn.core"):
        out = jnp.einsum("bthc,hcv->bthv", o_lat, ap["w_uv"])
    return out.reshape(B, T, -1)


def _latent_kernel(cfg: ModelConfig, mesh, T: int, kc, row_i, q_pos,
                   n_valid):
    """The one place that decides how a latent layer's cached rows are
    attended: ``ops/pallas/latent.latent_decode`` over layer ``row_i`` of the
    leaf ``kc`` itself, each slot to its own length, for the one-position
    step on one device where ``cfg.kernels`` resolves to a kernel and the
    widths tile (``latent_decode_tileable``); else None, and the caller reads
    a window of the leaf through the einsum form: by design for T > 1 (an
    ``extend`` piece, the probe's prefill), flagged ``kernel_fallback`` where
    the kernel was wanted. Returns (q_abs [B, H, C], q_rope [B, H, dr], keep
    [B, A] or None) -> o_lat [B, H, C]."""
    from ..ops.attention import resolve_kernels
    from ..ops.pallas.latent import latent_decode, latent_decode_tileable
    from ..ops.quant_cache import is_quantized_cache
    mode = resolve_kernels(cfg.kernels)
    wanted = T == 1 and mode in ("pallas", "interpret")
    interp = mode == "interpret"
    S, W = (kc["q"] if is_quantized_cache(kc) else kc).shape[-2:]
    if not (wanted and (mesh is None or mesh.size == 1)
            and latent_decode_tileable(cfg.n_heads, cfg.kv_latent_dim, W, S,
                                       interp)):
        note_kernel("decode", "einsum", fell_back=wanted)
        return None
    note_kernel("decode", "latent_decode")
    return lambda q_abs, q_rope, keep: latent_decode(
        kc, row_i, q_abs, q_rope, q_pos[:, 0], n_valid, keep,
        _latent_scale(cfg), interpret=interp)


def _latent_cached(cfg: ModelConfig, ap, h, kc, vc, row_i, positions,
                   n_valid, A: int, cos, sin, mesh=None):
    """One latent-attention layer against row ``row_i`` of the cache: kc
    the rows [La, B, 1, S, C + dr], vc the indexer's keys [La, B, 1, S, di]
    (either int8 {"q", "s"}; vc None for a model without an indexer).
    Writes the new positions' rows and keys (those at or past n_valid [B]
    write nothing: a padded position, an inactive slot leave the cache's
    bits alone), attends over the first A positions. -> (out [B, T, D], kc,
    vc)."""
    from ..ops import quant_cache as QC
    B, T, _ = h.shape
    C = cfg.kv_latent_dim
    q_nope, q_rope, row, cq = _latent_project(cfg, ap, h, cos, sin)
    qi = ki = w = ki_rows = None
    if cfg.index_topk:
        with device_scope("attn.index"):
            qi, ki, w = _index_project(cfg, ap, h, cq, cos, sin)
    quant = QC.is_quantized_cache(kc)
    residual = cfg.latent_key_residual if quant else 0
    pad = [(0, 0), (0, 0), (0, cfg.latent_row_pad - residual)]
    if residual:        # the query's share of the key's second code
        q_rope = jnp.concatenate(
            [q_rope, q_rope * (1.0 / QC.RESIDUAL_STEPS)], axis=-1)
    S = (kc["q"] if quant else kc).shape[3]
    real = jnp.arange(T, dtype=jnp.int32)[None, :] < n_valid[:, None]
    bidx = jnp.arange(B)[:, None]
    # a position that is not real rewrites what lies there (the engine keeps
    # lengths + T <= S, so a row's positions are its own). A scatter that
    # DROPS them instead (an index past the end) is the same program but for
    # one shape: ``decode.(32, 1024)`` at the published widths then fails to
    # compile for the v5e ("Used 173.00M of 128.00M vmem", an empty heap:
    # hack/compile_cell.py's way, PR 46)
    pidx = jnp.minimum(positions, S - 1)

    def put(c, x):
        old = c[row_i, bidx, 0, pidx]
        keep = real if old.ndim == 2 else real[..., None]
        return c.at[row_i, bidx, 0, pidx].set(
            jnp.where(keep, x.astype(c.dtype), old))

    def window(c, n):
        lead = (1, B, 1, A)
        return lax.dynamic_slice(c, (row_i,) + (0,) * (c.ndim - 1),
                                 lead[:n] + c.shape[n:])

    if quant:
        with device_scope("attn.kv_write"):
            # [B,T,C+dr] (or C+2dr with the key's second code), [B,T,2]
            rq, rs = QC.quantize_latent(row, C, residual)
            kc = {"q": put(kc["q"], jnp.pad(rq, pad)),
                  "s": kc["s"].at[row_i, bidx, :, pidx].set(jnp.where(
                      real[..., None], rs, kc["s"][row_i, bidx, :, pidx]))}
        if cfg.index_topk:
            with device_scope("attn.index"):
                iq, is_ = QC.quantize_kv(ki)
                vc = {"q": put(vc["q"], iq), "s": put(vc["s"], is_)}
            ki_rows = {"q": window(vc["q"], 4)[0, :, 0],
                       "s": window(vc["s"], 4)[0, :, 0]}
    else:
        with device_scope("attn.kv_write"):
            kc = put(kc, jnp.pad(row, pad))
        if cfg.index_topk:
            with device_scope("attn.index"):
                vc = put(vc, ki)
            ki_rows = window(vc, 4)[0, :, 0]
    kernel = _latent_kernel(cfg, mesh, T, kc, row_i, positions, n_valid)
    rows = None                 # the kernel reads the leaf itself
    if kernel is None:
        rows = ({"q": window(kc["q"], 4)[0, :, 0],
                 "s": lax.dynamic_slice(
                     kc["s"], (row_i, 0, 0, 0), (1, B, 2, A))[0]}
                if quant else window(kc, 4)[0, :, 0])
    out = _latent_absorbed(cfg, ap, q_nope, q_rope, rows, qi, w, ki_rows,
                           positions, kernel)
    return _proj_out(cfg, ap, out, B, T), kc, vc


def _latent_prefill(params: Params, cfg: ModelConfig, x, n_valid):
    """``prefill_chunk`` of a latent-attention stack from the embedded chunk
    x [B, T, D]: -> (logits, rows [La, B, 1, T, C + dr (+ the cache's
    padding, zeros)], indexer keys [La, B, 1, T, di] or None where the model
    has no indexer), both as computed (the engine quantizes them as it does
    keys and values); nothing else is carried."""
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    cos, sin = _latent_rope(cfg, positions)

    def attend(ap, h, kc, vc, row_i):
        q_nope, q_rope, row, cq = _latent_project(cfg, ap, h, cos, sin)
        qi = ki = w = None
        if cfg.index_topk:
            with device_scope("attn.index"):
                qi, ki, w = _index_project(cfg, ap, h, cq, cos, sin)
        out = _latent_expanded(cfg, ap, q_nope, q_rope, row, qi, w, ki,
                               positions)
        kc = lax.dynamic_update_index_in_dim(kc, row[:, None], row_i, 0)
        if cfg.index_topk:
            vc = lax.dynamic_update_index_in_dim(
                vc, ki[:, None].astype(vc.dtype), row_i, 0)
        return _proj_out(cfg, ap, out, B, T), kc, vc

    _, kd, vd = cfg.cache_row_dims
    La = cfg.n_full_layers
    x, ks, vs, _, _ = _hybrid_layers(
        params, cfg, x, jnp.zeros((La, B, 1, T, kd), x.dtype),
        jnp.zeros((La, B, 1, T, vd), x.dtype) if vd else None, None,
        _valid_rows(n_valid, B, T), attend)
    return _unembed(cfg, params, _last_real(x, n_valid)), ks, vs


def _hybrid_prefill(params: Params, cfg: ModelConfig, tokens, n_valid,
                    inputs_embeds, mesh):
    """``prefill_chunk`` of a hybrid stack: a fresh chunk from the empty
    state. Keys and values of the full-attention layers come back [La, B,
    KvH, T, hd]; the state as each row's position n_valid - 1 left it (a
    window layer's ring holds the last W real positions of the chunk, in
    the chunk's own type: the engine quantizes them as it does the
    rows)."""
    B, T = tokens.shape
    scale = _attn_scale(cfg)
    cfg_a, cfg_w = _kind_cfgs(cfg)
    mask = jnp.broadcast_to(causal_mask(T, T, 0), (B, 1, T, T))
    cos = sin = None
    if cfg.rope:
        cos, sin = rope_angles_cfg(jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32), (B, T)), cfg)
    if inputs_embeds is not None:
        x = inputs_embeds.astype(params["tok_emb"].dtype)
    else:
        x = _embed(cfg, params, tokens)
    if cfg.kv_latent_dim:
        return _latent_prefill(params, cfg, x, n_valid)

    def attend(ap, h, kc, vc, row):
        q, k, v = _qkv(cfg_a, ap, h, cos, sin)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        with device_scope("attn.core"):
            attn = chunk_attention(cfg_a, q, k, v, mask, scale, mesh=mesh)
        kc = lax.dynamic_update_index_in_dim(kc, k, row, 0)
        vc = lax.dynamic_update_index_in_dim(vc, v, row, 0)
        return _proj_out(cfg, ap, attn, B, T), kc, vc

    def attend_win(ap, h, win, row):
        q, k, v = _qkv(cfg_w, ap, h, cos, sin)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        with device_scope("attn.window"):
            attn = chunk_attention(cfg_w, q, k, v, mask_w, scale, mesh=mesh)
            # the ring as the chunk's last real position leaves it
            zero = jnp.zeros((B,), jnp.int32)
            win = tuple(lax.dynamic_update_index_in_dim(
                ring, _ring_merge(jnp.zeros_like(ring[0]), new, zero, nv),
                row, 0) for ring, new in zip(win, (k, v)))
        return _proj_out(cfg, ap, attn, B, T), win

    kv0 = jnp.zeros((cfg.n_full_layers, B, cfg.n_kv_heads, T, cfg.head_dim),
                    x.dtype)
    state = empty_state(cfg, B, x.dtype)
    nv = _valid_rows(n_valid, B, T)
    if cfg.n_window_layers:
        mask_w = jnp.broadcast_to(
            causal_mask(T, T, 0, sliding_window=cfg.sliding_window),
            (B, 1, T, T))
    x, ks, vs, state, _ = _hybrid_layers(
        params, cfg, x, kv0, kv0, state, nv, attend, attend_win)
    logits = _unembed(cfg, params, _last_real(x, n_valid))
    return (logits, *join_state({"kv": ks}, {"kv": vs}, state))


# --------------------------------------------------------------------------
# paged KV cache (block-table page pool) — SURVEY.md §7 hard-part 2
# --------------------------------------------------------------------------
#
# Pool layout [L, P, KvH, ps, hd] (int8: {"q": int8 pool, "s": [L, P,
# KvH, ps] f32 scales}; int4: {"q4": [L, P, KvH, ps//2, hd] nibble-packed
# pool — two positions per byte, ops/quant_cache.pack_kv4 — same "s"
# scales}); a slot's logical block j lives in physical page
# table[slot, j] (runtime/paged.py owns allocation; page 0 is the trash
# page for bucket-padding writes — mirrored constant below to avoid a
# models → runtime import cycle).

TRASH_PAGE = 0


def _pad_hd(x, hd_pool: int):
    """Zero-pad the trailing head dim to the pool's 128-lane-padded width
    (engine.py pads the POOL so XLA never materialises padded temp copies
    of it; zeros are inert in both the score and output dots)."""
    d = hd_pool - x.shape[-1]
    if d == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, d)])


def _paged_scatter(pool, i, vals, pg, off):
    """The writer's XLA form: ``vals`` [B, T, KvH(, hd)] into layer ``i``
    of a page pool at (page ``pg``, offset ``off``) per (row, position);
    pg/off [B, T]. One scatter index per (row, HEAD, position): XLA wants
    a scatter's window minor-most, and a window over the heads (``pool.at[i,
    pg, :, off]``) makes it re-lay the pool out around every write (PERF.md,
    PR 30). The chip runs ``_paged_write``'s kernel instead."""
    hx = jnp.arange(vals.shape[2])[None, None, :]
    return pool.at[i, pg[:, :, None], hx, off[:, :, None]].set(vals)


def _paged_write(cfg: ModelConfig, pools, i, rows, pg, off, mesh=None):
    """Write one layer's new ``rows`` (per pool [B, T, KvH(, hd)], already
    of the pool's type and width) into ``pools`` at (page, offset) per
    (row, position). Where ``resolve_kernels`` says pallas or interpret the
    kernel addresses the pool by (row, position) and moves every head's
    row in one window (ops/pallas/kv_write.py); else the XLA scatter, one
    index per (row, head, position). Both put the same bytes at the same
    addresses. ``mesh`` is given outside a manual region only: on a tp
    mesh the kernel runs manual over every axis, each device writing its
    own heads, as ``_paged_attend`` runs the attention kernel."""
    from ..ops.attention import resolve_kernels
    mode = resolve_kernels(cfg.kernels)
    if mode in ("pallas", "interpret"):
        from ..ops.pallas.kv_write import paged_kv_write
        write = functools.partial(paged_kv_write,
                                  interpret=mode == "interpret")
        out = None
        if mesh is None or mesh.size == 1:
            out = write(pools, i, pg, off, rows)
        elif (mesh.shape.get("tp", 1) == mesh.size
              and pools[0].shape[2] % mesh.size == 0):
            from jax.sharding import PartitionSpec as P
            heads = lambda xs: tuple(                  # noqa: E731
                P(*(None, None, "tp") + (None,) * (x.ndim - 3)) for x in xs)
            out = jax.shard_map(
                write, mesh=mesh,
                in_specs=(heads(pools), P(), P(None, None), P(None, None),
                          heads(rows)),
                out_specs=heads(pools), check_vma=False)(
                tuple(pools), i, pg, off, tuple(rows))
        if out is not None:
            note_kernel("paged_write", "paged_kv_write")
            return out
    note_kernel("paged_write", "xla_scatter", fell_back=mode != "xla")
    return tuple(_paged_scatter(p, i, r, pg, off)
                 for p, r in zip(pools, rows))


def _gather_pages(pool, i, tbl, ps: Optional[int] = None):
    """Layer ``i`` pages ``tbl`` [B, NA] → contiguous logical view
    [B, KvH, NA*ps(, hd)] (one XLA gather; only attended pages copied).
    ``ps`` slices a lane-padded last dim back to the true page size
    (scale pools pad to the 128 tile — engine.py)."""
    pages = pool[i, tbl]                      # [B, NA, KvH, ps(, hd)]
    if pages.ndim == 5:
        B, NA, KvH, psp, hd = pages.shape
        return pages.transpose(0, 2, 1, 3, 4).reshape(B, KvH, NA * psp, hd)
    if ps is not None and ps < pages.shape[-1]:
        pages = pages[..., :ps]
    B, NA, KvH, psp = pages.shape
    return pages.transpose(0, 2, 1, 3).reshape(B, KvH, NA * psp)


def _insert_pages(pool, vals, table_row, n_valid, ps: int, stride: int = 1):
    """Write an admission's ``vals`` [L, KvH, R(, hd)] into the pool a PAGE
    at a time: row r holds position ``r * stride`` (2 for nibble-packed
    codes), and since an admission starts at offset 0 of its first page,
    page j of ``table_row`` takes rows [j * ps/stride, (j+1) * ps/stride)
    of every head: one scatter index per (layer, page). The window is the
    WHOLE page of every head, the pool's minor dims as they lie (a window
    over part of a page, or of a scale pool's padded lanes, has XLA re-lay
    the pool out: tests/test_chip_compile.py); rows the chunk does not
    reach, and positions at or past ``n_valid``, keep what the pool held
    (the windows are gathered first). A page wholly past ``n_valid`` is
    the trash page."""
    L, KvH, R = vals.shape[:3]
    rows = pool.shape[3]                      # stored rows a page, padded
    pr = ps // stride                         # of which the page uses
    npg = -(-R // pr)
    t = jnp.arange(npg * pr, dtype=jnp.int32) * stride
    live = (t < jnp.minimum(n_valid, R * stride)).reshape(npg, pr)
    vals = jnp.pad(vals, [(0, 0), (0, 0), (0, npg * pr - R)]
                   + [(0, 0)] * (vals.ndim - 3))
    vals = jnp.moveaxis(vals.reshape(L, KvH, npg, pr, *vals.shape[3:]), 2, 1)
    if rows > pr:                             # a scale pool's padded lanes
        vals = jnp.pad(vals, [(0, 0)] * 3 + [(0, rows - pr)])
        live = jnp.pad(live, [(0, 0), (0, rows - pr)])
    j = jnp.arange(npg, dtype=jnp.int32)
    nblk = table_row.shape[0]
    page = jnp.where((j * ps < n_valid) & (j < nblk),
                     table_row[jnp.minimum(j, nblk - 1)],
                     jnp.int32(TRASH_PAGE))
    window = pool.at[jnp.arange(L)[:, None], page[None, :]]
    live = live.reshape((1, npg, 1, rows) + (1,) * (vals.ndim - 4))
    return window.set(jnp.where(live, vals, window.get()))


@device_scope("attn.kv_write")
def paged_insert(cfg: ModelConfig, k_pool, v_pool, ks, vs, table_row,
                 n_valid):
    """Insert a fresh B=1 prefill chunk (ks/vs [L, 1, KvH, Tb, hd] from
    ``prefill_chunk``) into pool pages listed by ``table_row`` [NBLK], one
    window per page (``_insert_pages``): ceil(Tb / ps) updates a tensor
    where the scatter it replaces had L x KvH x Tb indices. Positions >=
    n_valid leave the pool as it was and pages wholly past it are the
    trash page, so admissions allocate pages only for real tokens."""
    quant = isinstance(k_pool, dict)
    quant4 = quant and "q4" in k_pool
    arr = (k_pool["q4"] if quant4 else k_pool["q"]) if quant else k_pool
    L, P, KvH, ps, hd = arr.shape
    if quant4:
        ps *= 2                               # packed pool: 2 positions/byte
    put = functools.partial(_insert_pages, table_row=table_row,
                            n_valid=n_valid, ps=ps)
    if quant4:
        from ..ops import quant_cache as QC
        kq, ksc = QC.quantize_kv4(ks)     # codes [-7,7] over the TRUE hd
        vq, vsc = QC.quantize_kv4(vs)
        # admissions always start at offset 0, so the nibble pairs
        # (2j, 2j+1) are byte-aligned: pack directly, no read-modify-write.
        # A pair straddling n_valid writes its garbage high nibble one
        # position past the slot's length — beyond-length entries are
        # never attended and the next decode write overwrites the nibble.
        k_pool = {"q4": put(k_pool["q4"],
                            QC.pack_kv4(_pad_hd(kq[:, 0], hd)), stride=2),
                  "s": put(k_pool["s"], ksc[:, 0])}
        v_pool = {"q4": put(v_pool["q4"],
                            QC.pack_kv4(_pad_hd(vq[:, 0], hd)), stride=2),
                  "s": put(v_pool["s"], vsc[:, 0])}
    elif quant:
        from ..ops import quant_cache as QC
        kq, ksc = QC.quantize_kv(ks)      # quantize over the TRUE hd,
        vq, vsc = QC.quantize_kv(vs)      # then pad codes with zeros
        k_pool = {"q": put(k_pool["q"], _pad_hd(kq[:, 0], hd)),
                  "s": put(k_pool["s"], ksc[:, 0])}
        v_pool = {"q": put(v_pool["q"], _pad_hd(vq[:, 0], hd)),
                  "s": put(v_pool["s"], vsc[:, 0])}
    else:
        k_pool = put(k_pool, _pad_hd(ks[:, 0].astype(arr.dtype), hd))
        v_pool = put(v_pool, _pad_hd(vs[:, 0].astype(arr.dtype), hd))
    return k_pool, v_pool


def _paged_kernel_usable(cfg: ModelConfig, mesh, T: int, k_pool) -> bool:
    """Route this paged forward's attention through the pallas kernel? The
    one place the route is chosen and recorded, before the layer scan is
    traced. The policy is here: the resolved kernel mode, T == 1 (a T > 1
    extend is gather + einsum by design), no per-layer window, a mesh the
    kernel's manual region covers. The shapes are
    ``ops/pallas/paged.paged_decode_tileable``'s to judge. There is no MHA
    bail-out as on the dense cache: gather + einsum copies every attended
    page each step, and phi-2 (KvH = 32) is served by the kernel in every
    line of the ledger (PRs 25-30). What the kernel was wanted for and
    cannot take is flagged ``kernel_fallback``."""
    from ..ops.attention import resolve_kernels
    from ..ops.pallas.paged import paged_decode_tileable
    site = "paged_decode" if T == 1 else "paged_extend"

    def gather(fell_back: bool = False) -> bool:
        note_kernel(site, "gather_einsum", fell_back)
        return False

    mode = resolve_kernels(cfg.kernels)
    if mode not in ("pallas", "interpret") or T != 1:
        return gather()
    if cfg.altern_sliding:
        return gather()   # per-layer window rides the (traced) mask
    # from here on the kernel was wanted: giving way is a fallback
    if not paged_decode_tileable(cfg.n_heads, k_pool, mode == "interpret"):
        return gather(fell_back=True)
    if mesh is not None and mesh.size > 1:
        KvH = cfg.n_kv_heads
        tp = mesh.shape.get("tp", 1)
        if _paged_dp_axes(cfg, mesh, KvH) is None and tp != mesh.size:
            return gather(fell_back=True)  # engine enforces dp/tp meshes
        if cfg.n_heads % tp or KvH % tp:
            return gather(fell_back=True)
    return True


def _paged_dp_axes(cfg: ModelConfig, mesh, KvH: int):
    """("dp", h_ax) when this mesh runs the paged forward as a dp-manual
    region (pool PAGE axis sharded over dp, per-shard LOCAL tables —
    runtime/paged.ShardedPageTable + engine.py build that layout), else
    None. Strict divisibility: inside a manual region there is no einsum
    fallback, so the engine refuses dp meshes that fail this check."""
    if mesh is None or mesh.size == 1:
        return None
    shape = dict(mesh.shape)
    dp, tp = shape.get("dp", 1), shape.get("tp", 1)
    if dp <= 1 or dp * tp != mesh.size:
        return None
    if tp > 1 and (cfg.n_heads % tp or KvH % tp):
        return None
    return "dp", ("tp" if tp > 1 else None)


@device_scope("attn.core")
def _paged_attend(cfg: ModelConfig, q, kp, vp, i, tables, lengths, mask,
                  scale, attn_blocks: int, mesh, use_kernel: bool):
    """Attention for one layer of the paged forward: pallas kernel with
    block-table scalar prefetch (T=1), else gather + einsum."""
    quant = isinstance(kp, dict)
    if use_kernel:
        from ..ops.attention import resolve_kernels
        from ..ops.pallas.paged import paged_decode_attention
        interp = resolve_kernels(cfg.kernels) == "interpret"
        if mesh is not None and mesh.size > 1:
            from jax.sharding import PartitionSpec as P
            qkey = "q4" if (quant and "q4" in kp) else "q"
            pool_spec = P(None, None, "tp", None, None)
            pool_specs = ({qkey: pool_spec, "s": P(None, None, "tp", None)}
                          if quant else pool_spec)
            qspec = P(None, None, "tp", None)

            # manual over EVERY mesh axis (the others are size 1 here —
            # _paged_kernel_usable): Mosaic refuses a kernel inside a
            # region that leaves any axis to the partitioner
            def inner(q, kp, vp, i, tables, lengths):
                return paged_decode_attention(
                    q, kp, vp, i, tables, lengths, scale, cfg.attn_softcap,
                    cfg.sliding_window, nblk=attn_blocks, interpret=interp)

            out = jax.shard_map(
                inner, mesh=mesh,
                in_specs=(qspec, pool_specs, pool_specs, P(), P(None, None),
                          P(None)),
                out_specs=qspec, check_vma=False)(
                q, kp, vp, i, tables, lengths)
        else:
            out = paged_decode_attention(
                q, kp, vp, i, tables, lengths, scale, cfg.attn_softcap,
                cfg.sliding_window, nblk=attn_blocks, interpret=interp)
        if out is not None:
            return out
        note_kernel("paged_decode", "gather_einsum", fell_back=True)
    tbl = tables[:, :attn_blocks]
    # gather fallback: the pool hd is 128-lane padded; pad q to match
    # (zeros are inert in the score dot) and slice the pad lanes back off
    # the output
    quant4 = quant and "q4" in kp
    hd_q = q.shape[-1]
    qp = _pad_hd(q, ((kp["q4"] if quant4 else kp["q"]) if quant
                     else kp).shape[-1])
    if quant4:
        from ..ops.quant_cache import attend_hf_q4
        ps = kp["q4"].shape[3] * 2
        kw = {"q4": _gather_pages(kp["q4"], i, tbl),
              "s": _gather_pages(kp["s"], i, tbl, ps=ps)}
        vw = {"q4": _gather_pages(vp["q4"], i, tbl),
              "s": _gather_pages(vp["s"], i, tbl, ps=ps)}
        return attend_hf_q4(qp, kw, vw, mask, scale,
                            cfg.attn_softcap)[..., :hd_q]
    if quant:
        from ..ops.quant_cache import attend_hf_q
        ps = kp["q"].shape[3]
        kw = {"q": _gather_pages(kp["q"], i, tbl),
              "s": _gather_pages(kp["s"], i, tbl, ps=ps)}
        vw = {"q": _gather_pages(vp["q"], i, tbl),
              "s": _gather_pages(vp["s"], i, tbl, ps=ps)}
        return attend_hf_q(qp, kw, vw, mask, scale,
                           cfg.attn_softcap)[..., :hd_q]
    kw = _gather_pages(kp, i, tbl)
    vw = _gather_pages(vp, i, tbl)
    return attend_hf(qp, kw, vw, mask, scale, cfg.attn_softcap)[..., :hd_q]


def _paged_scatter4(pool, i, codes, pg, off):
    """int4 twin of ``_paged_scatter``: merge per-position codes [-7, 7]
    ([B, T, KvH, hd]) into the nibble-packed pool at byte row off//2 —
    read-modify-write, one parity class at a time (even offsets share no
    byte with other even offsets, so each pass is conflict-free, and the
    odd pass reads the even pass's merged bytes through the dataflow)."""
    codes = codes.transpose(0, 2, 1, 3)                # [B, KvH, T, hd]
    KvH = codes.shape[1]
    hx = jnp.arange(KvH)[None, :, None]
    nib = (codes + 8).astype(jnp.uint8) & 0xF          # code + INT4_BIAS
    n_rows = pool.shape[3]
    for parity in (0, 1):
        sel = (off % 2) == parity                      # [B, T]
        row = off // 2
        # unselected positions write out-of-bounds and drop — writing a
        # stale readback at their (page, row) would race the selected
        # write that shares the byte
        rowx = jnp.where(sel, row, n_rows)[:, None, :]
        pgx = pg[:, None, :]
        cur = pool[i, pgx, hx, jnp.minimum(rowx, n_rows - 1)
                   ].astype(jnp.uint8)                 # [B, KvH, T, hd]
        keep, put = (0xF0, nib) if parity == 0 else (0x0F, nib << 4)
        new = ((cur & keep) | put).astype(jnp.int8)
        pool = pool.at[i, pgx, hx, rowx].set(new, mode="drop")
    return pool


@device_scope("attn.kv_write")
def _scatter_kv_pools(cfg: ModelConfig, kp, vp, i, k, v, pg_w, off_w,
                      mesh=None):
    """Quantize (int8/int4 pools) and write one layer's fresh K/V
    ([B, T, KvH, hd], as ``_qkv`` leaves them) into the pools at (page,
    offset) per (row, position) — shared by the dp-manual region and the
    single-shard paged forward so the write layout can never drift
    between them. Codes and scales of both pools go through ONE
    ``_paged_write``; int4 codes keep their read-modify-write by parity."""
    quant = isinstance(kp, dict)
    quant4 = quant and "q4" in kp
    arr = (kp["q4"] if quant4 else kp["q"]) if quant else kp
    hd_pool = arr.shape[-1]
    if quant4:
        from ..ops import quant_cache as QC
        kq, ksc = QC.quantize_kv4(k)
        vq, vsc = QC.quantize_kv4(v)
        ks_pool, vs_pool = _paged_write(cfg, (kp["s"], vp["s"]), i,
                                        (ksc, vsc), pg_w, off_w, mesh)
        kp = {"q4": _paged_scatter4(kp["q4"], i, _pad_hd(kq, hd_pool),
                                    pg_w, off_w), "s": ks_pool}
        vp = {"q4": _paged_scatter4(vp["q4"], i, _pad_hd(vq, hd_pool),
                                    pg_w, off_w), "s": vs_pool}
        return kp, vp
    if quant:
        from ..ops import quant_cache as QC
        kq, ksc = QC.quantize_kv(k)       # quantize over the TRUE hd,
        vq, vsc = QC.quantize_kv(v)       # then pad codes with zeros
        kq_pool, ks_pool, vq_pool, vs_pool = _paged_write(
            cfg, (kp["q"], kp["s"], vp["q"], vp["s"]), i,
            (_pad_hd(kq, hd_pool), ksc, _pad_hd(vq, hd_pool), vsc),
            pg_w, off_w, mesh)
        return {"q": kq_pool, "s": ks_pool}, {"q": vq_pool, "s": vs_pool}
    return _paged_write(
        cfg, (kp, vp), i,
        (_pad_hd(k.astype(arr.dtype), hd_pool),
         _pad_hd(v.astype(arr.dtype), hd_pool)), pg_w, off_w, mesh)


def _paged_write_attend_local(cfg: ModelConfig, q, k, v, kp, vp, i, tables,
                              lengths, positions, mask, scale,
                              attn_blocks: int, use_kernel: bool,
                              interp: bool):
    """Scatter one layer's fresh K/V into the (device-local) page pool and
    attend — the body of the dp-manual region. ``tables`` carry LOCAL page
    indices; on a single device local == global and this is just the
    fused write+attend."""
    quant = isinstance(kp, dict)
    quant4 = quant and "q4" in kp
    arr = (kp["q4"] if quant4 else kp["q"]) if quant else kp
    ps = arr.shape[3] * (2 if quant4 else 1)
    NBLK = tables.shape[1]
    bi = jnp.arange(tables.shape[0])[:, None]
    blk_w = positions // ps
    pg_w = jnp.where(blk_w < NBLK, tables[bi, jnp.minimum(blk_w, NBLK - 1)],
                     jnp.int32(TRASH_PAGE))
    off_w = positions % ps
    kp, vp = _scatter_kv_pools(cfg, kp, vp, i, k, v, pg_w, off_w)
    if use_kernel:
        from ..ops.pallas.paged import paged_decode_attention
        with device_scope("attn.core"):
            out = paged_decode_attention(
                q, kp, vp, i, tables, lengths, scale, cfg.attn_softcap,
                cfg.sliding_window, nblk=attn_blocks, interpret=interp)
        if out is not None:
            return kp, vp, out
        note_kernel("paged_decode", "gather_einsum", fell_back=True)
    out = _paged_attend(cfg, q, kp, vp, i, tables, lengths, mask, scale,
                        attn_blocks, None, False)
    return kp, vp, out


def _paged_write_attend_dp(cfg: ModelConfig, q, k, v, kp, vp, i, tables,
                           lengths, positions, mask, scale,
                           attn_blocks: int, use_kernel: bool, interp: bool,
                           mesh, h_ax):
    """Manual wrapper (over every mesh axis; only dp and tp are wider than
    1 — _paged_dp_axes) around ``_paged_write_attend_local``: the pool
    PAGE axis is sharded over dp (each shard's local page 0 is its trash
    page) and tables/lengths/batch rows ride dp — so scatter AND attend
    stay device-local with no collectives, the same property the dense
    kernels get from ``ops/attention._sharded_kernel_call``."""
    from jax.sharding import PartitionSpec as P
    quant = isinstance(kp, dict)
    qkey = "q4" if (quant and "q4" in kp) else "q"
    pool_spec = P(None, "dp", h_ax, None, None)
    pool_specs = ({qkey: pool_spec, "s": P(None, "dp", h_ax, None)}
                  if quant else pool_spec)
    qspec = P("dp", None, h_ax, None)       # q, and k/v [B, T, KvH, hd]

    def inner(q, k, v, kp, vp, i, tables, lengths, positions, mask):
        return _paged_write_attend_local(
            cfg, q, k, v, kp, vp, i, tables, lengths, positions, mask,
            scale, attn_blocks, use_kernel, interp)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(qspec, qspec, qspec, pool_specs, pool_specs, P(),
                  P("dp", None), P("dp"), P("dp", None),
                  P("dp", None, None, None)),
        out_specs=(pool_specs, pool_specs, qspec), check_vma=False)(
        q, k, v, kp, vp, i, tables, lengths, positions, mask)


def paged_insert_dp(cfg: ModelConfig, k_pool, v_pool, ks, vs, table_rows,
                    n_valid, mesh):
    """dp twin of ``paged_insert``: ``table_rows`` [dp, NBLK] carries each
    shard's LOCAL table row — the slot's owning shard gets the real pages,
    every other shard an all-trash row, so the replicated B=1 prefill
    writes land in non-owners' own trash pages and the real insert happens
    only where the slot lives. No collectives, no cross-shard indexing."""
    from jax.sharding import PartitionSpec as P
    quant = isinstance(k_pool, dict)
    qkey = "q4" if (quant and "q4" in k_pool) else "q"
    KvH = (k_pool[qkey] if quant else k_pool).shape[2]
    tp = dict(mesh.shape).get("tp", 1)
    h_ax = "tp" if (tp > 1 and KvH % tp == 0) else None
    pool_spec = P(None, "dp", h_ax, None, None)
    pool_specs = ({qkey: pool_spec, "s": P(None, "dp", h_ax, None)}
                  if quant else pool_spec)
    kvs = P(None, None, h_ax, None, None)

    def inner(kp, vp, ks, vs, trow, n_valid):
        return paged_insert(cfg, kp, vp, ks, vs, trow[0], n_valid)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(pool_specs, pool_specs, kvs, kvs, P("dp", None), P()),
        out_specs=(pool_specs, pool_specs),
        axis_names={"dp", "tp"}, check_vma=False)(
        k_pool, v_pool, ks, vs, table_rows, n_valid)


def paged_extend_dp(params: Params, cfg: ModelConfig, tokens: jax.Array,
                    k_pool, v_pool, table_rows: jax.Array,
                    lengths: jax.Array, attn_blocks: int,
                    owner: jax.Array, mesh):
    """dp twin of the paged prefix-cache extend (B=1 tail prefill).

    The pool PAGE axis is dp-sharded and the reused prefix lives on ONE
    shard, so the tail replicates its compute across dp the same way
    ``paged_insert_dp`` replicates admissions: ``table_rows`` [dp, NBLK]
    carries the owner's real LOCAL row and all-trash rows elsewhere —
    non-owners scatter into their own trash page and attend garbage,
    and an owner-select psum drops their logits (jnp.where picks 0 for
    the unselected branch, so even a non-owner NaN cannot propagate).
    Manual over dp ONLY: params/pool tp shardings stay GSPMD-auto inside
    the region (the same trick parallel/long_context.py uses for sp),
    and the inner forward is the plain single-shard paged path
    (``mesh=None`` — T>1 rides the gather fallback).
    """
    from jax.sharding import PartitionSpec as P
    quant = isinstance(k_pool, dict)
    qkey = "q4" if (quant and "q4" in k_pool) else "q"
    pool_spec = P(None, "dp", None, None, None)
    pool_specs = ({qkey: pool_spec, "s": P(None, "dp", None, None)}
                  if quant else pool_spec)

    # tp stays with the partitioner in this region, and Mosaic refuses a
    # kernel there: the tail's rows go through the writer's XLA form
    cfg_in = dataclasses.replace(cfg, kernels="xla")

    def inner(tokens, kp, vp, trow, lengths, owner):
        logits, kp, vp = forward_with_cache_paged(
            params, cfg_in, tokens, kp, vp, trow, lengths, attn_blocks,
            mesh=None)
        my = lax.axis_index("dp")
        logits = lax.psum(jnp.where(my == owner, logits, 0.0), "dp")
        return logits, kp, vp

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, None), pool_specs, pool_specs, P("dp", None),
                  P(None), P()),
        out_specs=(P(None, None, None), pool_specs, pool_specs),
        axis_names={"dp"}, check_vma=False)(
        tokens, k_pool, v_pool, table_rows, lengths, owner)


def forward_with_cache_paged(params: Params, cfg: ModelConfig,
                             tokens: jax.Array, k_pool, v_pool,
                             tables: jax.Array, lengths: jax.Array,
                             attn_blocks: int, mesh=None,
                             route_live: Optional[jax.Array] = None):
    """Paged twin of ``forward_with_cache`` (``route_live`` as there).

    tokens   [B, T] — T=1 decode (pallas kernel path), T>1 extend tails
             (gathered einsum path; B=1 there).
    tables   [B, NBLK] int32 physical page per logical block.
    lengths  [B] int32 cached tokens per row; new token t of row b is
             written to page tables[b, (lengths[b]+t)//ps].
    attn_blocks — static width: blocks attended/gathered (bucket // ps).
    Returns (logits [B, T, V], k_pool, v_pool).
    """
    quant = isinstance(k_pool, dict)
    quant4 = quant and "q4" in k_pool
    k_arr = (k_pool["q4"] if quant4 else k_pool["q"]) if quant else k_pool
    L, P, KvH, ps, hd = k_arr.shape
    if quant4:
        ps *= 2                               # packed pool: 2 positions/byte
    B, T = tokens.shape
    scale = _attn_scale(cfg)
    positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    cos, sin, cos_l, sin_l = _rope_pair(positions, cfg)
    S_attn = attn_blocks * ps
    k_pos = jnp.arange(S_attn, dtype=jnp.int32)[None, None, :]
    q_pos = positions[:, :, None]

    # a hybrid stack's window belongs to its "w" layers, which attend over
    # rings of their own (``_ring_attend``); its "A" layers see every key
    mask = _causal_window_mask(
        k_pos, q_pos, 0 if cfg.layer_kinds else cfg.sliding_window)
    m_full = (_causal_window_mask(k_pos, q_pos, 0)
              if cfg.altern_sliding else None)

    x = _embed(cfg, params, tokens)
    bi = jnp.arange(B)[:, None]
    # out-of-table blocks (a slot over-running max_seq) redirect to the
    # trash page — never clamp into the slot's LAST live page, which
    # would corrupt resident prefix K/V
    use_kernel = _paged_kernel_usable(cfg, mesh, T, k_pool)
    dp_axes = _paged_dp_axes(cfg, mesh, KvH)
    if dp_axes is None:
        # single-shard write indices, computed once outside the scan (the
        # dp-manual region derives its LOCAL indices per shard instead)
        blk_w = positions // ps
        NBLK = tables.shape[1]
        pg_w = jnp.where(blk_w < NBLK,
                         tables[bi, jnp.minimum(blk_w, NBLK - 1)],
                         jnp.int32(TRASH_PAGE))
        off_w = positions % ps
    if dp_axes is not None:
        assert T == 1, ("the dp-manual region decodes only (T=1); T>1 "
                        "extends ride paged_extend_dp, whose inner "
                        "forward is the single-shard path")
        from ..ops.attention import resolve_kernels
        interp = resolve_kernels(cfg.kernels) == "interpret"

    load = (None if route_live is None
            else jnp.zeros((cfg.n_experts,), jnp.int32))

    def body(carry, layer_in):
        x, kp, vp, load = carry
        lp, i = layer_in
        h = _norm(cfg, x, lp["attn_norm_w"], lp.get("attn_norm_b"))
        cos_i, sin_i = _layer_rope(cfg, i, cos, sin, cos_l, sin_l)
        q, k, v = _qkv(cfg, lp, h, cos_i, sin_i)   # k, v [B, T, KvH, hd]
        mask_l = _layer_mask(cfg, i, mask, m_full)
        if dp_axes is not None:
            # dp mesh: pool page axis is dp-sharded with per-shard local
            # tables — scatter AND attend run in one dp/tp-manual region
            kp, vp, attn = _paged_write_attend_dp(
                cfg, q, k, v, kp, vp, i, tables, lengths, positions,
                mask_l, scale, attn_blocks, use_kernel, interp, mesh,
                dp_axes[1])
        else:
            kp, vp = _scatter_kv_pools(cfg, kp, vp, i, k, v, pg_w, off_w,
                                       mesh)
            attn = _paged_attend(cfg, q, kp, vp, i, tables, lengths,
                                 mask_l, scale, attn_blocks, mesh,
                                 use_kernel)
        attn = _proj_out(cfg, lp, attn, B, T)
        x, load = _residual_counting(cfg, lp, x, h, attn, route_live, load)
        return (x, kp, vp, load), None

    (x, k_pool, v_pool, load), _ = _scan_layers(
        cfg, body, (x, k_pool, v_pool, load), params["layers"])
    logits = _unembed(cfg, params, x)
    return (logits, k_pool, v_pool, *_given(load))
