"""Model architecture configs for the decoder family.

The reference operator never describes architectures — it delegates them to
the GGUF metadata consumed by llama.cpp inside the ollama image
(/root/reference/pkg/model/pod.go:11). Here the architecture is a first-class
config object so the engine can be jit-specialised per model, and so GGUF
metadata (gguf/reader.py) can be mapped onto it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Static architecture description. Frozen + hashable → usable as a jit
    static argument."""

    arch: str = "llama"
    gguf_arch: str = ""                # raw GGUF source arch ("" = native);
                                       # rope-layout decisions key on this,
                                       # NOT on the normalized arch (qwen2/
                                       # gemma map to arch="llama" but are
                                       # not interleaved-rope)
    vocab_size: int = 32000
    dim: int = 4096                    # model/residual width
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32               # < n_heads → GQA
    head_dim: int = 128
    ffn_dim: int = 11008               # hidden width of the MLP
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # context-extension rope scaling (ops/rope.scaled_inv_freq): the scheme
    # llama.cpp reads from GGUF rope.scaling.* metadata / the rope_freqs
    # tensor inside the image the reference delegates to
    # (/root/reference/pkg/model/pod.go:11)
    rope_scaling_type: str = "none"    # none | linear | yarn | llama3
    rope_scaling: float = 1.0          # the scaling factor (1.0 = off);
                                       # with type "none" a non-1 factor is
                                       # honored as linear (legacy field)
    rope_orig_ctx: int = 0             # original (pre-extension) context
    rope_attn_factor: float = 0.0      # yarn cos/sin magnitude; 0 = auto
    rope_low_freq_factor: float = 1.0  # llama3 interpolation band
    rope_high_freq_factor: float = 4.0
    rope_yarn_beta_fast: float = 32.0  # yarn correction-dim betas
    rope_yarn_beta_slow: float = 1.0
    # yarn's two magnitudes where the config states them (rope_scaling
    # mscale / mscale_all_dim, the DeepSeek-V3 convention): with m(s) =
    # 0.1 s ln(factor) + 1, cos/sin scale by m(mscale) / m(mscale_all_dim)
    # and the softmax scale by m(mscale_all_dim)^2. Both 0 = the Llama
    # convention above (cos/sin by rope_attn_factor or 0.1 ln(factor) + 1,
    # the softmax scale untouched)
    rope_yarn_mscale: float = 0.0
    rope_yarn_mscale_all_dim: float = 0.0
    # per-frequency factors from a GGUF rope_freqs.weight tensor
    # (llama3.1-family conversions bake their scheme into this); tuple so
    # the config stays hashable for jit static args
    rope_freq_factors: Optional[Tuple[float, ...]] = None
    rotary_pct: float = 1.0            # phi-2 rotates only part of head_dim
    max_seq_len: int = 4096
    sliding_window: int = 0            # 0 = full attention (mistral: 4096)
    # block structure
    norm_type: str = "rmsnorm"         # "rmsnorm" | "layernorm"
    norm_bias: bool = True             # layernorm only; command-r stores
                                       # NO norm biases
    norm_weight_offset: float = 0.0    # gemma: weight stored as (w - 1)
    mlp_type: str = "gated"            # "gated" (silu/gelu gate*up) | "plain"
    act: str = "silu"                  # "silu" | "relu" | "gelu" |
                                       # "gelu_tanh"
    parallel_block: bool = False       # phi-2: attn and mlp share the input LN
    attn_bias: bool = False            # qwen2/phi-2: bias on q/k/v
    out_bias: bool = False             # phi-2: bias on o/mlp projections
    tie_embeddings: bool = False       # share tok_emb and lm_head
    emb_scale: bool = False            # gemma: scale embeddings by sqrt(dim)
    logit_softcap: float = 0.0         # gemma2: tanh soft-capping of logits
    attn_softcap: float = 0.0          # gemma2: tanh soft-capping of scores
    post_norms: bool = False           # gemma2: sandwich norms — extra RMS
                                       # on attn/mlp OUTPUTS before the
                                       # residual adds
    altern_sliding: bool = False       # gemma2/gemma3: layers alternate
                                       # sliding-window and full attention
                                       # (einsum path only)
    sliding_pattern: int = 2           # alternation period: layer i runs
                                       # FULL attention iff
                                       # i % pattern == pattern - 1
                                       # (gemma2: 2 — odd layers full;
                                       # gemma3: 6 — every 6th layer full)
    rope_local_theta: float = 0.0      # gemma3: SLIDING layers rope at
                                       # this theta with no scaling; full
                                       # layers use rope_theta + scaling.
                                       # 0 = one rope for all layers
    attn_scale: float = 0.0            # gemma2 query_pre_attn_scalar:
                                       # scores scale 1/sqrt(this);
                                       # 0 = 1/sqrt(head_dim)
    qk_norm: bool = False              # qwen3/llama4-style per-head RMS on q,k
    # granite-family scalar multipliers (0 = off)
    emb_multiplier: float = 0.0        # embeddings scaled by this
    residual_multiplier: float = 0.0   # block outputs scaled before the
                                       # residual adds
    logit_scale: float = 0.0           # final logits DIVIDED by this
    attn_scale_mult: float = 0.0       # exact score multiplier (granite
                                       # attention_multiplier); overrides
                                       # the 1/sqrt(attn_scale|head_dim)
                                       # convention when set
    # mixture-of-experts (mixtral family); 0 experts = dense MLP
    n_experts: int = 0                 # total routed experts per layer
    n_experts_used: int = 2            # top-k experts per token
    moe_renorm: bool = True            # softmax over the SELECTED top-k
                                       # (mixtral/qwen3moe); False = full
                                       # softmax, top-k gates kept as-is
                                       # (qwen2moe norm_topk_prob=false)
    n_shared_ffn: int = 0              # qwen2moe: a SHARED gated expert
                                       # of this ffn width runs for every
                                       # token, scaled by a sigmoid gate
    shared_gate: bool = True           # the shared expert's per-token
                                       # sigmoid gate (qwen2moe sh_gate);
                                       # False = added whole (granite)
    # the chip's share of an expert-parallel layer: the router scores all
    # n_experts and keeps n_experts_used; this chip holds the
    # n_experts_held experts from expert_first on and adds only what they
    # give. Gates of kept experts held elsewhere are neither renormalised
    # nor replaced. 0 = holds them all.
    n_experts_held: int = 0
    expert_first: int = 0
    moe_impl: str = "auto"             # auto|einsum|scan (models/decoder.py)
    # the router's form (models/decoder._moe_gates). "softmax": the two
    # forms moe_renorm tells apart. "sigmoid" (lfm2_moe): scores
    # s = sigmoid(logits) in float32; the kept are the top-k of s + b, b
    # the router_bias leaf ([E] float32 a layer) where moe_select_bias,
    # which takes part in the SELECTION only; gates are s of the kept,
    # divided by their sum + 1e-6 where moe_renorm, times moe_scale
    moe_score: str = "softmax"
    moe_select_bias: bool = False
    moe_scale: float = 1.0             # routed_scaling_factor
    # the stream the router reads, a property of the architecture: "mlp" =
    # the normed input of the feed-forward, after the mixer's residual add
    # (every routed stack but one); "block" = the layer's own input,
    # un-normed, ahead of the mixer (smallthinker: the gates are a function
    # of what enters the layer); hybrid stacks only
    moe_router_input: str = "mlp"
    # leading layers whose feed-forward is one dense gated MLP of width
    # dense_ffn_dim in a stack whose other layers are routed (lfm2_moe
    # num_dense_layers / intermediate_size); hybrid stacks only
    n_dense_layers: int = 0
    dense_ffn_dim: int = 0
    # hybrid stacks: one letter a layer, "m" a Mamba-2 mixer
    # (granitemoehybrid), "c" a gated short convolution (lfm2), "d" a
    # gated delta-rule linear-attention mixer (olmo_hybrid), "A" attention
    # over every earlier position (latent attention over the positions its
    # indexer keeps where kv_latent_dim, below), "w" attention over the last
    # sliding_window positions (exaone_moe), whose keys and values are a
    # ring of that many positions a slot; "" = every layer attends. A
    # stack has ONE kind beside "A" (no published stack mixes two of "m",
    # "c", "d" and "w"). "A" and "w" share one stack of projections. A
    # string, so the config stays hashable and arrives whole from JSON.
    layer_kinds: str = ""
    # the attention kinds of a hybrid stack whose q and k rotate, where
    # cfg.rope: "" = both; "w" = the window layers alone, the full layers
    # without positional embedding (the EXAONE-4 hybrid convention)
    rope_kinds: str = ""
    # Mamba-2 mixer sizes (one group of B/C): d_inner = ssm_heads *
    # ssm_head_dim; the state a slot carries is [ssm_heads, ssm_head_dim,
    # ssm_state] float32 a layer plus ssm_conv - 1 columns of the
    # convolution's d_inner + 2 * ssm_state inputs
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256               # prefill block (mamba_chunk_size)
    # gated short convolution ("c"): [B, C, v] = split3(h W_in); u = B * v;
    # depthwise causal convolution of conv_kernel taps over u, no bias, no
    # activation; out = (C * conv) W_out. A slot carries the last
    # conv_kernel - 1 values of u, [conv_kernel - 1, dim] float32 a layer
    conv_kernel: int = 3               # lfm2 conv_L_cache
    # gated delta rule ("d", models/decoder._delta_mixer): delta_heads
    # heads of delta_key_dim (q, k) and delta_value_dim (v); a depthwise
    # causal convolution of delta_conv taps and SiLU over [q, k, v]. A slot
    # carries, a layer, the state S [heads, key_dim, value_dim] float32,
    # updated by a rank-one correction of itself, and the convolution's
    # last delta_conv - 1 inputs. delta_neg_eigval doubles beta, so the
    # eigenvalue of I - beta k k^T along k lies in [-1, 1]
    # (linear_allow_neg_eigval). delta_chunk: the blocked prefill's block
    delta_heads: int = 0
    delta_key_dim: int = 96
    delta_value_dim: int = 192
    delta_conv: int = 4
    delta_neg_eigval: bool = False
    delta_chunk: int = 64
    # latent attention (glm_moe_dsa, models/decoder.py "latent attention"):
    # kv_latent_dim > 0 turns the "A" layers of a hybrid stack into it. The
    # query goes through a normed latent of q_latent_dim to n_heads heads of
    # [qk_nope_dim | qk_rope_dim]; keys and values of every head are
    # expanded from ONE normed latent of kv_latent_dim a position, beside
    # ONE rotated key of qk_rope_dim all heads share; values are v_head_dim
    # wide. The cache holds that row, kv_latent_dim + qk_rope_dim channels a
    # position a layer, and nothing a head. Beside it (glm_moe_dsa) an
    # indexer of index_heads heads of index_head_dim scores every cached
    # position against the query (its key, index_head_dim channels a
    # position a layer, is the cache's second row) and attention reads the
    # index_topk positions of largest score; the three index_* fields all 0
    # (kimi_k2) = no indexer: attention reads every earlier position and
    # the cache has the one row. rope_interleave: rotated channels pair
    # (2i, 2i + 1), as the checkpoint stores them, in attention and indexer
    kv_latent_dim: int = 0             # kv_lora_rank
    q_latent_dim: int = 0              # q_lora_rank
    qk_nope_dim: int = 0               # qk_nope_head_dim
    qk_rope_dim: int = 0               # qk_rope_head_dim
    v_head_dim: int = 0
    index_heads: int = 0               # index_n_heads
    index_head_dim: int = 0
    index_topk: int = 0
    rope_interleave: bool = False
    rope: bool = True                  # False = no positional embedding
                                       # (position_embedding_type "nope")
    kernels: str = "auto"              # attention impl: auto|pallas|xla|interpret
    mm_kernels: str = "auto"           # quantized-matmul impl:
                                       # auto|pallas|xla|interpret. "auto"
                                       # is resolved by the engine
                                       # (ops/quant.resolve_mm_kernels):
                                       # "pallas" on a single-device TPU,
                                       # where ops/quant.matmul routes by
                                       # row count (int8 <= 16 rows: the
                                       # grouped XLA form; above, and int4
                                       # throughout: the fused kernel),
                                       # "xla" on meshes and other backends

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def n_ssm_layers(self) -> int:
        return self.layer_kinds.count("m")

    @property
    def n_conv_layers(self) -> int:
        return self.layer_kinds.count("c")

    @property
    def n_delta_layers(self) -> int:
        return self.layer_kinds.count("d")

    @property
    def delta_conv_dim(self) -> int:
        """Channels the delta mixer's convolution runs over: q, k and v."""
        return self.delta_heads * (2 * self.delta_key_dim
                                   + self.delta_value_dim)

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.n_dense_layers if self.n_experts else 0

    @property
    def n_window_layers(self) -> int:
        return self.layer_kinds.count("w")

    @property
    def n_full_layers(self) -> int:
        """Layers whose keys and values are kept at every position: the
        rows of the cache."""
        return (self.layer_kinds.count("A") if self.layer_kinds
                else self.n_layers)

    @property
    def n_attn_layers(self) -> int:
        """Layers with attention's projections, full or window."""
        return self.n_full_layers + self.n_window_layers

    @property
    def cache_row_dims(self) -> Tuple[int, int, int]:
        """(heads, channels of the keys' row, channels of the values' row)
        a position of a full layer's cache: a head's keys and values, or
        latent attention's one row [latent | rotated key] and the indexer's
        key, which ride where keys and values do (0 channels where the
        model has no indexer: nothing rides there)."""
        if self.kv_latent_dim:
            return (1, self.kv_latent_dim + self.qk_rope_dim
                    + self.latent_row_pad, self.index_head_dim)
        return self.n_kv_heads, self.head_dim, self.head_dim

    @property
    def latent_row_pad(self) -> int:
        """Zero channels behind a cached latent row's rotated key: where the
        latent fills whole 128-lane tiles (the published 512) the key's
        part is rounded up to whole ones too (64 -> 128; the device's tiles
        hold the row at that width anyway), so that the compiler keeps the
        cache as it is handed over: at 576 channels it turned the whole
        cache position-minor inside every decode chunk and copied each
        layer's attended bucket back, 3.2 ms of a 26.8 ms step (my chip
        run, PR 46, call 3). Read from the shape; a toy's row is as it
        is."""
        if self.kv_latent_dim % 128:
            return 0
        return -self.qk_rope_dim % 128

    @property
    def latent_key_residual(self) -> int:
        """Channels of the rotated key's SECOND code in a cached latent row
        kept int8 (``ops/quant_cache.quantize_latent``): 0, a code a channel,
        unless the softmax's scale is scaled up (YaRN's m(mscale_all_dim)^2
        under the DeepSeek-V3 convention): every rounding of a key then
        moves each attention weight by that factor more, and at the
        published widths under seeded weights, where the rotated key is nine
        tenths of the scores' spread, the decode step read 2.1-3.3% of the
        reference's largest logit through plain int8 rows and 1.3-1.8
        through bfloat16 ones (my chip runs, PR 53). Such a row keeps, in the
        padding behind the rotated key, a second code a channel for what
        the first rounded away: the same bytes a position, the same two
        scales. Read from the widths: a row without that padding stays as
        it is."""
        dr = self.qk_rope_dim
        if (self.rope_scaling_type == "yarn" and self.rope_scaling > 1.0
                and self.rope_yarn_mscale_all_dim > 0
                and self.kv_latent_dim and self.latent_row_pad >= dr):
            return dr
        return 0

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the causal convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_state

    @property
    def ssm_state_bytes(self) -> int:
        """Recurrent state one sequence carries, all layers, float32: a
        Mamba layer's or a delta layer's state and convolution inputs, a
        short convolution's inputs alone."""
        return 4 * (self.n_ssm_layers * (
            self.ssm_inner * self.ssm_state
            + (self.ssm_conv - 1) * self.ssm_conv_dim)
            + self.n_conv_layers * (self.conv_kernel - 1) * self.dim
            + self.n_delta_layers * (
                self.delta_heads * self.delta_key_dim * self.delta_value_dim
                + (self.delta_conv - 1) * self.delta_conv_dim))

    @property
    def window_ring_bytes(self) -> int:
        """Keys and values of the window layers' rings one sequence
        carries, as the TPU's default cache keeps them: int8 codes and a
        float32 scale a head a position."""
        return (2 * self.n_window_layers * self.n_kv_heads
                * self.sliding_window * (self.head_dim + 4))

    @property
    def rotary_dim(self) -> int:
        rd = int(self.head_dim * self.rotary_pct)
        return rd - rd % 2

    @property
    def attn_params(self) -> int:
        """Matrix elements of one attention layer's projections (latent
        attention's: its indexer's with them)."""
        d = self.dim
        if not self.kv_latent_dim:
            return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        h, c, rq = self.n_heads, self.kv_latent_dim, self.q_latent_dim
        return (d * rq + rq * h * (self.qk_nope_dim + self.qk_rope_dim)
                + d * (c + self.qk_rope_dim)
                + c * h * (self.qk_nope_dim + self.v_head_dim)
                + h * self.v_head_dim * d
                # the indexer: its queries, its key, its heads' weights
                + rq * self.index_heads * self.index_head_dim
                + d * self.index_head_dim + d * self.index_heads)

    @property
    def n_params(self) -> int:
        """Approximate parameter count (for sizing / logs)."""
        d, f, l, v = self.dim, self.ffn_dim, self.n_layers, self.vocab_size
        attn = self.attn_params
        mlp = 3 * d * f if self.mlp_type == "gated" else 2 * d * f
        if self.n_experts:
            mlp = (self.experts_held * mlp + d * self.n_experts
                   + 3 * d * self.n_shared_ffn)
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.layer_kinds:
            ssm = (d * (2 * self.ssm_inner + 2 * self.ssm_state
                        + self.ssm_heads) + self.ssm_inner * d)
            conv = d * 3 * d + d * d
            # q, k, v, the output gate, the two per-head gates, out
            dv = self.delta_heads * self.delta_value_dim
            delta = (d * (self.delta_conv_dim + dv + 2 * self.delta_heads)
                     + dv * d)
            # the leading dense layers' MLP, at its own width and in the
            # stack's own form
            dense = (3 if self.mlp_type == "gated" else 2) \
                * d * self.dense_ffn_dim
            return (self.n_attn_layers * attn + self.n_ssm_layers * ssm
                    + self.n_conv_layers * conv
                    + self.n_delta_layers * delta
                    + self.n_dense_layers * dense
                    + (l - self.n_dense_layers) * mlp + emb)
        return l * (attn + mlp) + emb

    def validate(self) -> "ModelConfig":
        assert self.n_heads % self.n_kv_heads == 0, "GQA requires n_heads % n_kv_heads == 0"
        assert self.rope_scaling_type in ("none", "linear", "yarn", "llama3")
        if self.rope_freq_factors is not None:
            # JSON round-trips (gguf/store.py meta) hand back a list; the
            # config must stay hashable for jit static args
            object.__setattr__(self, "rope_freq_factors",
                               tuple(float(x)
                                     for x in self.rope_freq_factors))
            assert len(self.rope_freq_factors) == self.rotary_dim // 2, (
                f"rope_freq_factors: {len(self.rope_freq_factors)} entries "
                f"for rotary_dim {self.rotary_dim}")
        if self.rope_scaling_type in ("yarn", "llama3"):
            assert self.rope_orig_ctx > 0, (
                f"{self.rope_scaling_type} rope scaling requires "
                "rope_orig_ctx")
        if self.rope_yarn_mscale or self.rope_yarn_mscale_all_dim:
            assert self.rope_scaling_type == "yarn", (
                "mscale and mscale_all_dim are yarn's")
            # the softmax's share of them is applied where latent attention
            # scales its scores (decoder._latent_scale) and nowhere else:
            # ordinary attention would run at a wrong magnitude unsaid
            assert self.kv_latent_dim > 0, (
                "mscale and mscale_all_dim (the DeepSeek-V3 convention of "
                "yarn) are served under latent attention alone")
        assert self.norm_type in ("rmsnorm", "layernorm")
        assert self.mlp_type in ("gated", "plain")
        assert self.act in ("silu", "relu", "gelu", "gelu_tanh")
        assert self.kernels in ("auto", "pallas", "xla", "interpret")
        assert self.mm_kernels in ("auto", "pallas", "xla", "interpret")
        assert self.moe_impl in ("auto", "einsum", "scan")
        assert self.moe_score in ("softmax", "sigmoid")
        assert self.moe_router_input in ("mlp", "block")
        if self.moe_router_input == "block":
            assert self.layer_kinds and self.n_experts, (
                "a router that reads the block's input is routed ahead of "
                "the mixer by the hybrid stacks' scan")
        if self.moe_select_bias or self.moe_scale != 1.0:
            assert self.moe_score == "sigmoid", (
                "a selection bias and a scaling factor belong to the "
                "sigmoid router")
        if self.n_experts:
            assert self.mlp_type == "gated", "MoE is gated-MLP only"
            assert 0 < self.n_experts_used <= self.n_experts
            assert (0 <= self.expert_first
                    and self.expert_first + self.experts_held
                    <= self.n_experts), "held experts lie past the router"
        if self.layer_kinds:
            assert len(self.layer_kinds) == self.n_layers, (
                f"layer_kinds names {len(self.layer_kinds)} layers, "
                f"n_layers is {self.n_layers}")
            assert set(self.layer_kinds) <= {"m", "c", "d", "w", "A"}, (
                self.layer_kinds)
            assert "A" in self.layer_kinds, "no attention layer to cache"
            if self.kv_latent_dim:
                assert set(self.layer_kinds) == {"A"}, (
                    "latent attention stands in a stack of its own: no "
                    "recurrent or window kind beside it has been served")
                for field in ("q_latent_dim", "qk_nope_dim", "qk_rope_dim",
                              "v_head_dim"):
                    assert getattr(self, field) > 0, (
                        f"latent attention needs {field} > 0")
                index = (self.index_heads, self.index_head_dim,
                         self.index_topk)
                assert all(x > 0 for x in index) or not any(index), (
                    "an indexer has heads, a head size and a top-k, or "
                    f"there is none: {index}")
                assert (self.qk_rope_dim % 2 == 0
                        and (not self.index_heads
                             or self.qk_rope_dim <= self.index_head_dim)), (
                    "the rotated channels pair up, and the indexer rotates "
                    "as many of its own")
                assert (self.rope and not self.rope_kinds
                        and not self.rope_freq_factors
                        and (self.rope_scaling_type == "yarn"
                             or (self.rope_scaling_type == "none"
                                 and self.rope_scaling == 1.0))), (
                    "latent attention rotates at rope_theta alone or "
                    "under yarn")
                for field in ("attn_bias", "qk_norm", "attn_softcap",
                              "attn_scale", "attn_scale_mult"):
                    assert not getattr(self, field), (
                        f"latent attention has no {field}")
            assert len(set(self.layer_kinds) & {"m", "c", "d"}) <= 1, (
                "one recurrent kind a stack")
            if "m" in self.layer_kinds:
                assert self.ssm_heads > 0 and self.ssm_conv >= 2
            if "c" in self.layer_kinds:
                assert self.conv_kernel >= 2
            if "d" in self.layer_kinds:
                assert self.delta_heads > 0 and self.delta_conv >= 2
                assert self.delta_chunk > 0
            for field in ("parallel_block", "post_norms", "altern_sliding"):
                assert not getattr(self, field), (
                    f"hybrid stacks run the plain pre-norm block: {field} "
                    "is set")
            if "w" in self.layer_kinds:
                assert not set(self.layer_kinds) & {"m", "c", "d"}, (
                    "window layers stand beside full attention alone: "
                    "no recurrent kind in their stack")
                assert self.sliding_window > 0, (
                    "window layers (\"w\") need sliding_window > 0")
            else:
                assert not self.sliding_window, (
                    "sliding_window in a hybrid stack belongs to its "
                    "window layers: layer_kinds has no \"w\"")
        if not (self.kv_latent_dim and self.layer_kinds):
            for field in ("kv_latent_dim", "q_latent_dim", "qk_nope_dim",
                          "qk_rope_dim", "v_head_dim", "index_heads",
                          "index_head_dim", "index_topk", "rope_interleave"):
                assert not getattr(self, field), (
                    f"{field} belongs to latent attention: a hybrid stack "
                    "(layer_kinds) with kv_latent_dim > 0")
        assert set(self.rope_kinds) <= {"A", "w"} and (
            not self.rope_kinds or self.layer_kinds), (
            "rope_kinds names attention kinds of a hybrid stack")
        if self.n_dense_layers:
            assert self.layer_kinds and self.n_experts, (
                "leading dense layers stand before a hybrid stack's "
                "routed ones")
            assert 0 < self.n_dense_layers < self.n_layers
            assert self.dense_ffn_dim > 0
        if self.rope_local_theta:
            assert self.altern_sliding, (
                "rope_local_theta pairs with per-layer (altern_sliding) "
                "attention — the dual rope selects by the same pattern")
        assert self.sliding_pattern >= 2
        return self


def _mk(**kw) -> ModelConfig:
    return ModelConfig(**kw).validate()


# --- presets -----------------------------------------------------------------
# Dims cross-checked against the public GGUF metadata of the ollama library
# images listed in the reference README model table (/root/reference/README.md).

PRESETS = {
    # tiny config for unit tests / CI (CPU mesh)
    "tiny": _mk(arch="llama", vocab_size=256, dim=64, n_layers=2, n_heads=4,
                n_kv_heads=2, head_dim=16, ffn_dim=128, max_seq_len=128),
    "tinyllama": _mk(arch="llama", vocab_size=32000, dim=2048, n_layers=22,
                     n_heads=32, n_kv_heads=4, head_dim=64, ffn_dim=5632,
                     max_seq_len=2048),
    "phi": _mk(arch="phi2", vocab_size=51200, dim=2560, n_layers=32,
               n_heads=32, n_kv_heads=32, head_dim=80, ffn_dim=10240,
               norm_type="layernorm", mlp_type="plain", act="gelu_tanh",
               parallel_block=True, attn_bias=True, out_bias=True,
               rotary_pct=0.4, max_seq_len=2048),
    # phi3-mini 3.8B (the ollama `phi3` default tag): llama-family block,
    # MHA (32/32), full rotary; the 4k-instruct variant serves without
    # longrope (the 128k tags carry rope_factors tensors the transcoder
    # maps to rope_freq_factors)
    "phi3": _mk(arch="llama", vocab_size=32064, dim=3072, n_layers=32,
                n_heads=32, n_kv_heads=32, head_dim=96, ffn_dim=8192,
                max_seq_len=4096, sliding_window=2047),
    # gemma3-4b (the ollama `gemma3` default tag): pattern-6 alternating
    # attention with DUAL rope (local 10k on sliding layers, global 1e6
    # linear-scaled ×8 on full layers), gemma-offset qk norms, sandwich
    # norms, no softcapping
    "gemma3": _mk(arch="llama", vocab_size=262208, dim=2560, n_layers=34,
                  n_heads=8, n_kv_heads=4, head_dim=256, ffn_dim=10240,
                  act="gelu_tanh", emb_scale=True, tie_embeddings=True,
                  norm_weight_offset=1.0, post_norms=True,
                  altern_sliding=True, sliding_pattern=6, qk_norm=True,
                  sliding_window=1024, rope_local_theta=10000.0,
                  rope_theta=1000000.0, rope_scaling_type="linear",
                  rope_scaling=8.0, attn_scale=256.0,
                  max_seq_len=131072),
    # starcoder2-3b (the ollama `starcoder2` default tag): LayerNorm +
    # biases, plain gelu MLP, GQA 12:1, sliding window
    "starcoder2": _mk(arch="llama", vocab_size=49152, dim=3072,
                      n_layers=30, n_heads=24, n_kv_heads=2, head_dim=128,
                      ffn_dim=12288, norm_type="layernorm",
                      mlp_type="plain", act="gelu_tanh", attn_bias=True,
                      out_bias=True, tie_embeddings=True,
                      max_seq_len=16384, sliding_window=4096,
                      rope_theta=999999.0),
    "llama2": _mk(arch="llama", vocab_size=32000, dim=4096, n_layers=32,
                  n_heads=32, n_kv_heads=32, head_dim=128, ffn_dim=11008,
                  max_seq_len=4096),
    "llama2:13b": _mk(arch="llama", vocab_size=32000, dim=5120, n_layers=40,
                      n_heads=40, n_kv_heads=40, head_dim=128, ffn_dim=13824,
                      max_seq_len=4096),
    "llama2:70b": _mk(arch="llama", vocab_size=32000, dim=8192, n_layers=80,
                      n_heads=64, n_kv_heads=8, head_dim=128, ffn_dim=28672,
                      max_seq_len=4096),
    "llama3": _mk(arch="llama", vocab_size=128256, dim=4096, n_layers=32,
                  n_heads=32, n_kv_heads=8, head_dim=128, ffn_dim=14336,
                  rope_theta=500000.0, max_seq_len=8192),
    "llama3:70b": _mk(arch="llama", vocab_size=128256, dim=8192, n_layers=80,
                      n_heads=64, n_kv_heads=8, head_dim=128, ffn_dim=28672,
                      rope_theta=500000.0, max_seq_len=8192),
    # llama3.1 shares llama3-8B dims; the 131072 context comes from
    # llama3-type rope scaling (ops/rope.scaled_inv_freq) — factor 8 over
    # the 8192 native window, low/high-freq interpolation band 1..4 (real
    # GGUF pulls carry the equivalent pre-baked rope_freqs tensor, which
    # the transcoder reads into rope_freq_factors). 3.2 are the small GQA
    # variants — factor 32, tied embeddings.
    "llama3.1": _mk(arch="llama", vocab_size=128256, dim=4096, n_layers=32,
                    n_heads=32, n_kv_heads=8, head_dim=128, ffn_dim=14336,
                    rope_theta=500000.0, rope_scaling_type="llama3",
                    rope_scaling=8.0, rope_orig_ctx=8192,
                    rope_low_freq_factor=1.0, rope_high_freq_factor=4.0,
                    max_seq_len=131072),
    "llama3.2:1b": _mk(arch="llama", vocab_size=128256, dim=2048,
                       n_layers=16, n_heads=32, n_kv_heads=8, head_dim=64,
                       ffn_dim=8192, rope_theta=500000.0,
                       rope_scaling_type="llama3", rope_scaling=32.0,
                       rope_orig_ctx=8192, rope_low_freq_factor=1.0,
                       rope_high_freq_factor=4.0,
                       tie_embeddings=True, max_seq_len=131072),
    "llama3.2:3b": _mk(arch="llama", vocab_size=128256, dim=3072,
                       n_layers=28, n_heads=24, n_kv_heads=8, head_dim=128,
                       ffn_dim=8192, rope_theta=500000.0,
                       rope_scaling_type="llama3", rope_scaling=32.0,
                       rope_orig_ctx=8192, rope_low_freq_factor=1.0,
                       rope_high_freq_factor=4.0,
                       tie_embeddings=True, max_seq_len=131072),
    "mistral": _mk(arch="llama", vocab_size=32000, dim=4096, n_layers=32,
                   n_heads=32, n_kv_heads=8, head_dim=128, ffn_dim=14336,
                   sliding_window=4096, max_seq_len=32768),
    "gemma2": _mk(arch="llama", vocab_size=256000, dim=3584, n_layers=42,
                  n_heads=16, n_kv_heads=8, head_dim=256, ffn_dim=14336,
                  act="gelu_tanh", emb_scale=True, tie_embeddings=True,
                  norm_weight_offset=1.0, post_norms=True,
                  altern_sliding=True, sliding_window=4096,
                  attn_softcap=50.0, logit_softcap=30.0,
                  max_seq_len=8192),
    "gemma2:27b": _mk(arch="llama", vocab_size=256000, dim=4608,
                      n_layers=46, n_heads=32, n_kv_heads=16, head_dim=128,
                      ffn_dim=36864, act="gelu_tanh", emb_scale=True,
                      tie_embeddings=True, norm_weight_offset=1.0,
                      post_norms=True, altern_sliding=True,
                      sliding_window=4096, attn_softcap=50.0,
                      logit_softcap=30.0, attn_scale=144.0,
                      max_seq_len=8192),
    "qwen3": _mk(arch="llama", vocab_size=151936, dim=4096, n_layers=36,
                 n_heads=32, n_kv_heads=8, head_dim=128, ffn_dim=12288,
                 qk_norm=True, rope_theta=1000000.0, max_seq_len=32768),
    "qwen2": _mk(arch="llama", vocab_size=152064, dim=3584, n_layers=28,
                 n_heads=28, n_kv_heads=4, head_dim=128, ffn_dim=18944,
                 attn_bias=True, rope_theta=1000000.0, max_seq_len=32768),
    # qwen2.5-7B keeps qwen2-7B's architecture/dims
    "qwen2.5": _mk(arch="llama", vocab_size=152064, dim=3584, n_layers=28,
                   n_heads=28, n_kv_heads=4, head_dim=128, ffn_dim=18944,
                   attn_bias=True, rope_theta=1000000.0,
                   max_seq_len=32768),
    "qwen2:0.5b": _mk(arch="llama", vocab_size=151936, dim=896, n_layers=24,
                      n_heads=14, n_kv_heads=2, head_dim=64, ffn_dim=4864,
                      attn_bias=True, tie_embeddings=True,
                      rope_theta=1000000.0, max_seq_len=32768),
    "gemma": _mk(arch="llama", vocab_size=256000, dim=3072, n_layers=28,
                 n_heads=16, n_kv_heads=16, head_dim=256, ffn_dim=24576,
                 act="gelu_tanh", emb_scale=True, tie_embeddings=True,
                 norm_weight_offset=1.0, max_seq_len=8192),
    # multimodal (vicuna-7b LLM half of llava-1.5; vision tower in
    # models/vision.py via the mmproj layer)
    "llava": _mk(arch="llama", vocab_size=32000, dim=4096, n_layers=32,
                 n_heads=32, n_kv_heads=32, head_dim=128, ffn_dim=11008,
                 max_seq_len=4096),
    # mixture-of-experts family (sparse MoE; expert-parallel over "ep")
    "tiny-moe": _mk(arch="llama", vocab_size=256, dim=64, n_layers=2,
                    n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128,
                    n_experts=4, n_experts_used=2, max_seq_len=128),
    "mixtral": _mk(arch="llama", vocab_size=32000, dim=4096, n_layers=32,
                   n_heads=32, n_kv_heads=8, head_dim=128, ffn_dim=14336,
                   n_experts=8, n_experts_used=2, rope_theta=1000000.0,
                   max_seq_len=32768),
    "mixtral:8x22b": _mk(arch="llama", vocab_size=32768, dim=6144,
                         n_layers=56, n_heads=48, n_kv_heads=8, head_dim=128,
                         ffn_dim=16384, n_experts=8, n_experts_used=2,
                         rope_theta=1000000.0, max_seq_len=65536),
    # granite-4.0-h-small (granitemoehybrid), ONE CHIP'S SHARE of a stated
    # deployment: each layer's 72 routed experts divided over 2 chips
    # (this is chip 0: experts 0-35, vocabulary rows 0-50,175 of 100,352)
    # and the first period of the 40 layers, m m m m m A m m m m (the
    # other three would lie on further chips as pipeline stages). Every
    # width is the published one: hidden 4096, Mamba-2 128 heads of 64
    # with state 128 and convolution 4, GQA 32/8 at head_dim 128 without
    # rotary embedding, router 72 / 10 a token, expert width 768, shared
    # expert 1536. benchmark/configs/granite-4.0-h-small.json states the
    # cut beside the published numbers.
    "granite-4.0-h-small": _mk(
        arch="granitehybrid", vocab_size=50176, dim=4096, n_layers=10,
        n_heads=32, n_kv_heads=8, head_dim=128, ffn_dim=768,
        n_experts=72, n_experts_used=10, n_experts_held=36, expert_first=0,
        n_shared_ffn=1536, shared_gate=False, layer_kinds="mmmmmAmmmm",
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_conv=4,
        ssm_chunk=256, rope=False, emb_multiplier=12.0,
        residual_multiplier=0.22, logit_scale=16.0,
        attn_scale_mult=0.0078125, tie_embeddings=True, norm_eps=1e-5,
        max_seq_len=131072),
    # the same shape at toy widths (tests, --rehearse)
    "tiny-hybrid": _mk(
        arch="granitehybrid", vocab_size=256, dim=64, n_layers=10,
        n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=32, n_experts=8,
        n_experts_used=3, n_experts_held=4, expert_first=0,
        n_shared_ffn=48, shared_gate=False, layer_kinds="mmmmmAmmmm",
        ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_conv=4,
        ssm_chunk=16, rope=False, emb_multiplier=12.0,
        residual_multiplier=0.22, logit_scale=16.0,
        attn_scale_mult=0.0625, tie_embeddings=True, max_seq_len=256),
    # LFM2-8B-A1B (lfm2_moe), cut in DEPTH alone: the first four whole
    # periods of the 24 layers, c c A c c c A c c c A c c c A c (layers
    # 0-15: both leading dense layers, 14 of the 22 routed ones; layers
    # 16-23 would lie on a second chip as a pipeline stage). Every width,
    # all 32 experts and the whole vocabulary are the published ones:
    # hidden 2048, short convolution of 3 taps, GQA 32/8 at head_dim 64
    # with q/k norms before rotary at theta 1e6, dense width 7168, expert
    # width 1792, sigmoid router 32 / 4 a token with a selection bias.
    # benchmark/configs/lfm2-8b-a1b.json states the cut.
    "lfm2-8b-a1b": _mk(
        arch="lfm2moe", vocab_size=65536, dim=2048, n_layers=16,
        n_heads=32, n_kv_heads=8, head_dim=64, ffn_dim=1792,
        n_experts=32, n_experts_used=4, moe_score="sigmoid",
        moe_select_bias=True, moe_renorm=True, moe_scale=1.0,
        n_dense_layers=2, dense_ffn_dim=7168,
        layer_kinds="ccAcccAcccAcccAc", conv_kernel=3, qk_norm=True,
        rope_theta=1000000.0, tie_embeddings=True, norm_eps=1e-5,
        max_seq_len=128000),
    # the same shape at toy widths (tests, --rehearse): two periods
    "tiny-lfm2": _mk(
        arch="lfm2moe", vocab_size=256, dim=64, n_layers=8, n_heads=4,
        n_kv_heads=2, head_dim=16, ffn_dim=32, n_experts=8,
        n_experts_used=3, moe_score="sigmoid", moe_select_bias=True,
        moe_renorm=True, moe_scale=1.0, n_dense_layers=2,
        dense_ffn_dim=96, layer_kinds="ccAcccAc", conv_kernel=3,
        qk_norm=True, rope_theta=1000000.0, tie_embeddings=True,
        max_seq_len=256),
    # K-EXAONE-236B-A23B (exaone_moe), ONE CHIP'S SHARE of a stated
    # deployment: each layer shared by 8 chips (expert parallel: this is
    # chip 0, routed experts 0-15 of 128, rows 0-19,199 of the untied
    # embedding and head; attention and the shared expert on every chip)
    # and the first pipeline stage of 8 of the 48 layers, w w w A w w w A
    # (two whole periods: layer 0 with its dense MLP, seven routed ones).
    # Every width is the published one: hidden 6144, GQA 64/8 at head_dim
    # 128 with q/k norms, window 128, dense width 18432, sigmoid router
    # 128 / 8 a token scaled 2.5, expert and shared-expert width 2048.
    # Window layers rotate at theta 1e6, full layers carry no position.
    # benchmark/configs/k-exaone-236b-a23b.json states the cut and what
    # the published config leaves to assumption.
    "k-exaone-236b-a23b": _mk(
        arch="exaonemoe", vocab_size=19200, dim=6144, n_layers=8,
        n_heads=64, n_kv_heads=8, head_dim=128, ffn_dim=2048,
        n_experts=128, n_experts_used=8, n_experts_held=16, expert_first=0,
        n_shared_ffn=2048, shared_gate=False, moe_score="sigmoid",
        moe_select_bias=True, moe_renorm=True, moe_scale=2.5,
        n_dense_layers=1, dense_ffn_dim=18432, layer_kinds="wwwAwwwA",
        sliding_window=128, rope_kinds="w", qk_norm=True,
        rope_theta=1000000.0, norm_eps=1e-5, max_seq_len=262144),
    # the same shape at toy widths (tests, --rehearse): two periods
    "tiny-exaone": _mk(
        arch="exaonemoe", vocab_size=256, dim=64, n_layers=8, n_heads=4,
        n_kv_heads=2, head_dim=16, ffn_dim=32, n_experts=8,
        n_experts_used=3, n_experts_held=4, expert_first=0,
        n_shared_ffn=32, shared_gate=False, moe_score="sigmoid",
        moe_select_bias=True, moe_renorm=True, moe_scale=2.5,
        n_dense_layers=1, dense_ffn_dim=96, layer_kinds="wwwAwwwA",
        sliding_window=8, rope_kinds="w", qk_norm=True,
        rope_theta=1000000.0, max_seq_len=256),
    # SmallThinker-21BA3B-Instruct (smallthinker), cut in DEPTH alone:
    # pipeline stage 0 of seven, layers 0-7 of 52, two whole periods A w w w
    # (a full layer without positional embedding, then three window layers
    # of 4,096 positions that rotate at theta 1.5e6), every expert and the
    # whole untied vocabulary. Every width is the published one: hidden
    # 2560, GQA 28/4 at head_dim 128 without q/k norms, a softmax router
    # over 64 ReLU-gated experts of 768 keeping 6 a token, which reads the
    # layer's un-normed INPUT ahead of attention (moe_router_input), no
    # shared expert, no dense layer. benchmark/configs/
    # smallthinker-21b-a3b.json states the cut and what the published config
    # leaves to assumption.
    "smallthinker-21b-a3b": _mk(
        arch="smallthinker", vocab_size=151936, dim=2560, n_layers=8,
        n_heads=28, n_kv_heads=4, head_dim=128, ffn_dim=768, n_experts=64,
        n_experts_used=6, moe_score="softmax", moe_renorm=True, act="relu",
        moe_router_input="block", layer_kinds="AwwwAwww",
        sliding_window=4096, rope_kinds="w", rope_theta=1500000.0,
        tie_embeddings=False, norm_eps=1e-6, max_seq_len=16384),
    # the same shape at toy widths (tests, --rehearse): two periods
    "tiny-smallthinker": _mk(
        arch="smallthinker", vocab_size=512, dim=64, n_layers=8, n_heads=4,
        n_kv_heads=2, head_dim=16, ffn_dim=32, n_experts=8,
        n_experts_used=3, moe_score="softmax", moe_renorm=True, act="relu",
        moe_router_input="block", layer_kinds="AwwwAwww", sliding_window=8,
        rope_kinds="w", rope_theta=1500000.0, tie_embeddings=False,
        norm_eps=1e-6, max_seq_len=2048),
    # Olmo-Hybrid-7B (olmo_hybrid), cut in DEPTH alone: the first three
    # whole periods of the 32 layers, d d d A (layers 0-11; the others would
    # lie on further chips as pipeline stages). Every width, every head
    # and the whole vocabulary are the published ones: hidden 3840, gated
    # delta-rule layers of 30 heads (keys 96, values 192, convolution of 4
    # taps, negative eigenvalues allowed), MHA 30/30 at head_dim 128
    # without positional embedding, SwiGLU MLP of 11008, untied head over
    # 100,352 rows, no experts. benchmark/configs/olmo-hybrid-7b.json
    # states the cut and what the published config leaves to assumption.
    "olmo-hybrid-7b": _mk(
        arch="olmohybrid", vocab_size=100352, dim=3840, n_layers=12,
        n_heads=30, n_kv_heads=30, head_dim=128, ffn_dim=11008,
        layer_kinds="dddAdddAdddA", delta_heads=30, delta_key_dim=96,
        delta_value_dim=192, delta_conv=4, delta_neg_eigval=True,
        delta_chunk=64, rope=False, tie_embeddings=False, norm_eps=1e-6,
        max_seq_len=65536),
    # the same shape at toy widths (tests, --rehearse): two periods
    "tiny-olmo-hybrid": _mk(
        arch="olmohybrid", vocab_size=256, dim=64, n_layers=8, n_heads=4,
        n_kv_heads=4, head_dim=16, ffn_dim=128, layer_kinds="dddAdddA",
        delta_heads=4, delta_key_dim=8, delta_value_dim=16, delta_conv=4,
        delta_neg_eigval=True, delta_chunk=8, rope=False,
        tie_embeddings=False, norm_eps=1e-6, max_seq_len=256),
    # GLM-5 (glm_moe_dsa, 744B-A40B), ONE CHIP'S SHARE of a stated
    # deployment: each layer shared by 16 chips (expert parallel: this is
    # chip 0, routed experts 0-15 of 256, rows 0-19,359 of the untied
    # embedding and head; attention, the indexer and the shared expert on
    # every chip) and the first pipeline stage of 7 of the 78 layers: layer
    # 0 (one of the three leading dense layers: they count once) and six
    # routed ones. Every width is the published one: hidden 6144, latent
    # attention of 64 heads (query latent 2048, key/value latent 512, 192
    # un-rotated + 64 rotated query channels, values 256), the indexer of
    # 32 heads of 128 keeping 2048 positions a query, dense width 12288,
    # sigmoid router 256 / 8 a token scaled 2.5, expert and shared-expert
    # width 2048. benchmark/configs/glm-5.json states the cut and what the
    # published config leaves to assumption.
    "glm-5": _mk(
        arch="glmmoedsa", vocab_size=19360, dim=6144, n_layers=7,
        n_heads=64, n_kv_heads=64, head_dim=64, ffn_dim=2048,
        n_experts=256, n_experts_used=8, n_experts_held=16, expert_first=0,
        n_shared_ffn=2048, shared_gate=False, moe_score="sigmoid",
        moe_select_bias=True, moe_renorm=True, moe_scale=2.5,
        n_dense_layers=1, dense_ffn_dim=12288, layer_kinds="AAAAAAA",
        kv_latent_dim=512, q_latent_dim=2048, qk_nope_dim=192,
        qk_rope_dim=64, v_head_dim=256, index_heads=32, index_head_dim=128,
        index_topk=2048, rope_interleave=True, rope_theta=1000000.0,
        norm_eps=1e-5, max_seq_len=202752),
    # the same shape at toy widths (tests, --rehearse): the indexer keeps
    # 16 positions a query, so a prompt of a few dozen tokens chooses
    "tiny-glm5": _mk(
        arch="glmmoedsa", vocab_size=256, dim=64, n_layers=4, n_heads=4,
        n_kv_heads=4, head_dim=8, ffn_dim=32, n_experts=16,
        n_experts_used=3, n_experts_held=4, expert_first=0,
        n_shared_ffn=32, shared_gate=False, moe_score="sigmoid",
        moe_select_bias=True, moe_renorm=True, moe_scale=2.5,
        n_dense_layers=1, dense_ffn_dim=96, layer_kinds="AAAA",
        kv_latent_dim=32, q_latent_dim=48, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=24, index_heads=4, index_head_dim=16, index_topk=16,
        rope_interleave=True, rope_theta=1000000.0, max_seq_len=256),
    # Kimi-K2.7-Code (kimi_k2, 1.04T-A32B), ONE CHIP'S SHARE of a stated
    # deployment: each layer shared by 32 chips (expert parallel: this is
    # chip 0, routed experts 0-11 of 384, rows 0-20,479 of the untied
    # embedding and head; attention and the shared expert on every chip)
    # and the first pipeline stage of 8 of the 61 layers: layer 0 (dense)
    # and seven routed ones. Every width is the published one: hidden 7168,
    # latent attention of 64 heads (query latent 1536, key/value latent 512,
    # 128 un-rotated + 64 rotated query channels, values 128) with NO
    # indexer, rotary under YaRN (factor 64 over 4,096 positions at theta
    # 50,000, mscale = mscale_all_dim = 1: cos/sin as they are, the softmax
    # scale times (0.1 ln 64 + 1)^2), dense width 18432, sigmoid router 384
    # / 8 a token scaled 2.827, expert and shared-expert width 2048.
    # benchmark/configs/kimi-k2.7-code.json states the cut and what the
    # published config leaves to assumption.
    "kimi-k2.7-code": _mk(
        arch="kimik2", vocab_size=20480, dim=7168, n_layers=8, n_heads=64,
        n_kv_heads=64, head_dim=64, ffn_dim=2048, n_experts=384,
        n_experts_used=8, n_experts_held=12, expert_first=0,
        n_shared_ffn=2048, shared_gate=False, moe_score="sigmoid",
        moe_select_bias=True, moe_renorm=True, moe_scale=2.827,
        n_dense_layers=1, dense_ffn_dim=18432, layer_kinds="AAAAAAAA",
        kv_latent_dim=512, q_latent_dim=1536, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, rope_interleave=True,
        rope_theta=50000.0, rope_scaling_type="yarn", rope_scaling=64.0,
        rope_orig_ctx=4096, rope_yarn_beta_fast=32.0,
        rope_yarn_beta_slow=1.0, rope_yarn_mscale=1.0,
        rope_yarn_mscale_all_dim=1.0, norm_eps=1e-5, max_seq_len=262144),
    # the same shape at toy widths (tests, --rehearse): four shares of a
    # 16-wide router; YaRN over 32 positions, so a prompt of a few dozen
    # tokens passes the original context
    "tiny-kimi-k2": _mk(
        arch="kimik2", vocab_size=256, dim=64, n_layers=4, n_heads=4,
        n_kv_heads=4, head_dim=8, ffn_dim=32, n_experts=16,
        n_experts_used=3, n_experts_held=4, expert_first=0,
        n_shared_ffn=32, shared_gate=False, moe_score="sigmoid",
        moe_select_bias=True, moe_renorm=True, moe_scale=2.827,
        n_dense_layers=1, dense_ffn_dim=96, layer_kinds="AAAA",
        kv_latent_dim=32, q_latent_dim=48, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, rope_interleave=True, rope_theta=50000.0,
        rope_scaling_type="yarn", rope_scaling=8.0, rope_orig_ctx=32,
        rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0,
        rope_yarn_mscale=1.0, rope_yarn_mscale_all_dim=1.0,
        max_seq_len=256),
    "dolphin-mixtral": _mk(arch="llama", vocab_size=32002, dim=4096,
                           n_layers=32, n_heads=32, n_kv_heads=8,
                           head_dim=128, ffn_dim=14336, n_experts=8,
                           n_experts_used=2, rope_theta=1000000.0,
                           max_seq_len=32768),
}


def get_config(name: str) -> ModelConfig:
    base = name.split(":")[0]
    if name in PRESETS:
        return PRESETS[name]
    if base in PRESETS:
        return PRESETS[base]
    raise KeyError(f"unknown model preset: {name!r}; known: {sorted(PRESETS)}")
