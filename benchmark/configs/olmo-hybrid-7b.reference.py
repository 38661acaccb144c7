"""Plain reference of Olmo-Hybrid-7B (olmo_hybrid) as the benchmark cuts it:
the first periods of the layer pattern ``linear_attention x3, full_attention``
at the published widths, no experts. Float32 at the highest matmul precision,
one sequence, no cache, no batching, no blocks, no import from the program;
``params`` is the served weight tree (stacked leaves, input-major matrices),
and every size comes from ``conf``.

    h = E[tokens]
    each layer:  h = h + mixer(RMSNorm(h));  h = h + SwiGLU(RMSNorm(h))
    logits = RMSNorm(h) @ W_head                         (untied)

* Linear-attention mixer (``layer_types`` "linear_attention": the gated delta
  rule; H = ``linear_num_key_heads`` = ``linear_num_value_heads`` heads, dk =
  ``linear_key_head_dim``, dv = ``linear_value_head_dim``, K =
  ``linear_conv_kernel_dim`` taps), x_t the normed input of position t:
  1. [q~ | k~ | v~] = x_t W_qkv (H dk, H dk, H dv wide); [a_t | b_t] = x_t
     W_ab (H each); z_t = x_t W_z (H dv wide, the output gate).
  2. A depthwise causal convolution of K taps, no bias, then SiLU, over the
     channels of [q~ | k~ | v~], zeros before the first position: q', k', v'.
  3. A head: q = q' / sqrt(|q'|^2 + 1e-6) / sqrt(dk), k = k' / sqrt(|k'|^2 +
     1e-6) (over dk), v = v'.
  4. beta_t = sigmoid(b_t), doubled where ``linear_allow_neg_eigval``;
     g_t = -exp(A_log) softplus(a_t + dt_bias); alpha_t = exp(g_t).
  5. The state S [dk, dv] a head, zeros before the first position, a plain
     ``lax.scan`` over the positions: S' = alpha_t S_{t-1}; u_t = beta_t (v_t
     - S'^T k_t); S_t = S' + k_t u_t^T; o_t = S_t^T q_t.
  6. y_t = RMSNorm_dv(o_t; w) * SiLU(z_t) a head (``rms_norm_eps``), heads
     concatenated; out = y_t W_o.
* Attention mixer ("full_attention"): multi-head (``num_key_value_heads``
  = ``num_attention_heads``), no bias, no positional embedding
  (``rope_parameters.rope_theta`` is null), no q/k norm, scores over
  sqrt(head_dim), causal.

What the configuration's file lists under ``assumed`` (the block's norm
placement, no q/k norms, no positions on the full layers) is what this file
and the program both take; none of it touches steps 1-6. The model makes no
choice inside its forward pass, so there is no ``forward_chosen``."""

import jax
import jax.numpy as jnp

from benchmark import refmath as R

HEAD_BLOCKS = 8         # the output head goes a block of columns at a time


def kinds(conf):
    """'d' or 'A' a layer, from the published ``layer_types``."""
    return ["A" if t == "full_attention" else "d"
            for t in conf["layer_types"]]


def leaf(lp_all, name, r):
    """Row ``r`` of a stacked leaf the program may serve quantized."""
    return R.dequant(jax.tree_util.tree_map(lambda a: a[r], lp_all[name]))


def head(params, h):
    """h [T, D] -> logits [T, V] against the untied head, a block of its
    columns at a time (the whole of it in float32 is 1.5 GB)."""
    w = params["lm_head"]
    if isinstance(w, dict):
        return h @ R.dequant(w)
    D, V = w.shape
    nb = HEAD_BLOCKS if V % HEAD_BLOCKS == 0 else 1

    def block(j):
        cols = jax.lax.dynamic_slice(w, (0, j * (V // nb)), (D, V // nb))
        return h @ R.f32(cols)                              # [T, V / nb]
    out = jax.lax.map(block, jnp.arange(nb))                # [nb, T, V / nb]
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)


def forward(params, conf, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32."""
    nH, hd = conf["num_attention_heads"], conf["head_dim"]
    KvH = conf["num_key_value_heads"]
    H, dk, dv = (conf["linear_num_key_heads"], conf["linear_key_head_dim"],
                 conf["linear_value_head_dim"])
    assert conf["linear_num_value_heads"] == H
    K, eps = conf["linear_conv_kernel_dim"], conf["rms_norm_eps"]
    beta_max = 2.0 if conf["linear_allow_neg_eigval"] else 1.0
    T = tokens.shape[0]
    lp_all = params["layers"]

    def attention(x, r):
        q = (x @ leaf(lp_all, "wq", r)).reshape(T, nH, hd)
        k = (x @ leaf(lp_all, "wk", r)).reshape(T, KvH, hd)
        v = (x @ leaf(lp_all, "wv", r)).reshape(T, KvH, hd)
        a = R.causal_attention(q, k, v)
        return a.reshape(T, nH * hd) @ leaf(lp_all, "wo", r)

    def l2(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    def delta(x, r):
        qkv = x @ leaf(lp_all, "delta_qkv", r)              # step 1
        ab = x @ leaf(lp_all, "delta_ab", r)
        z = (x @ leaf(lp_all, "delta_z", r)).reshape(T, H, dv)
        w = R.f32(lp_all["delta_conv_w"][r])                # step 2, [K, C]
        pad = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1])), qkv], 0)
        qkv = jax.nn.silu(sum(w[j] * pad[j:j + T] for j in range(K)))
        q = l2(qkv[:, :H * dk].reshape(T, H, dk)) / jnp.sqrt(
            jnp.float32(dk))                                # step 3
        k = l2(qkv[:, H * dk:2 * H * dk].reshape(T, H, dk))
        v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
        beta = beta_max * jax.nn.sigmoid(ab[:, H:])         # step 4, [T, H]
        alpha = jnp.exp(-jnp.exp(R.f32(lp_all["delta_a_log"][r]))
                        * jax.nn.softplus(
                            ab[:, :H] + R.f32(lp_all["delta_dt_bias"][r])))

        def step(S, xs):                                    # step 5
            q_t, k_t, v_t, alpha_t, beta_t = xs
            S = alpha_t[:, None, None] * S
            u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
            S = S + k_t[:, :, None] * u[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, q_t)

        _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv)),
                            (q, k, v, alpha, beta))         # [T, H, dv]
        y = R.rms_norm(o, R.f32(lp_all["delta_norm_w"][r]), eps) \
            * jax.nn.silu(z)                                # step 6
        return y.reshape(T, H * dv) @ leaf(lp_all, "delta_out", r)

    def swiglu(x, i):
        return (jax.nn.silu(x @ leaf(lp_all, "w_gate", i))
                * (x @ leaf(lp_all, "w_up", i))) @ leaf(lp_all, "w_down", i)

    with jax.default_matmul_precision("highest"):
        h = R.f32(params["tok_emb"][tokens])
        n = {"A": 0, "d": 0}
        # layers of two mixers: a plain loop, each layer reading its own row
        # of its mixer's stack
        for i, kind in enumerate(kinds(conf)):
            x = R.rms_norm(h, R.f32(lp_all["attn_norm_w"][i]), eps)
            h = h + (attention(x, n[kind]) if kind == "A"
                     else delta(x, n[kind]))
            n[kind] += 1
            x = R.rms_norm(h, R.f32(lp_all["mlp_norm_w"][i]), eps)
            h = h + swiglu(x, i)
        h = R.rms_norm(h, R.f32(params["out_norm_w"]), eps)
        return head(params, h)
