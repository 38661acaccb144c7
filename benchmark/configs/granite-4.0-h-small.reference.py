"""Plain reference of granite-4.0-h-small (granitemoehybrid) as the benchmark
cuts it: ONE CHIP'S SHARE of a layer divided over two (this chip's held
experts, its rows of the tied vocabulary) and the first period of the layer
pattern. Float32 at the highest matmul precision, one sequence, no cache, no
batching, no import from the program; ``params`` is the served weight tree
(stacked leaves, input-major matrices), and every size comes from ``conf``.

    h = E[tokens] * embedding_multiplier
    each layer:  h = h + residual_multiplier * mixer(RMSNorm(h))
                 h = h + residual_multiplier * (experts(u) + shared(u)),
                                                u = RMSNorm(h)
    logits = RMSNorm(h) @ E^T / logits_scaling          (the held rows of E)

* Mamba-2 mixer (``layer_types`` "mamba"; H heads of P, one group, state N,
  convolution K): [z | xBC | dt] = u @ W_in (widths H*P, H*P + 2N, H);
  xBC_t = silu(b_c + sum_j w_c[j] * xBC_{t-K+1+j}) per channel, zeros before
  the start; xBC = [x (H, P) | B (N) | C (N)]; dt = softplus(dt + dt_bias);
  A = -exp(A_log); S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, a plain
  ``lax.scan`` over the positions; y_t = S_t C_t + D x_t; y = y * silu(z);
  y = y * rsqrt(mean(y^2 over all H*P) + eps) * w_norm; out = y @ W_out.
* Attention mixer ("attention"): grouped-query, no bias, NO rotary embedding
  (``position_embedding_type`` "nope"), scores scaled by
  ``attention_multiplier``, causal.
* Experts: s = u @ W_r over ALL ``router_width`` experts; the
  ``num_experts_per_tok`` of largest s are kept; gates = softmax over the kept
  scores; the chip adds gate_e * expert_e(u) for kept e among the
  ``num_local_experts`` it holds from ``expert_first`` on. A kept expert held
  elsewhere adds nothing here and its gate is neither renormalised nor
  replaced. shared(u): the same gated-SiLU form, added whole.

The model makes a choice (the router's top-k), so beside ``forward`` the module
has ``forward_chosen`` (the contract at the head of
``benchmark/server_child.py``) and, for the tests' control,
``forward_rounded``."""

import jax
import jax.numpy as jnp

from benchmark import refmath as R

SITE = "moe.route"      # the program's name of the one choice site


def kinds(conf):
    """'m' or 'A' a layer, from the published ``layer_types``."""
    return ["A" if t == "attention" else "m" for t in conf["layer_types"]]


def expert_layer(lp_all, conf, h, i, given=None, rnd=None):
    """The expert half of layer ``i`` on hidden states h [T, D]: RMSNorm,
    the router over all ``router_width`` experts, this chip's share of the
    kept experts' outputs, the shared expert whole. ``given`` [T, k] takes
    the place of the router's own top-k. -> (y [T, D], sets [T, k] ascending,
    shortfall [T])."""
    k = conf["num_experts_per_tok"]
    first, held = conf["expert_first"], conf["num_local_experts"]
    rnd = rnd or (lambda x: x)
    rows = jnp.arange(h.shape[0])[:, None]

    def gated(u, w_gate, w_up, w_down):
        return rnd(rnd(jax.nn.silu(rnd(u @ w_gate)) * rnd(u @ w_up)) @ w_down)

    with jax.default_matmul_precision("highest"):
        u = rnd(R.rms_norm(h, R.f32(lp_all["mlp_norm_w"][i]),
                           conf["rms_norm_eps"]))
        score = u @ R.f32(lp_all["router"][i])              # [T, E] float32
        own_w, own = jax.lax.top_k(score, k)
        sets = own if given is None else given
        kept = jnp.take_along_axis(score, sets, axis=1)     # [T, k]
        # how far the weakest kept member lies below the model's own k-th
        # best, as a share of the position's largest |score|
        short = (jnp.maximum(own_w[:, -1] - kept.min(axis=1), 0.0)
                 / jnp.abs(score).max(axis=1))
        gates = jnp.zeros_like(score).at[rows, sets].set(
            jax.nn.softmax(kept, axis=-1))
        gates = gates[:, first:first + held]    # this chip's experts only

        def one(name, e):
            # expert e of layer i, read where it lies: a scan over a slice
            # of the stack would copy the whole layer's experts first
            w = lp_all[name]
            return R.f32(jax.lax.dynamic_slice(
                w, (i, e, 0, 0), (1, 1) + w.shape[2:])[0, 0])

        def expert(acc, eg):
            e, g = eg
            return acc + g[:, None] * gated(u, one("we_gate", e),
                                            one("we_up", e),
                                            one("we_down", e)), None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                            (jnp.arange(held), gates.T))
        y = y + gated(u, R.f32(lp_all["we_sh_gate"][i]),
                      R.f32(lp_all["we_sh_up"][i]),
                      R.f32(lp_all["we_sh_down"][i]))
        return rnd(y), jnp.sort(sets, axis=1), short


def run(params, conf, tokens, chosen=None, rnd=None):
    """tokens [T] int32 -> (logits [T, V] float32, sets [L, T, k] int32
    ascending, shortfall [T]). ``chosen`` [L, T, k] takes the place of the
    model's own top-k where it is given; ``rnd`` rounds every activation."""
    nH, KvH, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    H, P, N, K = (conf["mamba_n_heads"], conf["mamba_d_head"],
                  conf["mamba_d_state"], conf["mamba_d_conv"])
    di = H * P
    eps, rm = conf["rms_norm_eps"], conf["residual_multiplier"]
    rnd = rnd or (lambda x: x)
    T = tokens.shape[0]
    rows = jnp.arange(T)[:, None]
    lp_all = params["layers"]

    def attention(u, r):
        def w(name):        # a leaf the program may serve quantized
            return R.dequant(jax.tree_util.tree_map(lambda a: a[r],
                                                    lp_all[name]))
        q = rnd(u @ w("wq")).reshape(T, nH, hd)
        kk = rnd(u @ w("wk")).reshape(T, KvH, hd)
        v = rnd(u @ w("wv")).reshape(T, KvH, hd)
        kk = jnp.repeat(kk, nH // KvH, axis=1)
        v = jnp.repeat(v, nH // KvH, axis=1)
        s = jnp.einsum("thd,shd->hts", q, kk) * conf["attention_multiplier"]
        s = jnp.where((jnp.arange(T)[None, :] <= rows)[None], s, -jnp.inf)
        a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
        return rnd(rnd(a).reshape(T, nH * hd) @ w("wo"))

    def mamba(u, r):
        zxd = rnd(u @ R.f32(lp_all["ssm_in"][r]))
        z, xbc, dt = (zxd[:, :di], zxd[:, di:2 * di + 2 * N],
                      zxd[:, 2 * di + 2 * N:])
        w = R.f32(lp_all["ssm_conv_w"][r])                  # [K, C]
        pad = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
        conv = R.f32(lp_all["ssm_conv_b"][r])
        for j in range(K):
            conv = conv + w[j] * pad[j:j + T]
        xbc = rnd(jax.nn.silu(conv))
        x = xbc[:, :di].reshape(T, H, P)
        Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
        dt = jax.nn.softplus(dt + R.f32(lp_all["ssm_dt_bias"][r]))  # [T, H]
        A = -jnp.exp(R.f32(lp_all["ssm_a_log"][r]))                # [H]

        def step(S, xs):
            x_t, B_t, C_t, dt_t = xs
            S = (jnp.exp(dt_t * A)[:, None, None] * S
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
            return S, S @ C_t                                       # [H, P]

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, Bm, Cm, dt))
        y = y + R.f32(lp_all["ssm_d"][r])[:, None] * x
        y = rnd(y.reshape(T, di) * jax.nn.silu(z))
        y = rnd(R.rms_norm(y, R.f32(lp_all["ssm_norm_w"][r]), eps))
        return rnd(y @ R.f32(lp_all["ssm_out"][r]))

    with jax.default_matmul_precision("highest"):
        h = rnd(R.f32(params["tok_emb"])[tokens]
                * conf["embedding_multiplier"])
        all_sets, all_short = [], []
        n = {"A": 0, "m": 0}
        # ten layers of two kinds: a plain loop, each layer reading its own
        # row of its mixer's stack
        for i, kind in enumerate(kinds(conf)):
            u = rnd(R.rms_norm(h, R.f32(lp_all["attn_norm_w"][i]), eps))
            mix = attention(u, n[kind]) if kind == "A" else mamba(u, n[kind])
            n[kind] += 1
            h = rnd(h + rm * mix)
            y, sets, short = expert_layer(
                lp_all, conf, h, i, None if chosen is None else chosen[i],
                rnd)
            h = rnd(h + rm * y)
            all_sets.append(sets)
            all_short.append(short)
        h = rnd(R.rms_norm(h, R.f32(params["out_norm_w"]), eps))
        logits = h @ R.f32(params["tok_emb"]).T / conf["logits_scaling"]
        return (logits, jnp.stack(all_sets),
                jnp.stack(all_short).max(axis=0))


def forward(params, conf, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32."""
    return run(params, conf, tokens)[0]


def forward_chosen(params, conf, tokens, chosen):
    """-> (logits [T, V], shortfall [T]) with ``chosen[SITE]`` [L, T, k] in
    place of the model's own top-k."""
    logits, _sets, short = run(params, conf, tokens,
                               jnp.asarray(chosen[SITE], jnp.int32))
    return logits, short


def forward_rounded(params, conf, tokens, dtype=jnp.bfloat16):
    """The control: every activation rounded through ``dtype``.
    -> (logits [T, V], {SITE: its own sets [L, T, k]})."""
    logits, sets, _ = run(params, conf, tokens,
                          rnd=lambda x: x.astype(dtype).astype(jnp.float32))
    return logits, {SITE: sets}
