"""Plain reference of LFM2-8B-A1B (lfm2_moe) as the benchmark cuts it: the
first ``num_hidden_layers`` layers of the published stack, every width, every
expert and the whole vocabulary. Float32 at the highest matmul precision, one
sequence, no cache, no batching, no import from the program; ``params`` is the
served weight tree (stacked leaves, input-major matrices), and every size
comes from ``conf``.

    h = E[tokens]
    each layer:  h = h + mixer(RMSNorm(h));  h = h + ffn(RMSNorm(h))
    logits = RMSNorm(h) @ E^T                       (the head is tied to E)

* Short-convolution mixer (``layer_types`` "conv"; K = ``conv_L_cache`` taps,
  ``conv_bias`` false): [B | C | v] = u @ W_in (three widths of hidden_size);
  x = B * v; c_t = sum_j w[j] * x_{t-K+1+j} per channel, zeros before the
  start, no bias, no activation; out = (C * c) @ W_out.
* Attention mixer ("full_attention"): grouped-query, no bias; RMSNorm over
  head_dim on q and on k (weights [head_dim]) BEFORE the rotary embedding;
  rotary over the whole head at ``rope_theta``, half-split pairing; scores
  over sqrt(head_dim); causal.
* Feed-forward of the first ``num_dense_layers`` layers: W2(silu(W1 u) * W3 u)
  of width ``intermediate_size``.
* Feed-forward of the others: s = sigmoid(u @ W_r) over ``num_experts``
  experts, float32; the ``num_experts_per_tok`` kept are the largest of s + b
  (``use_expert_bias``: b takes part in the SELECTION only); gates = s of the
  kept / (their sum + 1e-6) (``norm_topk_prob``) * ``routed_scaling_factor``;
  out = sum over the kept of gate_e * W2_e(silu(W1_e u) * W3_e u), width
  ``moe_intermediate_size``. No shared expert.

Departures from the published description: the depth (``reduced`` in the
configuration's file); the seeded weights; b read from the served leaf,
whatever type the program keeps it in, and added in float32.

The model makes a choice (the router's top-k), so beside ``forward`` the module
has ``forward_chosen`` (the contract at the head of
``benchmark/server_child.py``; its sets are [routed layers, T, k] in layer
order, and the score a shortfall is measured on is the one the selection
used, s + b) and, for the tests' control, ``forward_rounded``. It works a
layer at a time, an expert at a time and the head a block of the vocabulary
at a time, so that its float32 copies stay small beside 10.8 GB of served
weights."""

import jax
import jax.numpy as jnp

from benchmark import refmath as R

SITE = "moe.route"      # the program's name of the one choice site
HEAD_BLOCKS = 8         # the tied head, this many row blocks of E at a time


def kinds(conf):
    """'c' or 'A' a layer, from the published ``layer_types``."""
    return ["A" if t == "full_attention" else "c"
            for t in conf["layer_types"]]


def leaf(lp_all, name, r):
    """Row ``r`` of a stacked matrix as float32, dequantized where the
    program serves it quantized."""
    return R.dequant(jax.tree_util.tree_map(lambda a: a[r], lp_all[name]))


def gated(u, w_gate, w_up, w_down, rnd):
    return rnd(rnd(jax.nn.silu(rnd(u @ w_gate)) * rnd(u @ w_up)) @ w_down)


def route(lp_all, conf, u, r, given=None):
    """The router of routed layer ``r`` on normed hidden states u [T, D]:
    -> (gates [T, E] float32, zero for experts not kept; sets [T, k]
    ascending; shortfall [T]). ``given`` [T, k] takes the place of the
    router's own top-k."""
    k = conf["num_experts_per_tok"]
    rows = jnp.arange(u.shape[0])[:, None]
    score = jax.nn.sigmoid(u @ R.f32(lp_all["router"][r]))  # [T, E] float32
    pick = score
    if conf["use_expert_bias"]:
        pick = score + R.f32(lp_all["router_bias"][r])
    own_w, own = jax.lax.top_k(pick, k)
    sets = own if given is None else given
    # how far the weakest kept member lies below the model's own k-th best
    # on the score the selection used, as a share of the position's largest
    short = (jnp.maximum(own_w[:, -1]
                         - jnp.take_along_axis(pick, sets, axis=1).min(axis=1),
                         0.0) / jnp.abs(pick).max(axis=1))
    kept = jnp.take_along_axis(score, sets, axis=1)         # s, never s + b
    if conf["norm_topk_prob"]:
        kept = kept / (kept.sum(axis=1, keepdims=True) + 1e-6)
    kept = kept * conf["routed_scaling_factor"]
    gates = jnp.zeros_like(score).at[rows, sets].set(kept)
    return gates, jnp.sort(sets, axis=1), short


def expert_layer(lp_all, conf, h, i, r, given=None, rnd=None):
    """The routed feed-forward of layer ``i`` (row ``r`` of the routed
    stacks) on hidden states h [T, D]. -> (y [T, D], sets, shortfall)."""
    rnd = rnd or (lambda x: x)
    with jax.default_matmul_precision("highest"):
        u = rnd(R.rms_norm(h, R.f32(lp_all["mlp_norm_w"][i]),
                           conf["norm_eps"]))
        gates, sets, short = route(lp_all, conf, u, r, given)

        def one(name, e):
            # expert e of routed layer r, read where it lies: a scan over a
            # slice of the stack would copy the whole layer's experts first
            w = lp_all[name]
            return R.f32(jax.lax.dynamic_slice(
                w, (r, e, 0, 0), (1, 1) + w.shape[2:])[0, 0])

        def expert(acc, eg):
            e, g = eg
            return acc + g[:, None] * gated(u, one("we_gate", e),
                                            one("we_up", e),
                                            one("we_down", e), rnd), None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                            (jnp.arange(conf["num_experts"]), gates.T))
        return rnd(y), sets, short


def head(params, h):
    """h [T, D] -> logits [T, V] against the tied embedding, a block of its
    rows at a time."""
    emb = params["tok_emb"]
    V, D = emb.shape
    nb = HEAD_BLOCKS if V % HEAD_BLOCKS == 0 else 1

    def block(j):
        rows = jax.lax.dynamic_slice(emb, (j * (V // nb), 0), (V // nb, D))
        return h @ R.f32(rows).T                            # [T, V / nb]
    out = jax.lax.map(block, jnp.arange(nb))                # [nb, T, V / nb]
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)


def run(params, conf, tokens, chosen=None, rnd=None):
    """tokens [T] int32 -> (logits [T, V] float32, sets [Lr, T, k] int32
    ascending, shortfall [T]). ``chosen`` [Lr, T, k] takes the place of the
    model's own top-k where it is given; ``rnd`` rounds every activation."""
    nH, KvH, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    D, K, eps = conf["hidden_size"], conf["conv_L_cache"], conf["norm_eps"]
    rnd = rnd or (lambda x: x)
    T = tokens.shape[0]
    positions = jnp.arange(T)
    lp_all = params["layers"]

    def attention(u, r):
        q = rnd(u @ leaf(lp_all, "wq", r)).reshape(T, nH, hd)
        k = rnd(u @ leaf(lp_all, "wk", r)).reshape(T, KvH, hd)
        v = rnd(u @ leaf(lp_all, "wv", r)).reshape(T, KvH, hd)
        q = rnd(R.rms_norm(q, R.f32(lp_all["q_norm_w"][r]), eps))
        k = rnd(R.rms_norm(k, R.f32(lp_all["k_norm_w"][r]), eps))
        q = R.rotate_half(q, positions, hd, conf["rope_theta"])
        k = R.rotate_half(k, positions, hd, conf["rope_theta"])
        a = R.causal_attention(rnd(q), rnd(k), v)
        return rnd(rnd(a).reshape(T, nH * hd) @ leaf(lp_all, "wo", r))

    def short_conv(u, r):
        bcv = rnd(u @ leaf(lp_all, "conv_in", r))          # [T, 3 D]
        gate_b, gate_c, v = bcv[:, :D], bcv[:, D:2 * D], bcv[:, 2 * D:]
        x = rnd(gate_b * v)
        w = R.f32(lp_all["conv_w"][r])                      # [K, D]
        pad = jnp.concatenate([jnp.zeros((K - 1, D)), x], 0)
        c = sum(w[j] * pad[j:j + T] for j in range(K))
        return rnd(rnd(gate_c * rnd(c)) @ leaf(lp_all, "conv_out", r))

    with jax.default_matmul_precision("highest"):
        h = rnd(R.f32(params["tok_emb"][tokens]))
        all_sets, all_short = [], []
        n = {"A": 0, "c": 0}
        n_dense = conf["num_dense_layers"]
        # layers of two mixers and two feed-forwards: a plain loop, each
        # layer reading its own row of the stacks it has a part in
        for i, kind in enumerate(kinds(conf)):
            u = rnd(R.rms_norm(h, R.f32(lp_all["attn_norm_w"][i]), eps))
            mix = attention(u, n[kind]) if kind == "A" \
                else short_conv(u, n[kind])
            n[kind] += 1
            h = rnd(h + mix)
            if i < n_dense:
                u = rnd(R.rms_norm(h, R.f32(lp_all["mlp_norm_w"][i]), eps))
                y = gated(u, leaf(lp_all, "w_gate", i),
                          leaf(lp_all, "w_up", i),
                          leaf(lp_all, "w_down", i), rnd)
            else:
                r = i - n_dense
                y, sets, short = expert_layer(
                    lp_all, conf, h, i, r,
                    None if chosen is None else chosen[r], rnd)
                all_sets.append(sets)
                all_short.append(short)
            h = rnd(h + y)
        h = rnd(R.rms_norm(h, R.f32(params["out_norm_w"]), eps))
        return (head(params, h), jnp.stack(all_sets),
                jnp.stack(all_short).max(axis=0))


def forward(params, conf, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32."""
    return run(params, conf, tokens)[0]


def forward_chosen(params, conf, tokens, chosen):
    """-> (logits [T, V], shortfall [T]) with ``chosen[SITE]`` [Lr, T, k] in
    place of the model's own top-k."""
    logits, _sets, short = run(params, conf, tokens,
                               jnp.asarray(chosen[SITE], jnp.int32))
    return logits, short


def forward_rounded(params, conf, tokens, dtype=jnp.bfloat16):
    """The control: every activation rounded through ``dtype``.
    -> (logits [T, V], {SITE: its own sets [Lr, T, k]})."""
    logits, sets, _ = run(params, conf, tokens,
                          rnd=lambda x: x.astype(dtype).astype(jnp.float32))
    return logits, {SITE: sets}
