"""Plain reference of K-EXAONE-236B-A23B (exaone_moe) as the benchmark cuts
it: the first ``num_hidden_layers`` layers of the published stack at every
published width, of each routed layer the ``num_experts`` experts this chip
holds from ``expert_first`` on (the router keeps its ``router_width`` outputs),
and the held rows of the untied embedding and head. Float32 at the highest
matmul precision, one sequence, no cache, no ring, no batching, no import from
the program; ``params`` is the served weight tree (stacked leaves, input-major
matrices), and every size comes from ``conf``.

    h = E[tokens]
    each layer:  h = h + attention(RMSNorm(h));  h = h + ffn(RMSNorm(h))
    logits = RMSNorm(h) @ W_head                    (untied: its own matrix)

* Attention, every layer: grouped-query, no bias; RMSNorm over head_dim on q
  and on k (weights [head_dim]); scores over sqrt(head_dim); causal over
  FULL-LENGTH keys.
  - ``layer_types`` "sliding_attention": rotary over the whole head at
    ``rope_theta``, half-split pairing, after the norms; a key is visible iff
    it lies fewer than ``sliding_window`` positions back (the query's own
    position counted: 128 keys).
  - "full_attention": no positional embedding; every earlier key is visible.
* Feed-forward of the first ``first_k_dense_replace`` layers:
  W2(silu(W1 u) * W3 u) of width ``intermediate_size``.
* Feed-forward of the others: s = sigmoid(u @ W_r) over ``router_width``
  experts, float32; the ``num_experts_per_tok`` kept are the largest of s + b
  (b a selection bias, in the SELECTION only; ``n_group`` = ``topk_group`` = 1:
  no group limit); gates = s of the kept / (their sum + 1e-6)
  (``norm_topk_prob``) * ``routed_scaling_factor``; out = sum over the kept
  that this chip holds of gate_e * W2_e(silu(W1_e u) * W3_e u), width
  ``moe_intermediate_size``, plus one shared expert of the same form and
  width, added whole. Gates of kept experts held elsewhere are neither
  renormalised nor replaced.

Departures from the published description: the cut (``reduced`` in the
configuration's file); what the published config leaves open (``assumed``
there); the seeded weights; b read from the served leaf, whatever type the
program keeps it in, and added in float32. The multi-token-prediction layer is
not held: it changes no logit of the main stack.

The model makes a choice (the router's top-k), so beside ``forward`` the module
has ``forward_chosen`` (the contract at the head of
``benchmark/server_child.py``; its sets are [routed layers, T, k] in layer
order, and the score a shortfall is measured on is the one the selection
used, s + b) and, for the tests' control, ``forward_rounded``. It works a
layer at a time, an expert at a time and the dense width a block at a time,
so that its float32 copies stay small beside 12 GB of served weights."""

import jax
import jax.numpy as jnp

from benchmark import refmath as R

SITE = "moe.route"      # the program's name of the one choice site
DENSE_BLOCKS = 8        # the dense feed-forward, this many blocks of its width


def kinds(conf):
    """'w' or 'A' a layer, from the published ``layer_types``."""
    return ["A" if t == "full_attention" else "w"
            for t in conf["layer_types"]]


def leaf(lp_all, name, r):
    """Row ``r`` of a stacked matrix as float32, dequantized where the
    program serves it quantized."""
    return R.dequant(jax.tree_util.tree_map(lambda a: a[r], lp_all[name]))


def gated(u, w_gate, w_up, w_down, rnd):
    return rnd(rnd(jax.nn.silu(rnd(u @ w_gate)) * rnd(u @ w_up)) @ w_down)


def dense_layer(lp_all, u, r, rnd):
    """The dense gated MLP of layer ``r`` on normed hidden states u [T, D],
    a block of its width at a time: the three matrices of 6144 x 18432 are
    1.4 GB in float32 whole."""
    if isinstance(lp_all["w_down"], dict):      # served quantized: whole
        return gated(u, leaf(lp_all, "w_gate", r), leaf(lp_all, "w_up", r),
                     leaf(lp_all, "w_down", r), rnd)
    F = lp_all["w_down"].shape[1]
    nb = DENSE_BLOCKS if F % DENSE_BLOCKS == 0 else 1
    width = F // nb

    def block(acc, j):
        def cols(name):
            w = lp_all[name]
            return R.f32(jax.lax.dynamic_slice(
                w, (r, 0, j * width), (1, w.shape[1], width))[0])
        w = lp_all["w_down"]
        down = R.f32(jax.lax.dynamic_slice(
            w, (r, j * width, 0), (1, width, w.shape[2]))[0])
        mid = rnd(jax.nn.silu(rnd(u @ cols("w_gate"))) * rnd(u @ cols("w_up")))
        return acc + mid @ down, None
    y, _ = jax.lax.scan(block, jnp.zeros_like(u), jnp.arange(nb))
    return rnd(y)


def route(lp_all, conf, u, r, given=None):
    """The router of routed layer ``r`` on normed hidden states u [T, D]:
    -> (gates [T, E] float32 over the router's whole width, zero for experts
    not kept; sets [T, k] ascending; shortfall [T]). ``given`` [T, k] takes
    the place of the router's own top-k."""
    k = conf["num_experts_per_tok"]
    rows = jnp.arange(u.shape[0])[:, None]
    score = jax.nn.sigmoid(u @ R.f32(lp_all["router"][r]))  # [T, E] float32
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
    stacks) on hidden states h [T, D]: this chip's experts' part and the
    shared expert. -> (y [T, D], sets, shortfall)."""
    rnd = rnd or (lambda x: x)
    first, held = conf["expert_first"], conf["num_experts"]
    with jax.default_matmul_precision("highest"):
        u = rnd(R.rms_norm(h, R.f32(lp_all["mlp_norm_w"][i]),
                           conf["rms_norm_eps"]))
        gates, sets, short = route(lp_all, conf, u, r, given)
        gates = gates[:, first:first + held]    # this chip's experts only

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
                            (jnp.arange(held), gates.T))
        y = y + gated(u, R.f32(lp_all["we_sh_gate"][r]),
                      R.f32(lp_all["we_sh_up"][r]),
                      R.f32(lp_all["we_sh_down"][r]), rnd)
        return rnd(y), sets, short


def run(params, conf, tokens, chosen=None, rnd=None):
    """tokens [T] int32 -> (logits [T, V] float32, sets [Lr, T, k] int32
    ascending, shortfall [T]). ``chosen`` [Lr, T, k] takes the place of the
    model's own top-k where it is given; ``rnd`` rounds every activation."""
    nH, KvH, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    eps, W = conf["rms_norm_eps"], conf["sliding_window"]
    rnd = rnd or (lambda x: x)
    T = tokens.shape[0]
    positions = jnp.arange(T)
    lp_all = params["layers"]

    def attention(u, r, kind):
        q = rnd(u @ leaf(lp_all, "wq", r)).reshape(T, nH, hd)
        k = rnd(u @ leaf(lp_all, "wk", r)).reshape(T, KvH, hd)
        v = rnd(u @ leaf(lp_all, "wv", r)).reshape(T, KvH, hd)
        q = rnd(R.rms_norm(q, R.f32(lp_all["q_norm_w"][r]), eps))
        k = rnd(R.rms_norm(k, R.f32(lp_all["k_norm_w"][r]), eps))
        if kind == "w":
            q = R.rotate_half(q, positions, hd, conf["rope_theta"])
            k = R.rotate_half(k, positions, hd, conf["rope_theta"])
        a = R.causal_attention(rnd(q), rnd(k), v,
                               window=W if kind == "w" else 0)
        return rnd(rnd(a).reshape(T, nH * hd) @ leaf(lp_all, "wo", r))

    with jax.default_matmul_precision("highest"):
        h = rnd(R.f32(params["tok_emb"][tokens]))
        all_sets, all_short = [], []
        n_dense = conf["first_k_dense_replace"]
        # two kinds of attention over one stack of projections, two
        # feed-forwards: a plain loop, each layer reading its own rows
        for i, kind in enumerate(kinds(conf)):
            u = rnd(R.rms_norm(h, R.f32(lp_all["attn_norm_w"][i]), eps))
            h = rnd(h + attention(u, i, kind))
            if i < n_dense:
                u = rnd(R.rms_norm(h, R.f32(lp_all["mlp_norm_w"][i]), eps))
                y = dense_layer(lp_all, u, i, rnd)
            else:
                r = i - n_dense
                y, sets, short = expert_layer(
                    lp_all, conf, h, i, r,
                    None if chosen is None else chosen[r], rnd)
                all_sets.append(sets)
                all_short.append(short)
            h = rnd(h + y)
        h = rnd(R.rms_norm(h, R.f32(params["out_norm_w"]), eps))
        return (h @ R.dequant(params["lm_head"]), jnp.stack(all_sets),
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
