"""Plain reference of SmallThinker-21BA3B-Instruct (smallthinker) as the
benchmark cuts it: the first ``num_hidden_layers`` layers of the published
stack at every published width, every expert, the whole untied vocabulary.
Float32 at the highest matmul precision, one sequence, no cache, no ring, no
batching, no import from the program; ``params`` is the served weight tree
(stacked leaves, input-major matrices), and every size comes from ``conf``.

With x the residual stream that enters layer l at position t:

    r   = x W_r                      router logits [E], float32, from the
                                     layer's INPUT: before the input norm,
                                     before attention
    h   = RMSNorm(x; g1);  q, k, v = h W_q, h W_k, h W_v     (no bias, no
                                     q/k norm)
    rope_layout[l] = 1: rotary over the whole head at ``rope_theta``,
                        half-split pairing, on q and k;  0: no position
    sliding_window_layout[l] = 1: key s visible iff t - W < s <= t
                        (W = ``sliding_window_size``);  0: every s <= t
    x'  = x + softmax(q k^T / sqrt(head_dim)) v W_o
    u   = RMSNorm(x'; g2)
    S   = the ``moe_num_active_primary_experts`` largest of r
    g   = softmax(r[S])              (``moe_primary_router_apply_softmax``,
                                     ``norm_topk_prob``: over the kept alone)
    x'' = x' + sum_{e in S} g_e W_down,e( relu(u W_gate,e) * (u W_up,e) )
    logits = RMSNorm(x_L; g) W_head  (untied: its own matrix)

Departures from the published description: the cut (``reduced`` in the
configuration's file: depth alone); what the published config has no key for
(``assumed`` there: the pre-norm block, the router reading the un-normed
layer input, the ReLU gate, half-split rotary, no q/k norm, no bias); the
catalog's "primary+secondary experts": this checkpoint's config has the
primary keys alone, so nothing secondary is built; the seeded weights.

The model makes a choice (the router's top-k), so beside ``forward`` the module
has ``forward_chosen`` (the contract at the head of
``benchmark/server_child.py``; its sets are [layers, T, k] in layer order, and
the score a shortfall is measured on is the logit r, which the selection
used) and, for the tests' control, ``forward_rounded``. ``run`` also takes the
two things the tests' other controls change (which stream the router reads,
the experts' gate function), so that a test can tell the placements apart.
It works a layer at a time and an expert at a time, so that its float32
copies stay small beside 7.9 GB of served weights."""

import jax
import jax.numpy as jnp

from benchmark import refmath as R

SITE = "moe.route"      # the program's name of the one choice site


def leaf(lp_all, name, r):
    """Row ``r`` of a stacked matrix as float32, dequantized where the
    program serves it quantized."""
    return R.dequant(jax.tree_util.tree_map(lambda a: a[r], lp_all[name]))


def route(lp_all, conf, x, i, given=None):
    """The router of layer ``i`` on the stream x [T, D] it reads: -> (gates
    [T, E] float32, zero for experts not kept; sets [T, k] ascending;
    shortfall [T]). ``given`` [T, k] takes the place of the router's own
    top-k."""
    k = conf["moe_num_active_primary_experts"]
    rows = jnp.arange(x.shape[0])[:, None]
    logit = x @ R.f32(lp_all["router"][i])                  # [T, E] float32
    own_w, own = jax.lax.top_k(logit, k)
    sets = own if given is None else given
    # how far the weakest kept member lies below the model's own k-th best
    # logit, as a share of the position's largest
    short = (jnp.maximum(
        own_w[:, -1] - jnp.take_along_axis(logit, sets, axis=1).min(axis=1),
        0.0) / jnp.abs(logit).max(axis=1))
    kept = jnp.take_along_axis(logit, sets, axis=1)
    if conf["moe_primary_router_apply_softmax"] and conf["norm_topk_prob"]:
        kept = jax.nn.softmax(kept, axis=1)
    else:
        # the full softmax's probabilities, as they are or over their sum
        kept = jnp.take_along_axis(jax.nn.softmax(logit, axis=1), sets, axis=1)
        if conf["norm_topk_prob"]:
            kept = kept / kept.sum(axis=1, keepdims=True)
    gates = jnp.zeros_like(logit).at[rows, sets].set(kept)
    return gates, jnp.sort(sets, axis=1), short


def experts(lp_all, u, gates, i, rnd, gate_act):
    """sum_e gates[:, e] * W_down,e(act(u W_gate,e) * (u W_up,e)) of layer
    ``i``, an expert at a time."""
    def one(name, e):
        # expert e of layer i, read where it lies: a scan over a slice of the
        # stack would copy the whole layer's experts first
        w = lp_all[name]
        return R.f32(jax.lax.dynamic_slice(
            w, (i, e, 0, 0), (1, 1) + w.shape[2:])[0, 0])

    def expert(acc, eg):
        e, g = eg
        mid = rnd(gate_act(rnd(u @ one("we_gate", e)))
                  * rnd(u @ one("we_up", e)))
        return acc + g[:, None] * rnd(mid @ one("we_down", e)), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                        (jnp.arange(gates.shape[1]), gates.T))
    return rnd(y)


def run(params, conf, tokens, chosen=None, rnd=None, router_stream="block",
        gate_act=jax.nn.relu):
    """tokens [T] int32 -> (logits [T, V] float32, sets [L, T, k] int32
    ascending, shortfall [T]). ``chosen`` [L, T, k] takes the place of the
    model's own top-k where it is given; ``rnd`` rounds every activation.
    ``router_stream`` "block" is the model (the router reads the layer's
    input); "mlp" (the normed stream after attention) and another
    ``gate_act`` are the tests' controls."""
    nH, KvH, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    eps, W = conf["rms_norm_eps"], conf["sliding_window_size"]
    rnd = rnd or (lambda x: x)
    T = tokens.shape[0]
    positions = jnp.arange(T)
    lp_all = params["layers"]

    def attention(u, i, windowed, rotary):
        q = rnd(u @ leaf(lp_all, "wq", i)).reshape(T, nH, hd)
        k = rnd(u @ leaf(lp_all, "wk", i)).reshape(T, KvH, hd)
        v = rnd(u @ leaf(lp_all, "wv", i)).reshape(T, KvH, hd)
        if rotary:
            q = R.rotate_half(q, positions, hd, conf["rope_theta"])
            k = R.rotate_half(k, positions, hd, conf["rope_theta"])
        a = R.causal_attention(rnd(q), rnd(k), v,
                               window=W if windowed else 0)
        return rnd(rnd(a).reshape(T, nH * hd) @ leaf(lp_all, "wo", i))

    with jax.default_matmul_precision("highest"):
        h = rnd(R.f32(params["tok_emb"][tokens]))
        all_sets, all_short = [], []
        for i in range(conf["num_hidden_layers"]):
            given = None if chosen is None else chosen[i]
            if router_stream == "block":
                gates, sets, short = route(lp_all, conf, h, i, given)
            u = rnd(R.rms_norm(h, R.f32(lp_all["attn_norm_w"][i]), eps))
            h = rnd(h + attention(u, i, conf["sliding_window_layout"][i],
                                  conf["rope_layout"][i]))
            u = rnd(R.rms_norm(h, R.f32(lp_all["mlp_norm_w"][i]), eps))
            if router_stream != "block":
                gates, sets, short = route(lp_all, conf, u, i, given)
            h = rnd(h + experts(lp_all, u, gates, i, rnd, gate_act))
            all_sets.append(sets)
            all_short.append(short)
        h = rnd(R.rms_norm(h, R.f32(params["out_norm_w"]), eps))
        return (h @ R.dequant(params["lm_head"]), jnp.stack(all_sets),
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
