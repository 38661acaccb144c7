"""Plain reference of the fixture's routed model: a llama block (pre-norm
RMSNorm, grouped-query attention, full rotary embedding, half-split pairing)
whose MLP is a router over gated-SiLU experts. A token keeps the
``num_experts_per_tok`` experts of largest router score; its gates are the
softmax over the kept scores (``norm_topk_prob``); one shared gated-SiLU
expert, where the tree has one, runs for every token under a sigmoid gate.
Float32 at the highest matmul precision; ``params`` is the served weight tree
(stacked [L, ...] leaves, input-major matrices), quantized leaves dequantized
a layer at a time.

The model makes a choice, so beside ``forward`` the module has
``forward_chosen`` (the contract at the head of ``benchmark/server_child.py``)
and, for the tests' control, ``forward_rounded``: the same arithmetic with
every activation rounded through a lower precision, which hands out the sets
it chose itself."""

import jax
import jax.numpy as jnp

from benchmark import refmath as R

SITE = "moe.route"      # the program's name of the one choice site


def run(params, conf, tokens, chosen=None, rnd=None):
    """tokens [T] int32 -> (logits [T, V] float32, sets [L, T, k] int32
    ascending, shortfall [T]). ``chosen`` [L, T, k] takes the place of the
    model's own top-k where it is given; ``rnd`` rounds every activation."""
    H, KvH = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, k = conf["head_dim"], conf["num_experts_per_tok"]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    rnd = rnd or (lambda x: x)
    T = tokens.shape[0]
    pos = jnp.arange(T)
    rows = jnp.arange(T)[:, None]

    def gated(h, w_gate, w_up, w_down):
        return rnd(rnd(jax.nn.silu(rnd(h @ w_gate)) * rnd(h @ w_up)) @ w_down)

    def layer(x, xs):
        lp, given = xs
        h = rnd(R.rms_norm(x, R.f32(lp["attn_norm_w"]), eps))
        q = rnd(h @ R.dequant(lp["wq"])).reshape(T, H, hd)
        kk = rnd(h @ R.dequant(lp["wk"])).reshape(T, KvH, hd)
        v = rnd(h @ R.dequant(lp["wv"])).reshape(T, KvH, hd)
        q = rnd(R.rotate_half(q, pos, hd, theta))
        kk = rnd(R.rotate_half(kk, pos, hd, theta))
        a = rnd(R.causal_attention(q, kk, v)).reshape(T, H * hd)
        x = rnd(x + rnd(a @ R.dequant(lp["wo"])))
        h = rnd(R.rms_norm(x, R.f32(lp["mlp_norm_w"]), eps))

        score = h @ R.f32(lp["router"])                     # [T, E] float32
        own_w, own = jax.lax.top_k(score, k)
        sets = own if chosen is None else given
        kept = jnp.take_along_axis(score, sets, axis=1)     # [T, k]
        # how far the weakest kept member lies below the model's own k-th
        # best, as a share of the position's largest |score|
        short = (jnp.maximum(own_w[:, -1] - kept.min(axis=1), 0.0)
                 / jnp.abs(score).max(axis=1))
        if conf["norm_topk_prob"]:
            w = jax.nn.softmax(kept, axis=-1)
        else:
            w = jnp.take_along_axis(jax.nn.softmax(score, -1), sets, axis=1)
        gates = jnp.zeros_like(score).at[rows, sets].set(w)

        def expert(acc, ew):
            w_gate, w_up, w_down, g = ew
            return acc + g[:, None] * gated(h, R.f32(w_gate), R.f32(w_up),
                                            R.f32(w_down)), None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                            (lp["we_gate"], lp["we_up"], lp["we_down"],
                             gates.T))
        if "we_sh_gate" in lp:
            sh = gated(h, R.f32(lp["we_sh_gate"]), R.f32(lp["we_sh_up"]),
                       R.f32(lp["we_sh_down"]))
            y = y + jax.nn.sigmoid(h @ R.f32(lp["sh_gate"])) * sh
        return rnd(x + rnd(y)), (jnp.sort(sets, axis=1), short)

    with jax.default_matmul_precision("highest"):
        x = rnd(R.f32(params["tok_emb"])[tokens])
        L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        # a scan hands each layer its slice of the stacked leaves in place;
        # without given sets it carries zeros that no layer reads
        given = (jnp.zeros((L, T, k), jnp.int32) if chosen is None
                 else chosen)
        x, (sets, short) = jax.lax.scan(layer, x, (params["layers"], given))
        x = rnd(R.rms_norm(x, R.f32(params["out_norm_w"]), eps))
        logits = x @ R.dequant(params["lm_head"])
        return logits, sets, short.max(axis=0)


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
