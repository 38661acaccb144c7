"""Plain reference of Kimi-K2.7-Code (kimi_k2) as the benchmark cuts it: the
first ``num_hidden_layers`` layers of the published stack at every published
width, of each routed layer the ``n_routed_experts`` experts this chip holds
from ``expert_first`` on (the router keeps its ``router_width`` outputs), and
the held rows of the untied embedding and head. Float32 at the highest matmul
precision, one sequence, no cache, keys and values EXPANDED a head (never the
absorbed form the program decodes in), no batching, no import from the
program; ``params`` is the served weight tree (stacked leaves, input-major
matrices), and every size comes from ``conf``.

    h = E[tokens]
    each layer:  h = h + attention(RMSNorm(h));  h = h + ffn(RMSNorm(h))
    logits = RMSNorm(h) @ W_head                    (untied: its own matrix)

* Latent attention, every layer, with u_t the normed input of position t:
  cQ_t = RMSNorm(u_t W_qa) [``q_lora_rank``]; q_t = cQ_t W_qb as
  ``num_attention_heads`` heads of [q_nope ``qk_nope_head_dim`` | q_rope
  ``qk_rope_head_dim``]; [cKV_t | kR_t] = u_t W_kva [``kv_lora_rank`` +
  ``qk_rope_head_dim``], cKV_t = RMSNorm(cKV_t); rotary on q_rope and on the
  ONE kR_t all heads share, channels (2i, 2i + 1) a pair. Head i: k_nope_s =
  W_uk,i cKV_s, v_s = W_uv,i^T cKV_s [``v_head_dim``] (the served leaves
  ``w_uk`` [H, dn, C] and ``w_uv`` [H, C, dv] are the published kv_b_proj a
  head at a time); a_ts = softmax over ALL s <= t of (q_nope_t . k_nope_s +
  q_rope_t . kR_s) x scale; out = [sum_s a_ts v_s] W_o. No bias, no indexer.
* YaRN (``rope_scaling``: ``factor``, ``original_max_position_embeddings``,
  ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``; ``yarn``
  below, by its own arithmetic): with d = ``qk_rope_head_dim`` and f_i =
  ``rope_theta``^(-2i / d), corr(n) = d ln(original / (2 pi n)) / (2 ln
  theta), low = max(floor(corr(beta_fast)), 0), high = min(ceil(corr(
  beta_slow)), d - 1), ramp_i = clip((i - low) / (high - low), 0, 1), the
  pair i turns by positions x (f_i / factor x ramp_i + f_i x (1 - ramp_i)).
  With m(s) = 0.1 s ln(factor) + 1 (the DeepSeek-V3 convention): cos and sin
  are multiplied by m(mscale) / m(mscale_all_dim), the scores by (dn +
  dr)^-1/2 x m(mscale_all_dim)^2.
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
there); the seeded weights. The family's vision tower is not held: the
catalog row's ``config`` has no key of it and no logit of a text prompt reads
it.

The model makes ONE kind of choice, the router's, so beside ``forward`` the
module has ``forward_chosen`` (the contract at the head of
``benchmark/server_child.py``): ``chosen`` may carry the router's sets under
``moe.route`` ([routed layers, T, k]); absent, the reference's own top-k
stands. ``forward_rounded`` is the tests' and the checks' control. It works a
layer at a time, an expert at a time, the dense width a block at a time and
the queries a block at a time, so that its float32 copies stay small beside 11
GB of served weights."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refmath as R

SITE = "moe.route"      # the program's name of the router's choice site
DENSE_BLOCKS = 8        # the dense feed-forward, this many blocks of its width
QUERY_BLOCK = 256       # queries a pass of attention


def leaf(lp_all, name, r):
    """Row ``r`` of a stacked matrix as float32, dequantized where the
    program serves it quantized."""
    return R.dequant(jax.tree_util.tree_map(lambda a: a[r], lp_all[name]))


def gated(u, w_gate, w_up, w_down, rnd):
    return rnd(rnd(jax.nn.silu(rnd(u @ w_gate)) * rnd(u @ w_up)) @ w_down)


def dense_layer(lp_all, u, r, rnd):
    """The dense gated MLP of layer ``r`` on normed hidden states u [T, D],
    a block of its width at a time."""
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
    first, held = conf["expert_first"], conf["n_routed_experts"]
    u = rnd(R.rms_norm(h, R.f32(lp_all["mlp_norm_w"][i]),
                       conf["rms_norm_eps"]))
    gates, sets, short = route(lp_all, conf, u, r, given)
    gates = gates[:, first:first + held]        # this chip's experts only

    def one(name, e):
        w = lp_all[name]
        return R.f32(jax.lax.dynamic_slice(
            w, (r, e, 0, 0), (1, 1) + w.shape[2:])[0, 0])

    def expert(acc, eg):
        e, g = eg
        return acc + g[:, None] * gated(u, one("we_gate", e), one("we_up", e),
                                        one("we_down", e), rnd), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                        (jnp.arange(held), gates.T))
    y = y + gated(u, R.f32(lp_all["we_sh_gate"][r]),
                  R.f32(lp_all["we_sh_up"][r]),
                  R.f32(lp_all["we_sh_down"][r]), rnd)
    return rnd(y), sets, short


def yarn(conf):
    """(inv_freq [dr / 2] float32, the factor on cos and sin, the factor on
    the scores) of the configuration's ``rope_scaling``, written out; plain
    rotary at ``rope_theta`` (1, 1) where the file has none."""
    d, theta = conf["qk_rope_head_dim"], float(conf["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    rs = conf.get("rope_scaling")
    if not rs:
        return f.astype(np.float32), 1.0, 1.0
    assert rs["type"] == "yarn", rs
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def corr(n):
        return d * math.log(orig / (2.0 * math.pi * n)) / (
            2.0 * math.log(theta))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    if low == high:
        high = low + 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    inv = f / factor * ramp + f * (1.0 - ramp)

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1.0 else 1.0
    return (inv.astype(np.float32), m(rs["mscale"]) / m(rs["mscale_all_dim"]),
            m(rs["mscale_all_dim"]) ** 2)


def rotate_pairs(x, positions, inv_freq, magnitude: float):
    """Rotary embedding over all channels of x [T, ..., d], channels (2i, 2i
    + 1) a pair turned by positions x inv_freq[i], cos and sin times
    ``magnitude``; the pairs stay where they are."""
    d = 2 * inv_freq.shape[0]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * magnitude, jnp.sin(ang) * magnitude
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape[:-1] + (d,))


def by_query_blocks(fn, per_query):
    """``fn`` over blocks of QUERY_BLOCK queries (axis 0 of every array of
    ``per_query``, padded with copies of the last query), its results joined
    along axis 0 and cut back."""
    T = per_query[0].shape[0]
    if T <= QUERY_BLOCK:
        return fn(*per_query)
    pad = -T % QUERY_BLOCK

    def blocks(a):
        a = jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)]) if pad \
            else a
        return a.reshape((a.shape[0] // QUERY_BLOCK, QUERY_BLOCK)
                         + a.shape[1:])
    out = jax.lax.map(lambda xs: fn(*xs), tuple(blocks(a) for a in per_query))
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:])[:T], out)


def attention(lp_all, conf, u, r, rnd=None):
    """Latent attention of layer ``r`` on normed hidden states u [T, D], over
    every earlier position. -> out [T, D]."""
    rnd = rnd or (lambda x: x)
    T = u.shape[0]
    H, C = conf["num_attention_heads"], conf["kv_lora_rank"]
    dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    eps = conf["rms_norm_eps"]
    inv_freq, magnitude, soft = yarn(conf)
    pos = jnp.arange(T)

    cq = rnd(R.rms_norm(rnd(u @ leaf(lp_all, "wq_a", r)),
                        R.f32(lp_all["q_a_norm_w"][r]), eps))
    q = rnd(cq @ leaf(lp_all, "wq_b", r)).reshape(T, H, dn + dr)
    q_nope = q[..., :dn]
    q_rope = rnd(rotate_pairs(q[..., dn:], pos, inv_freq, magnitude))
    kv = rnd(u @ leaf(lp_all, "wkv_a", r))
    ckv = rnd(R.rms_norm(kv[:, :C], R.f32(lp_all["kv_a_norm_w"][r]), eps))
    kr = rnd(rotate_pairs(kv[:, C:], pos, inv_freq, magnitude))
    k_nope = rnd(jnp.einsum("sc,hnc->shn", ckv, R.f32(lp_all["w_uk"][r])))
    v = rnd(jnp.einsum("sc,hcv->shv", ckv, R.f32(lp_all["w_uv"][r])))
    s_pos = jnp.arange(T)[None, :]
    scale = soft / math.sqrt(dn + dr)

    def block(q_nope, q_rope, t):
        s = (jnp.einsum("thn,shn->hts", q_nope, k_nope)
             + jnp.einsum("thr,sr->hts", q_rope, kr)) * scale
        s = jnp.where((s_pos <= t[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hts,shv->thv", jax.nn.softmax(s, axis=-1), v)

    a = by_query_blocks(block, (q_nope, q_rope, pos))
    return rnd(rnd(a).reshape(T, -1) @ leaf(lp_all, "wo", r))


def run(params, conf, tokens, chosen=None, rnd=None):
    """tokens [T] int32 -> (logits [T, V] float32, {site: sets}, shortfall
    [T]). ``chosen`` maps a site to the sets that take the place of the
    model's own there; ``rnd`` rounds every activation."""
    chosen = chosen or {}
    eps = conf["rms_norm_eps"]
    rnd = rnd or (lambda x: x)
    lp_all = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = rnd(R.f32(params["tok_emb"][tokens]))
        routes, shorts = [], []
        n_dense = conf["first_k_dense_replace"]
        for i in range(conf["num_hidden_layers"]):
            u = rnd(R.rms_norm(h, R.f32(lp_all["attn_norm_w"][i]), eps))
            h = rnd(h + attention(lp_all, conf, u, i, rnd))
            if i < n_dense:
                u = rnd(R.rms_norm(h, R.f32(lp_all["mlp_norm_w"][i]), eps))
                y = dense_layer(lp_all, u, i, rnd)
            else:
                r = i - n_dense
                y, sets, short = expert_layer(
                    lp_all, conf, h, i, r,
                    chosen[SITE][r] if SITE in chosen else None, rnd)
                routes.append(sets)
                shorts.append(short)
            h = rnd(h + y)
        h = rnd(R.rms_norm(h, R.f32(params["out_norm_w"]), eps))
        return (h @ R.dequant(params["lm_head"]), {SITE: jnp.stack(routes)},
                jnp.stack(shorts).max(axis=0))


def forward(params, conf, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32."""
    return run(params, conf, tokens)[0]


def forward_chosen(params, conf, tokens, chosen):
    """-> (logits [T, V], shortfall [T]) with ``chosen``'s sets in place of
    the model's own top-k at the sites it names."""
    logits, _sets, short = run(
        params, conf, tokens,
        {site: jnp.asarray(sets, jnp.int32) for site, sets in chosen.items()})
    return logits, short


def forward_sets(params, conf, tokens):
    """-> (logits [T, V], {site: the reference's own sets})."""
    logits, sets, _ = run(params, conf, tokens)
    return logits, sets


def forward_rounded(params, conf, tokens, dtype=jnp.bfloat16):
    """The control: every activation rounded through ``dtype``.
    -> (logits [T, V], {site: its own sets})."""
    logits, sets, _ = run(params, conf, tokens,
                          rnd=lambda x: x.astype(dtype).astype(jnp.float32))
    return logits, sets
