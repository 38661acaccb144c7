"""Plain reference of phi-2's forward pass (PhiForCausalLM as published: one
LayerNorm a block feeding attention and MLP in parallel, multi-head attention
with rotary embedding over the first 40% of each head, biases on every
projection, tanh-approximated GELU, a biased output head), in float32 at the
highest matmul precision. ``params`` is the served weight tree (stacked
[L, ...] leaves, input-major matrices: y = x @ W + b); quantized leaves are
dequantized one layer at a time."""

import jax
import jax.numpy as jnp

from benchmark import refmath as R


def forward(params, conf, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32."""
    H, KvH = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    eps, theta = conf["layer_norm_eps"], conf["rope_theta"]
    rot = int(conf["partial_rotary_factor"] * hd)
    T = tokens.shape[0]
    pos = jnp.arange(T)

    def layer(x, lp):
        h = R.layer_norm(x, R.f32(lp["attn_norm_w"]), R.f32(lp["attn_norm_b"]),
                         eps)
        q = (h @ R.dequant(lp["wq"]) + R.f32(lp["bq"])).reshape(T, H, hd)
        k = (h @ R.dequant(lp["wk"]) + R.f32(lp["bk"])).reshape(T, KvH, hd)
        v = (h @ R.dequant(lp["wv"]) + R.f32(lp["bv"])).reshape(T, KvH, hd)
        q = R.rotate_half(q, pos, rot, theta)
        k = R.rotate_half(k, pos, rot, theta)
        a = R.causal_attention(q, k, v).reshape(T, H * hd)
        a = a @ R.dequant(lp["wo"]) + R.f32(lp["bo"])
        u = jax.nn.gelu(h @ R.dequant(lp["w_up"]) + R.f32(lp["b_up"]),
                        approximate=True)
        m = u @ R.dequant(lp["w_down"]) + R.f32(lp["b_down"])
        return x + a + m, None

    with jax.default_matmul_precision("highest"):
        x = R.f32(params["tok_emb"][tokens])
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = R.layer_norm(x, R.f32(params["out_norm_w"]),
                         R.f32(params["out_norm_b"]), eps)
        return x @ R.dequant(params["lm_head"]) + R.f32(params["lm_head_b"])
