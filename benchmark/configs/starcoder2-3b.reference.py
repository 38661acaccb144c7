"""Plain reference of StarCoder2-3B's forward pass (Starcoder2ForCausalLM as
published: pre-norm LayerNorm blocks with biases, grouped-query attention (24
query heads over 2 key/value heads) with full rotary embedding and a sliding
window, a plain tanh-GELU MLP with biases, the output head tied to the
embedding and without a bias), in float32 at the highest matmul precision.
``params`` is the served weight tree (stacked [L, ...] leaves, input-major
matrices: y = x @ W + b); quantized leaves are dequantized one layer at a
time."""

import jax
import jax.numpy as jnp

from benchmark import refmath as R


def forward(params, conf, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32."""
    H, KvH = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    eps, theta = conf["norm_epsilon"], conf["rope_theta"]
    window = int(conf.get("sliding_window") or 0)
    T = tokens.shape[0]
    pos = jnp.arange(T)

    def layer(x, lp):
        h = R.layer_norm(x, R.f32(lp["attn_norm_w"]), R.f32(lp["attn_norm_b"]),
                         eps)
        q = (h @ R.dequant(lp["wq"]) + R.f32(lp["bq"])).reshape(T, H, hd)
        k = (h @ R.dequant(lp["wk"]) + R.f32(lp["bk"])).reshape(T, KvH, hd)
        v = (h @ R.dequant(lp["wv"]) + R.f32(lp["bv"])).reshape(T, KvH, hd)
        q = R.rotate_half(q, pos, hd, theta)
        k = R.rotate_half(k, pos, hd, theta)
        a = R.causal_attention(q, k, v, window).reshape(T, H * hd)
        x = x + a @ R.dequant(lp["wo"]) + R.f32(lp["bo"])
        h = R.layer_norm(x, R.f32(lp["mlp_norm_w"]), R.f32(lp["mlp_norm_b"]),
                         eps)
        u = jax.nn.gelu(h @ R.dequant(lp["w_up"]) + R.f32(lp["b_up"]),
                        approximate=True)
        x = x + u @ R.dequant(lp["w_down"]) + R.f32(lp["b_down"])
        return x, None

    with jax.default_matmul_precision("highest"):
        emb = R.f32(params["tok_emb"])
        x = emb[tokens]
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = R.layer_norm(x, R.f32(params["out_norm_w"]),
                         R.f32(params["out_norm_b"]), eps)
        return x @ emb.T
