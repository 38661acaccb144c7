"""Work arithmetic of Kimi-K2.7-Code as the benchmark cuts it, from its
configuration file's own keys. Every layer has latent attention (a query
latent of ``q_lora_rank``, ``num_attention_heads`` heads of
``qk_nope_head_dim`` + ``qk_rope_head_dim`` query channels, keys and values
expanded from a latent of ``kv_lora_rank``, values ``v_head_dim`` wide) and NO
indexer; the first ``first_k_dense_replace`` layers a dense gated MLP of
``intermediate_size``, the others a router over ``router_width`` experts of
three matrices hidden x ``moe_intermediate_size`` each, of which this chip
holds ``n_routed_experts`` and a token keeps ``num_experts_per_tok``, plus a
shared expert of ``shared_intermediate_size``. Embedding and head are untied:
a step multiplies by the head's held rows.

The cache holds ONE row a position a layer (``row_bytes``: kept int8 it is
``kv_lora_rank`` codes under one scale and ``kv_row_key_codes`` codes a
channel of the rotated key under another, the file's ``assumed.cache_rows``),
and nothing else. The decode step is priced absorbed: a head's query against a
row is a dot over the whole row and a sum over its latent part
(``attn_flops_per_pair``). Every live position is read: there is no
selection, so ``work.py``'s live-position count is exact here.
``latent_bytes_per_live_position`` and ``latent_flops_per_live_position``
price the decode kernel alone (``latent_attn_roofline``): at 64 heads a row's
648 bytes meet 64 x (576 + 512) x 2 = 139,264 operations, 215 a byte, under
the v5e's ridge (197 TFLOP/s over 819 GB/s = 240) by a tenth, so neither
bound may be left out."""

from benchmark.work import KV_ITEM, KV_SCALE, WEIGHT_BYTES


def n_routed(conf):
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def attention_params(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    rq, c = conf["q_lora_rank"], conf["kv_lora_rank"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    return (d * rq + rq * h * (dn + dr) + d * (c + dr) + c * h * (dn + dv)
            + h * dv * d)


def dense_params(conf):
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def expert_params(conf):
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def shared_params(conf):
    return 3 * conf["hidden_size"] * conf["shared_intermediate_size"]


def router_params(conf):
    return conf["hidden_size"] * conf["router_width"]


def distinct_experts(conf, batch):
    """Held experts of one layer that ``batch`` tokens touch, expected: a
    token keeps k distinct of E, so it misses a given one with probability
    1 - k/E (the selection bias exists to keep the picks that even)."""
    miss = 1.0 - conf["num_experts_per_tok"] / conf["router_width"]
    return conf["n_routed_experts"] * (1.0 - miss ** batch)


def experts_bytes_step(conf, batch, weights):
    """Bytes of the held experts one step over ``batch`` tokens touches, all
    routed layers (the shared expert and the router are not among them)."""
    return (n_routed(conf) * distinct_experts(conf, batch)
            * expert_params(conf) * WEIGHT_BYTES[weights])


def fixed_params(conf):
    """Matrix elements every token is multiplied by: latent attention, the
    dense layer, the shared experts, the routers, the head."""
    return (conf["num_hidden_layers"] * attention_params(conf)
            + conf["first_k_dense_replace"] * dense_params(conf)
            + n_routed(conf) * (shared_params(conf) + router_params(conf))
            + conf["hidden_size"] * conf["vocab_size"])


def layer_matmul_params(conf):
    """Matrix elements on this chip, a layer on average."""
    body = (fixed_params(conf) - conf["hidden_size"] * conf["vocab_size"]
            + n_routed(conf) * conf["n_routed_experts"] * expert_params(conf))
    return body / conf["num_hidden_layers"]


def weight_bytes_step(conf, batch, weights):
    """What one step of ``batch`` tokens must read of the weights: the fixed
    matrices and each touched expert once."""
    return (fixed_params(conf) * WEIGHT_BYTES[weights]
            + experts_bytes_step(conf, batch, weights))


def matmul_flops_per_token(conf):
    """A token is multiplied by the fixed matrices and, of the
    ``num_experts_per_tok`` experts it keeps in each routed layer, by the
    ones held here: k x held / E of them, expected."""
    kept_here = (conf["num_experts_per_tok"] * conf["n_routed_experts"]
                 / conf["router_width"])
    return 2.0 * (fixed_params(conf)
                  + n_routed(conf) * kept_here * expert_params(conf))


def row_bytes(conf, kv):
    """One position's row [latent | rotated key] in ONE layer as the
    algorithm reads it. Kept int8: a code a channel of the latent under one
    scale, ``kv_row_key_codes`` codes a channel of the rotated key under
    another (512 + 2 x 64 + 2 x 4 = 648 bytes); in a wider type its
    channels alone."""
    c, dr = conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    if kv != "int8":
        return (c + dr) * KV_ITEM[kv]
    return c + conf["kv_row_key_codes"] * dr + 2 * KV_SCALE[kv]


def kv_bytes_per_token(conf, kv):
    """The rows of one LIVE position, all layers."""
    return conf["num_hidden_layers"] * row_bytes(conf, kv)


def attn_flops_per_pair(conf):
    """One query against one cached position, absorbed, all layers: a head's
    dot over the row (latent + rotated key) and its sum over the latent."""
    c, dr = conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    return (2 * conf["num_hidden_layers"] * conf["num_attention_heads"]
            * ((c + dr) + c))


def latent_bytes_per_live_position(conf, kv):
    """What the decode kernel has to read for one live position, all layers:
    its row, codes and scales."""
    return kv_bytes_per_token(conf, kv)


def latent_flops_per_live_position(conf):
    """What the decode kernel has to multiply for one live position, all
    layers and heads: ``attn_flops_per_pair``."""
    return attn_flops_per_pair(conf)
