"""Work arithmetic of LFM2-8B-A1B as the benchmark cuts it, from its
configuration file's own keys. A layer is a mixer (a gated short convolution
or attention, by ``layer_types``) and a feed-forward: one dense gated MLP of
``intermediate_size`` in the first ``num_dense_layers`` layers, in the others
a router over ``num_experts`` experts of three matrices hidden x
``moe_intermediate_size`` each, of which a token keeps
``num_experts_per_tok``. The output head is the tied embedding, whole.

A decode step also reads and writes every sequence's convolution inputs
(``conv_L_cache`` - 1 rows of hidden_size float32 a convolution layer):
``weight_bytes_step`` counts them, as granite's file counts its state,
because no other hook of ``work.py`` does. They are 16 KB a layer a sequence."""

from benchmark.work import KV_ITEM, KV_SCALE, WEIGHT_BYTES


def n_conv(conf):
    return sum(t == "conv" for t in conf["layer_types"])


def n_attention(conf):
    return sum(t == "full_attention" for t in conf["layer_types"])


def n_routed(conf):
    return conf["num_hidden_layers"] - conf["num_dense_layers"]


def conv_params(conf):
    """In-projection to three widths of hidden and out-projection: the
    matrices. The convolution's taps are vectors."""
    d = conf["hidden_size"]
    return d * 3 * d + d * d


def attention_params(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kv, hd = conf["num_key_value_heads"], conf["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def dense_params(conf):
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def expert_params(conf):
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def router_params(conf):
    return conf["hidden_size"] * conf["num_experts"]


def conv_state_bytes(conf):
    """One sequence's carried inputs in ONE convolution layer, float32."""
    return 4.0 * (conf["conv_L_cache"] - 1) * conf["hidden_size"]


def conv_state_bytes_step(conf, batch):
    """What a decode step reads and writes of them: every sequence's, once
    each way, in every convolution layer."""
    return batch * n_conv(conf) * 2.0 * conv_state_bytes(conf)


def distinct_experts(conf, batch):
    """Experts of one layer that ``batch`` tokens touch, expected: a token
    keeps k distinct of E, so it misses a given one with probability
    1 - k/E (the selection bias exists to keep the picks that even)."""
    miss = 1.0 - conf["num_experts_per_tok"] / conf["num_experts"]
    return conf["num_experts"] * (1.0 - miss ** batch)


def experts_bytes_step(conf, batch, weights):
    """Bytes of the experts one step over ``batch`` tokens touches, all
    routed layers (the router is not among them)."""
    return (n_routed(conf) * distinct_experts(conf, batch)
            * expert_params(conf) * WEIGHT_BYTES[weights])


def fixed_params(conf):
    """Matrix elements every token is multiplied by: the mixers, the dense
    layers, the routers, the head."""
    return (n_conv(conf) * conv_params(conf)
            + n_attention(conf) * attention_params(conf)
            + conf["num_dense_layers"] * dense_params(conf)
            + n_routed(conf) * router_params(conf)
            + conf["hidden_size"] * conf["vocab_size"])


def layer_matmul_params(conf):
    """Matrix elements on this chip, a layer on average."""
    body = (fixed_params(conf) - conf["hidden_size"] * conf["vocab_size"]
            + n_routed(conf) * conf["num_experts"] * expert_params(conf))
    return body / conf["num_hidden_layers"]


def weight_bytes_step(conf, batch, weights):
    """What one step of ``batch`` tokens must read of the weights (mixers,
    dense layers, routers, each touched expert once, the head), PLUS the
    convolution inputs it reads and writes (the head of this file)."""
    return (fixed_params(conf) * WEIGHT_BYTES[weights]
            + experts_bytes_step(conf, batch, weights)
            + conv_state_bytes_step(conf, batch))


def matmul_flops_per_token(conf):
    """A token is multiplied by the fixed matrices and by the
    ``num_experts_per_tok`` experts it keeps in each routed layer."""
    return 2.0 * (fixed_params(conf) + n_routed(conf)
                  * conf["num_experts_per_tok"] * expert_params(conf))


def kv_bytes_per_token(conf, kv):
    """Keys and values of one position: the attention layers alone."""
    return (2 * n_attention(conf) * conf["num_key_value_heads"]
            * (conf["head_dim"] * KV_ITEM[kv] + KV_SCALE[kv]))


def attn_flops_per_pair(conf):
    """One query against one cached position, the attention layers."""
    return (4 * n_attention(conf) * conf["num_attention_heads"]
            * conf["head_dim"])
