"""Work arithmetic of K-EXAONE-236B-A23B as the benchmark cuts it, from its
configuration file's own keys. Every layer has attention's four matrices (by
``layer_types`` over every earlier position or over the last
``sliding_window``); the first ``first_k_dense_replace`` layers a dense gated
MLP of ``intermediate_size``, the others a router over ``router_width``
experts of three matrices hidden x ``moe_intermediate_size`` each, of which
this chip holds ``num_experts`` and a token keeps ``num_experts_per_tok``,
plus a shared expert of ``shared_intermediate_size``. Embedding and head are
untied: a step multiplies by the head's held rows.

A decode step also reads every sequence's rings, ``sliding_window`` positions
of keys and values a window layer: ``weight_bytes_step`` counts them
(``window_bytes_step``), as granite's file counts its state, because
``work.py``'s hook for keys and values is a count a LIVE position and a ring
does not grow with the context. ``kv_bytes_per_token`` is the full layers'
alone."""

from benchmark.work import KV_ITEM, KV_SCALE, WEIGHT_BYTES


def n_window(conf):
    return sum(t == "sliding_attention" for t in conf["layer_types"])


def n_full(conf):
    return sum(t == "full_attention" for t in conf["layer_types"])


def n_routed(conf):
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def attention_params(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kv, hd = conf["num_key_value_heads"], conf["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def dense_params(conf):
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def expert_params(conf):
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def shared_params(conf):
    return 3 * conf["hidden_size"] * conf["shared_intermediate_size"]


def router_params(conf):
    return conf["hidden_size"] * conf["router_width"]


def position_bytes(conf, kv):
    """Keys and values of one position in ONE layer."""
    return (2 * conf["num_key_value_heads"]
            * (conf["head_dim"] * KV_ITEM[kv] + KV_SCALE[kv]))


def window_bytes_step(conf, batch, kv):
    """The rings a decode step reads: every sequence's, whole, in every
    window layer (the one position it writes is a 128th of that)."""
    return (batch * n_window(conf) * conf["sliding_window"]
            * position_bytes(conf, kv))


def distinct_experts(conf, batch):
    """Held experts of one layer that ``batch`` tokens touch, expected: a
    token keeps k distinct of E, so it misses a given one with probability
    1 - k/E (the selection bias exists to keep the picks that even)."""
    miss = 1.0 - conf["num_experts_per_tok"] / conf["router_width"]
    return conf["num_experts"] * (1.0 - miss ** batch)


def experts_bytes_step(conf, batch, weights):
    """Bytes of the held experts one step over ``batch`` tokens touches, all
    routed layers (the shared expert and the router are not among them)."""
    return (n_routed(conf) * distinct_experts(conf, batch)
            * expert_params(conf) * WEIGHT_BYTES[weights])


def fixed_params(conf):
    """Matrix elements every token is multiplied by: attention, the dense
    layer, the shared experts, the routers, the head."""
    return (conf["num_hidden_layers"] * attention_params(conf)
            + conf["first_k_dense_replace"] * dense_params(conf)
            + n_routed(conf) * (shared_params(conf) + router_params(conf))
            + conf["hidden_size"] * conf["vocab_size"])


def layer_matmul_params(conf):
    """Matrix elements on this chip, a layer on average."""
    body = (fixed_params(conf) - conf["hidden_size"] * conf["vocab_size"]
            + n_routed(conf) * conf["num_experts"] * expert_params(conf))
    return body / conf["num_hidden_layers"]


def weight_bytes_step(conf, batch, weights, kv="int8"):
    """What one step of ``batch`` tokens must read of the weights
    (attention, the dense layer, shared experts, routers, each touched
    expert once, the head), PLUS the rings it reads (the head of this file;
    at the int8 cache the configuration resolves to, as ``work.py``'s hook
    passes no cache type)."""
    return (fixed_params(conf) * WEIGHT_BYTES[weights]
            + experts_bytes_step(conf, batch, weights)
            + window_bytes_step(conf, batch, kv))


def matmul_flops_per_token(conf):
    """A token is multiplied by the fixed matrices and, of the
    ``num_experts_per_tok`` experts it keeps in each routed layer, by the
    ones held here: k x held / E of them, expected."""
    kept_here = (conf["num_experts_per_tok"] * conf["num_experts"]
                 / conf["router_width"])
    return 2.0 * (fixed_params(conf)
                  + n_routed(conf) * kept_here * expert_params(conf))


def kv_bytes_per_token(conf, kv):
    """Keys and values of one LIVE position: the full-attention layers
    alone (a window layer's ring is counted a step, above)."""
    return n_full(conf) * position_bytes(conf, kv)


def attn_flops_per_pair(conf):
    """One query against one cached position, the full-attention layers (a
    window layer's 128 pairs a token are 1% of the matrices' operations and
    are left out)."""
    return (4 * n_full(conf) * conf["num_attention_heads"]
            * conf["head_dim"])
