"""Work arithmetic of granite-4.0-h-small as the benchmark cuts it, from its
configuration file's own keys. A layer is a mixer (Mamba-2 or attention, by
``layer_types``), a router over ``router_width`` experts of which this chip
holds ``num_local_experts``, each three matrices hidden x
``intermediate_size``, and one shared expert of ``shared_intermediate_size``.
The output head is the tied embedding's held rows.

A decode step also reads and writes every sequence's recurrent state: per
Mamba layer H x P x N float32 and K-1 columns of the convolution's inputs.
``weight_bytes_step`` counts it (batch x Mamba layers x 2 x state bytes),
because no other hook of ``work.py`` does: it is what a step moves besides
weights, keys and values and logits, and it grows with the batch."""

from benchmark.work import KV_ITEM, KV_SCALE, WEIGHT_BYTES


def n_mamba(conf):
    return sum(t == "mamba" for t in conf["layer_types"])


def n_attention(conf):
    return sum(t == "attention" for t in conf["layer_types"])


def d_inner(conf):
    return conf["mamba_n_heads"] * conf["mamba_d_head"]


def conv_dim(conf):
    return d_inner(conf) + 2 * conf["mamba_n_groups"] * conf["mamba_d_state"]


def mamba_params(conf):
    """In-projection (z, xBC, dt) and out-projection: the matrices. The
    convolution, dt_bias, A_log, D and the gate's norm are vectors."""
    d = conf["hidden_size"]
    return (d * (d_inner(conf) + conv_dim(conf) + conf["mamba_n_heads"])
            + d_inner(conf) * d)


def attention_params(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kv, hd = conf["num_key_value_heads"], conf["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def expert_params(conf):
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def shared_params(conf):
    return 3 * conf["hidden_size"] * conf["shared_intermediate_size"]


def router_params(conf):
    return conf["hidden_size"] * conf["router_width"]


def state_bytes(conf):
    """One sequence's recurrent state in ONE Mamba layer, float32."""
    return 4.0 * (d_inner(conf) * conf["mamba_d_state"]
                  + (conf["mamba_d_conv"] - 1) * conv_dim(conf))


def ssm_state_bytes_step(conf, batch):
    """The state a decode step reads and writes: every sequence's, once
    each way, in every Mamba layer."""
    return batch * n_mamba(conf) * 2.0 * state_bytes(conf)


def distinct_experts(conf, batch):
    """Held experts that ``batch`` tokens touch, expected: a token keeps k
    distinct of E, so it misses a given one with probability 1 - k/E."""
    miss = 1.0 - conf["num_experts_per_tok"] / conf["router_width"]
    return conf["num_local_experts"] * (1.0 - miss ** batch)


def experts_bytes_step(conf, batch, weights):
    """Bytes of the held experts one step over ``batch`` tokens touches, all
    layers (the shared expert and the router are not among them)."""
    return (conf["num_hidden_layers"] * distinct_experts(conf, batch)
            * expert_params(conf) * WEIGHT_BYTES[weights])


def layer_matmul_params(conf):
    """Matrix elements on this chip, a layer on average."""
    mixers = (n_mamba(conf) * mamba_params(conf)
              + n_attention(conf) * attention_params(conf))
    return (mixers / conf["num_hidden_layers"] + shared_params(conf)
            + router_params(conf)
            + conf["num_local_experts"] * expert_params(conf))


def weight_bytes_step(conf, batch, weights):
    """What one step of ``batch`` tokens must read of the weights (mixers,
    shared expert, router, each touched expert once, the head), PLUS the
    recurrent state it reads and writes (the head of this file)."""
    fixed = (n_mamba(conf) * mamba_params(conf)
             + n_attention(conf) * attention_params(conf)
             + conf["num_hidden_layers"] * (shared_params(conf)
                                            + router_params(conf))
             + conf["hidden_size"] * conf["vocab_size"])
    return (fixed * WEIGHT_BYTES[weights]
            + experts_bytes_step(conf, batch, weights)
            + ssm_state_bytes_step(conf, batch))


def matmul_flops_per_token(conf):
    """A token is multiplied by the experts it keeps, and of those by the
    ones held here: k x held / E of them, expected. The state update and
    its read-out are about 6 operations an element of [H, P, N]."""
    kept_here = (conf["num_experts_per_tok"] * conf["num_local_experts"]
                 / conf["router_width"])
    per_layer = (shared_params(conf) + router_params(conf)
                 + kept_here * expert_params(conf))
    mixers = (n_mamba(conf) * (mamba_params(conf)
                               + 3 * d_inner(conf) * conf["mamba_d_state"])
              + n_attention(conf) * attention_params(conf))
    return 2.0 * (mixers + conf["num_hidden_layers"] * per_layer
                  + conf["hidden_size"] * conf["vocab_size"])


def kv_bytes_per_token(conf, kv):
    """Keys and values of one position: the attention layers alone."""
    return (2 * n_attention(conf) * conf["num_key_value_heads"]
            * (conf["head_dim"] * KV_ITEM[kv] + KV_SCALE[kv]))


def attn_flops_per_pair(conf):
    """One query against one cached position, the attention layers."""
    return (4 * n_attention(conf) * conf["num_attention_heads"]
            * conf["head_dim"])
