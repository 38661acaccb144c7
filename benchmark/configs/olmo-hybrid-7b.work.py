"""Work arithmetic of Olmo-Hybrid-7B as the benchmark cuts it, from its
configuration file's own keys. A layer is a mixer (the gated delta rule or
multi-head attention, by ``layer_types``) and one SwiGLU MLP of
``intermediate_size``; the output head is untied and whole.

A decode step also reads and writes every sequence's recurrent state: per
linear layer H x dk x dv float32 (a matrix a head, corrected by a rank-one
update of itself) and K-1 columns of the convolution's inputs.
``weight_bytes_step`` counts it (batch x linear layers x 2 x state bytes),
because no other hook of ``work.py`` does: it is what a step moves besides
weights, keys and values and logits, and it grows with the batch.
``delta_state_bytes_step`` is that term alone, for the update's roofline."""

from benchmark.work import KV_ITEM, KV_SCALE, WEIGHT_BYTES


def n_linear(conf):
    return sum(t == "linear_attention" for t in conf["layer_types"])


def n_full(conf):
    return sum(t == "full_attention" for t in conf["layer_types"])


def key_dim(conf):
    return conf["linear_num_key_heads"] * conf["linear_key_head_dim"]


def value_dim(conf):
    return conf["linear_num_value_heads"] * conf["linear_value_head_dim"]


def conv_dim(conf):
    """Channels of the causal convolution: q, k and v."""
    return 2 * key_dim(conf) + value_dim(conf)


def linear_params(conf):
    """q, k, v, the output gate, the two per-head gates and the
    out-projection: the matrices. The convolution, dt_bias, A_log and the
    gate's norm are vectors."""
    d = conf["hidden_size"]
    return (d * (conv_dim(conf) + value_dim(conf)
                 + 2 * conf["linear_num_value_heads"])
            + value_dim(conf) * d)


def attention_params(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kv, hd = conf["num_key_value_heads"], conf["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def mlp_params(conf):
    return conf["mlp_matrices"] * conf["hidden_size"] \
        * conf["intermediate_size"]


def state_elements(conf):
    """One sequence's state matrix in ONE linear layer, elements."""
    return (conf["linear_num_value_heads"] * conf["linear_key_head_dim"]
            * conf["linear_value_head_dim"])


def state_bytes(conf):
    """One sequence's recurrent state in ONE linear layer, float32: the
    matrices and the convolution's K-1 carried inputs."""
    return 4.0 * (state_elements(conf)
                  + (conf["linear_conv_kernel_dim"] - 1) * conv_dim(conf))


def delta_state_bytes_step(conf, batch):
    """The state a decode step reads and writes: every sequence's, once
    each way, in every linear layer."""
    return batch * n_linear(conf) * 2.0 * state_bytes(conf)


def layer_matmul_params(conf):
    """Matrix elements, a layer on average."""
    mixers = (n_linear(conf) * linear_params(conf)
              + n_full(conf) * attention_params(conf))
    return mixers / conf["num_hidden_layers"] + mlp_params(conf)


def n_params(conf):
    """Every matrix and both vocabulary tables (the program's count)."""
    return (conf["num_hidden_layers"] * layer_matmul_params(conf)
            + 2 * conf["hidden_size"] * conf["vocab_size"])


def weight_bytes_step(conf, batch, weights):
    """What one step of ``batch`` tokens must read of the weights (every
    matrix and the head; the embedding is a row lookup), PLUS the recurrent
    state it reads and writes (the head of this file)."""
    fixed = (conf["num_hidden_layers"] * layer_matmul_params(conf)
             + conf["hidden_size"] * conf["vocab_size"])
    return (fixed * WEIGHT_BYTES[weights]
            + delta_state_bytes_step(conf, batch))


def matmul_flops_per_token(conf):
    """A token is multiplied by every matrix once. The state's decay, its
    rank-one correction and its read-out are about 7 operations an element
    of [H, dk, dv] (3.5 multiply-adds, counted as the matrices' are)."""
    mixers = (n_linear(conf) * (linear_params(conf)
                                + 3.5 * state_elements(conf))
              + n_full(conf) * attention_params(conf))
    return 2.0 * (mixers + conf["num_hidden_layers"] * mlp_params(conf)
                  + conf["hidden_size"] * conf["vocab_size"])


def kv_bytes_per_token(conf, kv):
    """Keys and values of one position: the full-attention layers alone."""
    return (2 * n_full(conf) * conf["num_key_value_heads"]
            * (conf["head_dim"] * KV_ITEM[kv] + KV_SCALE[kv]))


def attn_flops_per_pair(conf):
    """One query against one cached position, the full-attention layers."""
    return (4 * n_full(conf) * conf["num_attention_heads"]
            * conf["head_dim"])
