"""Work arithmetic of SmallThinker-21BA3B-Instruct as the benchmark cuts it,
from its configuration file's own keys. Every layer has attention's four
matrices (by ``sliding_window_layout`` over every earlier position or over
the last ``sliding_window_size``), a router over ``moe_num_primary_experts``
experts of three matrices hidden x ``moe_ffn_hidden_size`` each, all held
here, of which a token keeps ``moe_num_active_primary_experts``; no dense
layer, no shared expert. Embedding and head are untied: a step multiplies by
the head.

**The rings are priced by live position, not by ring.** A window layer's ring
is written a row a slot and read as deep as the live contexts reach
(``models/decoder._ring_attend``), so what a step has to read of it is every
live position's keys and values once, as of a full layer's row:
``ring_bytes_per_live_position`` (the window layers' positions bytes, exact
while no served context passes ``sliding_window_size``: past it a slot's live
positions in a ring stop at the window). This file therefore defines NO
``window_bytes_step``: that hook counts whole rings by batch, which a
depth-bounded read beats, and ``window_attn_roofline`` would read over 100%.
For the same reason ``weight_bytes_step`` adds no ring bytes (K-EXAONE's
does): ``work.py``'s hook for keys and values is ``kv_bytes_per_token``, which
stays the two FULL layers' alone because their time is ``attn.core``'s and
``paged_attn_roofline`` divides by that; so ``decode_step_roofline`` reads low
here by the rings' live bytes (6 x 1,056 B a live position: about 0.57 GB of
a 7.8 GB step at 90,000 live positions, 7%)."""

from benchmark.work import KV_ITEM, KV_SCALE, WEIGHT_BYTES


def n_window(conf):
    return sum(1 for w in conf["sliding_window_layout"] if w)


def n_full(conf):
    return conf["num_hidden_layers"] - n_window(conf)


def attention_params(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kv, hd = conf["num_key_value_heads"], conf["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def expert_params(conf):
    return 3 * conf["hidden_size"] * conf["moe_ffn_hidden_size"]


def router_params(conf):
    return conf["hidden_size"] * conf["moe_num_primary_experts"]


def position_bytes(conf, kv):
    """Keys and values of one position in ONE layer."""
    return (2 * conf["num_key_value_heads"]
            * (conf["head_dim"] * KV_ITEM[kv] + KV_SCALE[kv]))


def ring_bytes_per_live_position(conf, kv):
    """Keys and values of one LIVE position in the window layers' rings (the
    head of this file): what ``ring_attn_roofline`` prices a step by."""
    return n_window(conf) * position_bytes(conf, kv)


def distinct_experts(conf, batch):
    """Experts of one layer that ``batch`` tokens touch, expected: a token
    keeps k distinct of E, so it misses a given one with probability
    1 - k/E."""
    experts = conf["moe_num_primary_experts"]
    miss = 1.0 - conf["moe_num_active_primary_experts"] / experts
    return experts * (1.0 - miss ** batch)


def experts_bytes_step(conf, batch, weights):
    """Bytes of the experts one step over ``batch`` tokens touches, all
    layers (the routers are not among them)."""
    return (conf["num_hidden_layers"] * distinct_experts(conf, batch)
            * expert_params(conf) * WEIGHT_BYTES[weights])


def fixed_params(conf):
    """Matrix elements every token is multiplied by: attention, the routers,
    the head."""
    return (conf["num_hidden_layers"]
            * (attention_params(conf) + router_params(conf))
            + conf["hidden_size"] * conf["vocab_size"])


def layer_matmul_params(conf):
    """Matrix elements of a layer: attention, the router, every expert."""
    return (attention_params(conf) + router_params(conf)
            + conf["moe_num_primary_experts"] * expert_params(conf))


def weight_bytes_step(conf, batch, weights):
    """What one step of ``batch`` tokens must read of the weights
    (attention, routers, each touched expert once, the head). No ring bytes:
    the head of this file."""
    return (fixed_params(conf) * WEIGHT_BYTES[weights]
            + experts_bytes_step(conf, batch, weights))


def matmul_flops_per_token(conf):
    """A token is multiplied by the fixed matrices and by the
    ``moe_num_active_primary_experts`` experts it keeps in each layer."""
    return 2.0 * (fixed_params(conf)
                  + conf["num_hidden_layers"]
                  * conf["moe_num_active_primary_experts"]
                  * expert_params(conf))


def kv_bytes_per_token(conf, kv):
    """Keys and values of one LIVE position: the full-attention layers
    alone (their time is ``attn.core``'s; the rings' live bytes are
    ``ring_bytes_per_live_position``)."""
    return n_full(conf) * position_bytes(conf, kv)


def attn_flops_per_pair(conf):
    """One query against one cached position, the full-attention layers (the
    window layers' pairs run under ``attn.window``; all eight layers' pairs
    are 0.2% of the matrices' operations at the cell's contexts)."""
    return (4 * n_full(conf) * conf["num_attention_heads"]
            * conf["head_dim"])
