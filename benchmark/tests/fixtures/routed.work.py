"""Work arithmetic of a routed model, from its configuration file's own keys.
Experts: ``n_routed_experts`` a layer of which this chip holds
``experts_held`` (all, where the key is absent), ``num_experts_per_tok`` kept a
token, each three matrices hidden x ``moe_intermediate_size``; one shared
expert of ``shared_intermediate_size`` (none where 0 or absent) with its
hidden x 1 gate; a router hidden x experts. Attention: grouped-query keys and
values, so the cache and a query's work against it are ``work.py``'s own and
this file defines neither."""

from benchmark.work import WEIGHT_BYTES


def attention_params(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kv, hd = conf["num_key_value_heads"], conf["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def expert_params(conf):
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def shared_params(conf):
    f = conf.get("shared_intermediate_size", 0)
    return (3 * conf["hidden_size"] * f + conf["hidden_size"]) if f else 0


def router_params(conf):
    return conf["hidden_size"] * conf["n_routed_experts"]


def held(conf):
    return conf.get("experts_held", conf["n_routed_experts"])


def layer_matmul_params(conf):
    """Every matrix element of a layer that lies on this chip."""
    return (attention_params(conf) + shared_params(conf) + router_params(conf)
            + held(conf) * expert_params(conf))


def distinct_experts(conf, batch):
    """Held experts that ``batch`` tokens touch, expected: a token keeps k
    distinct of E, so it misses a given one with probability 1 - k/E."""
    miss = 1.0 - conf["num_experts_per_tok"] / conf["n_routed_experts"]
    return held(conf) * (1.0 - miss ** batch)


def weight_bytes_step(conf, batch, weights):
    """What one step of ``batch`` tokens must read: attention, the shared
    expert and the router whole, each touched expert once, and the head."""
    per_layer = (attention_params(conf) + shared_params(conf)
                 + router_params(conf)
                 + distinct_experts(conf, batch) * expert_params(conf))
    return ((conf["num_hidden_layers"] * per_layer
             + conf["hidden_size"] * conf["vocab_size"])
            * WEIGHT_BYTES[weights])


def matmul_flops_per_token(conf):
    """A token is multiplied by the experts it keeps, and of those by the
    ones held here: k x held / E of them, expected."""
    kept_here = (conf["num_experts_per_tok"] * held(conf)
                 / conf["n_routed_experts"])
    per_layer = (attention_params(conf) + shared_params(conf)
                 + router_params(conf) + kept_here * expert_params(conf))
    return 2.0 * (conf["num_hidden_layers"] * per_layer
                  + conf["hidden_size"] * conf["vocab_size"])
