"""Operations and bytes a decode step or a prefill needs, from shapes alone.
The benchmark's own arithmetic (``runtime/accounting.py`` has the program's):
a roofline share divides the least time these imply by measured device time.

``conf`` is a configuration file's dict (the published config.json keys plus
``head_dim`` and ``mlp_matrices``). Bytes are what the algorithm has to move at
the stated storage types, not what a layout pads them to.

The formulas here are those of a dense layer with grouped-query attention. A
configuration whose layers are of another kind (experts of which a token
keeps a few, a latent cache) names ``"work": "<name>.work.py"`` in its file,
beside its ``"reference"``: plain arithmetic from the file's own keys that
may define any of ``layer_matmul_params(conf)``, ``weight_bytes_step(conf,
batch, weights)``, ``matmul_flops_per_token(conf)``, ``kv_bytes_per_token(conf,
kv)`` and ``attn_flops_per_pair(conf)``. Each function below of one of those
names asks the configuration's file first and is today's formula where the
file has none.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from typing import Callable, Dict, Optional


GROUP = 32      # weights share one float32 scale per 32 input rows

# bytes a stored weight takes, scale included
WEIGHT_BYTES = {"int4": 0.5 + 4.0 / GROUP, "int8": 1.0 + 4.0 / GROUP,
                "bfloat16": 2.0, "float32": 4.0}
# bytes one cached key or value channel takes; int8 and int4 caches add one
# float32 scale per position and head
KV_ITEM = {"int4": 0.5, "int8": 1.0, "bfloat16": 2.0, "float32": 4.0}
KV_SCALE = {"int4": 4.0, "int8": 4.0, "bfloat16": 0.0, "float32": 0.0}


def load_conf(path: str) -> dict:
    """A configuration's file, which remembers its directory: the files it
    names (``reference``, ``work``) lie beside it."""
    with open(path) as f:
        conf = json.load(f)
    conf["_dir"] = os.path.dirname(os.path.abspath(path))
    return conf


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """A file a configuration names (its ``reference``, its ``work``), as a
    module; loaded once."""
    name = os.path.basename(path)[:-len(".py")].replace(".", "_")
    spec = importlib.util.spec_from_file_location("benchmark_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def own(conf: dict, name: str) -> Optional[Callable]:
    """The configuration's own function of that name, where its file names a
    ``work`` file that defines one."""
    if "work" not in conf:
        return None
    path = os.path.join(conf["_dir"], conf["work"])
    return getattr(load_module(path), name, None)


def layer_matmul_params(conf: dict) -> int:
    f_own = own(conf, "layer_matmul_params")
    if f_own:
        return f_own(conf)
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, kv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    return (d * h * hd + 2 * d * kv * hd + h * hd * d
            + conf["mlp_matrices"] * d * f)


def matmul_params(conf: dict) -> int:
    """Every weight a token is multiplied by: the layers and the output
    head (the embedding is a row lookup, not a multiplication)."""
    return (conf["num_hidden_layers"] * layer_matmul_params(conf)
            + conf["hidden_size"] * conf["vocab_size"])


def weight_bytes(conf: dict, weights: str) -> float:
    """Bytes of every weight a step reads. An output head tied to the
    embedding is read as the embedding is stored: bfloat16, not quantized."""
    head = conf["hidden_size"] * conf["vocab_size"]
    if conf.get("tie_word_embeddings"):
        return ((matmul_params(conf) - head) * WEIGHT_BYTES[weights]
                + head * WEIGHT_BYTES["bfloat16"])
    return matmul_params(conf) * WEIGHT_BYTES[weights]


def weight_bytes_step(conf: dict, batch: float, weights: str) -> float:
    """Bytes of weights one step over ``batch`` tokens must read: all of
    them, unless the configuration says that a token touches only some."""
    f_own = own(conf, "weight_bytes_step")
    if f_own:
        return f_own(conf, batch, weights)
    return weight_bytes(conf, weights)


def matmul_flops_per_token(conf: dict) -> float:
    f_own = own(conf, "matmul_flops_per_token")
    return f_own(conf) if f_own else 2.0 * matmul_params(conf)


def attn_flops_per_pair(conf: dict) -> float:
    """One query against one cached position, all layers and heads."""
    f_own = own(conf, "attn_flops_per_pair")
    if f_own:
        return f_own(conf)
    return (4 * conf["num_hidden_layers"] * conf["num_attention_heads"]
            * conf["head_dim"])


def kv_bytes_per_token(conf: dict, kv: str) -> float:
    """Keys and values of one position over all layers."""
    f_own = own(conf, "kv_bytes_per_token")
    if f_own:
        return f_own(conf, kv)
    return (2 * conf["num_hidden_layers"] * conf["num_key_value_heads"]
            * (conf["head_dim"] * KV_ITEM[kv] + KV_SCALE[kv]))


def decode_step(conf: dict, batch: float, live_tokens: float, weights: str,
                kv: str) -> Dict[str, float]:
    """One decode step of ``batch`` sequences whose contexts hold
    ``live_tokens`` positions together: every weight read once, every live
    key and value read once, the new ones written, float32 logits written."""
    attn = attn_flops_per_pair(conf) * live_tokens
    return {
        "flops": matmul_flops_per_token(conf) * batch + attn,
        "bytes": (weight_bytes_step(conf, batch, weights)
                  + (live_tokens + batch) * kv_bytes_per_token(conf, kv)
                  + batch * conf["vocab_size"] * 4.0),
    }


def prefill(conf: dict, tokens: int, weights: str, kv: str
            ) -> Dict[str, float]:
    """One prompt of ``tokens`` positions from an empty cache: causal
    attention is half the square."""
    attn = attn_flops_per_pair(conf) * tokens * (tokens + 1) / 2.0
    return {
        "flops": matmul_flops_per_token(conf) * tokens + attn,
        "bytes": (weight_bytes_step(conf, tokens, weights)
                  + tokens * kv_bytes_per_token(conf, kv)
                  + conf["vocab_size"] * 4.0),
    }


def least_seconds(work: Dict[str, float], peaks: dict) -> Dict[str, float]:
    """The least time a chip with these peaks could take, and which of the
    two bounds sets it."""
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops",
            "bytes_s": t_bytes, "flops_s": t_flops}


def load_peaks(path: str, device_kind: str) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or not isinstance(table[device_kind], dict):
        raise KeyError(f"device kind {device_kind!r} is not in {path}: "
                       "add its published peaks with their source")
    return table[device_kind]
