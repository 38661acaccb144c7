"""Parameter and activation sharding specs (GSPMD / NamedSharding).

Megatron-style tensor parallelism expressed declaratively: column-parallel
q/k/v/gate/up, row-parallel o/down, vocab-parallel embedding + lm_head. XLA
inserts the all-reduces (psum over "tp") at the row-parallel boundaries —
there is no hand-written collective on the dense path (the ring-attention
path in ring_attention.py is the exception, by design).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig

# Specs for stacked layer leaves: leading axis is n_layers (never sharded).
_LAYER_SPECS: Dict[str, P] = {
    "attn_norm_w": P(None, None),
    "attn_norm_b": P(None, None),
    "mlp_norm_w": P(None, None),
    "mlp_norm_b": P(None, None),
    "wq": P(None, None, "tp"),
    "wk": P(None, None, "tp"),
    "wv": P(None, None, "tp"),
    "wo": P(None, "tp", None),
    "bq": P(None, "tp"),
    "bk": P(None, "tp"),
    "bv": P(None, "tp"),
    "bo": P(None, None),
    "w_gate": P(None, None, "tp"),
    "w_up": P(None, None, "tp"),
    "w_down": P(None, "tp", None),
    "b_up": P(None, "tp"),
    "b_down": P(None, None),
    "q_norm_w": P(None, None),
    "k_norm_w": P(None, None),
    "post_attn_norm_w": P(None, None),
    "post_ffw_norm_w": P(None, None),
    # MoE (mixtral family): experts on "ep", per-expert Megatron TP on "tp"
    "router": P(None, None, None),
    "we_gate": P(None, "ep", None, "tp"),
    "we_up": P(None, "ep", None, "tp"),
    "we_down": P(None, "ep", "tp", None),
    # qwen2moe shared expert: dense Megatron TP like w_gate/w_up/w_down;
    # the sigmoid gate projection replicates ([L, D, 1])
    "we_sh_gate": P(None, None, "tp"),
    "we_sh_up": P(None, None, "tp"),
    "we_sh_down": P(None, "tp", None),
    "sh_gate": P(None, None, None),
}

_TOP_SPECS: Dict[str, P] = {
    "tok_emb": P("tp", None),   # vocab-parallel; XLA all-gathers the lookup
    "out_norm_w": P(None),
    "out_norm_b": P(None),
    "lm_head": P(None, "tp"),
    "lm_head_b": P("tp"),
}


def resolve_specs(cfg: Optional[ModelConfig], mesh: Optional[Mesh]
                  ) -> tuple[Dict[str, P], Dict[str, P]]:
    """(top_specs, layer_specs) adjusted for GQA divisibility.

    With few KV heads (llama2:70b has 8) and a wide tp axis, KV heads may
    not divide tp; the standard layout then replicates K/V (and their
    projections) across the extra tp ways — each replica serves its local
    group of Q heads. Vocab-parallel embedding falls back to replication if
    the vocab doesn't divide tp.
    """
    top, layer = dict(_TOP_SPECS), dict(_LAYER_SPECS)
    if cfg is None or mesh is None:
        return top, layer
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and cfg.n_kv_heads % tp != 0:
        layer.update(wk=P(None, None, None), wv=P(None, None, None),
                     bk=P(None, None), bv=P(None, None))
    if tp > 1 and cfg.vocab_size % tp != 0:
        top.update(tok_emb=P(None, None), lm_head=P(None, None),
                   lm_head_b=P(None))
    ep = mesh.shape.get("ep", 1)
    if ep > 1 and cfg.n_experts % ep != 0:
        layer.update(we_gate=P(None, None, None, "tp"),
                     we_up=P(None, None, None, "tp"),
                     we_down=P(None, None, "tp", None))
        # shared-expert leaves keep their dense-TP specs
    return top, layer


def experts_ep_sharded(cfg: Optional[ModelConfig], mesh: Optional[Mesh]
                       ) -> bool:
    """True iff resolve_specs places the expert axis on "ep" for this mesh
    (the single source of truth for the divisibility fallback above)."""
    if cfg is None or mesh is None or not cfg.n_experts:
        return False
    ep = mesh.shape.get("ep", 1)
    return ep > 1 and cfg.n_experts % ep == 0


def resolve_moe_impl(cfg: ModelConfig, mesh: Optional[Mesh]) -> str:
    """The MoE impl an "auto" config must use on this mesh: the einsum
    layout whenever the experts are actually ep-sharded — the scan layout
    slices the expert axis per step, which under GSPMD would all-gather
    every ep-sharded expert weight onto every device."""
    if cfg.moe_impl == "auto" and experts_ep_sharded(cfg, mesh):
        return "einsum"
    return cfg.moe_impl


def _leaf_spec(spec: P, v: Any, mesh: Optional[Mesh], name: str = "?"):
    """A quantized dict leaf {"q"|"q4", "s"} shares its dense spec: q has
    the dense shape (q4 the packed K/2 at the same position) and the group
    axis of s is K/g at the same position, so the same PartitionSpec
    usually partitions both. When a scale dim is too small to divide its
    mesh axis (tiny K/g), that axis replicates for s only — XLA still
    partials the dot over the sharded q rows. An int4 leaf whose shard
    boundary splits a packing group (GROUP/2 packed rows carry one
    group's nibbles) still computes correctly — GSPMD reshards around
    qmm4's (G, g/2, O) reshape (tests/test_quant.py pins it) — but the
    reshard is an all-gather-class copy on a hot decode matmul, so it is
    flagged loudly at load with the leaf and mesh axis named."""
    from ..ops.quant import GROUP, is_int4, is_quantized
    if not is_quantized(v):
        return spec
    if is_int4(v) and mesh is not None:
        kp = v["q4"].shape[-2]          # packed K/2 rows
        ax = spec[-2] if len(spec) >= 2 else None
        size = mesh.shape.get(ax, 1) if ax else 1
        if size > 1 and (kp % size or (kp // size) % (GROUP // 2)):
            import warnings
            warnings.warn(
                f"int4 leaf {name!r}: packed K axis ({kp} rows) sharded "
                f"{size}-way over mesh axis {ax!r} does not split on "
                f"whole {GROUP}-row packing groups ({GROUP // 2} packed "
                f"rows); GSPMD inserts a reshard on this matmul every "
                f"decode step — prefer a tp that divides K into "
                f"multiples of {GROUP}, or serve this model int8",
                stacklevel=2)
    s_shape = v["s"].shape
    s_spec = []
    for i, ax in enumerate(spec):
        size = mesh.shape.get(ax, 1) if (mesh is not None and ax) else 1
        s_spec.append(ax if ax and s_shape[i] % size == 0 else None)
    return {("q4" if is_int4(v) else "q"): spec, "s": P(*s_spec)}


def params_pspec_tree(params: Dict[str, Any],
                      cfg: Optional[ModelConfig] = None,
                      mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    top, layer = resolve_specs(cfg, mesh)
    out: Dict[str, Any] = {}
    for k, v in params.items():
        if k == "layers":
            out[k] = {lk: _leaf_spec(layer[lk], lv, mesh, name=lk)
                      for lk, lv in v.items()}
        else:
            out[k] = _leaf_spec(top[k], v, mesh, name=k)
    return out


def params_sharding_tree(params: Dict[str, Any], mesh: Mesh,
                         cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        params_pspec_tree(params, cfg, mesh),
        is_leaf=lambda x: isinstance(x, P))


def shard_params(params: Dict[str, Any], mesh: Mesh,
                 cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    """device_put the params pytree with TP/vocab-parallel layout."""
    shardings = params_sharding_tree(params, mesh, cfg)
    return jax.device_put(params, shardings)


def kv_cache_pspec(cfg: Optional[ModelConfig] = None,
                   mesh: Optional[Mesh] = None) -> P:
    """KV cache [L, B, KvH, S, hd] (head-first): batch on dp, heads on tp
    (replicated over tp when KV heads don't divide it — see resolve_specs),
    sequence on sp when the mesh has a sequence-parallel axis (long-context
    mode, parallel/long_context.py)."""
    if cfg is not None and mesh is not None:
        tp = mesh.shape.get("tp", 1)
        dp = mesh.shape.get("dp", 1)
        sp = mesh.shape.get("sp", 1)
        b = "dp" if dp > 1 else None
        s = "sp" if sp > 1 else None
        h = "tp" if (tp > 1 and cfg.n_kv_heads % tp == 0) else None
        return P(None, b, h, s, None)
    return P(None, "dp", "tp", "sp", None)


def act_pspec() -> P:
    """Activations [B, T, D]: batch on dp."""
    return P("dp", None, None)
