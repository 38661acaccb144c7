"""70B roofline projection: bridge the measured per-chip HBM utilization to
the north-star config (llama2:70b on v5e-16, >1000 tok/s aggregate —
BASELINE.json).

Decode is HBM-bandwidth-bound: per decode step every resident weight byte
streams once per chip, plus the slots' live KV windows. Given the EXACT
per-device bytes of the sharded 70B program (eval_shape + NamedSharding —
same accounting as hack/prog_70b.py, no arrays materialise) and a
bandwidth-utilization fraction, the projected aggregate throughput is

    tok/s = n_slots / (per_device_bytes / (819 GB/s x util))

This makes the north star falsifiable: the table prints the utilization
each config needs to cross 1000 tok/s, next to the utilizations actually
measured on the v5e-1 (BENCH_r*.json: 26-30% dense, 14% paged v2). Run on
a virtual 16-device CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=16 JAX_PLATFORMS=cpu \
        python hack/roofline_70b.py
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=16")

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

V5E_BW = 819e9      # bytes/s per chip (public spec)

# ICI: v5e lists 1600 Gbps (~200 GB/s) of interchip bandwidth per chip
# across 4 links of a 2D torus. A tp8 ring all-reduce rides ONE torus
# axis — both directions of 2 links — so the effective per-chip rate for
# the tp collective is ~half the aggregate. 90 GB/s is the center
# estimate; the table prints a 45/90/180 sensitivity span because the
# real number depends on link mapping the partitioner picks.
ICI_EFF_BW = (45e9, 90e9, 180e9)


def collective_bytes_per_chip(cfg, tp: int, dp: int, slots: int) -> int:
    """Per-chip ring traffic per decode step, analytic (VERDICT r4 #6).

    Megatron row-parallel layers end in a psum: 2 all-reduces per layer
    (attention o-proj, MLP down-proj) of the [B_local, 1, dim]
    activations, B_local = slots/dp — in **f32** (the compiled HLO
    reduces pre-residual activations at f32, not bf16: 2 ×
    ``all-reduce(f32[8192,B_local,1])`` per layer trip). Ring all-reduce
    over tp ways moves 2·(tp−1)/tp × logical bytes through each chip.
    The vocab-sharded lm_head needs NO logits gather (sampling runs on
    the sharded logits; the HLO shows only one final f32[B,1,dim] AR,
    <1% of the per-layer term). Cross-checked against the partitioned
    HLO of the compiled 70B program (hack/prog_70b.py collective_stats →
    tests/test_70b_program.py::test_collectives_priced: HLO logical
    bytes 47.2 MB/step vs this model's 42 MB + index gathers)."""
    b_local = max(1, slots // max(dp, 1))
    act = b_local * cfg.dim * 4                     # f32 activations
    per_layer = 2 * act * 2 * (tp - 1) / tp         # 2 ARs, ring factor
    final = b_local * cfg.dim * 4 * 2 * (tp - 1) / tp
    return int(cfg.n_layers * per_layer + final)


def leaf_device_bytes(aval_tree, sharding_tree) -> int:
    total = 0
    for a, sh in zip(jax.tree.leaves(aval_tree),
                     jax.tree.leaves(sharding_tree,
                                     is_leaf=lambda x: isinstance(
                                         x, NamedSharding))):
        shard = sh.shard_shape(a.shape)
        n = 1
        for d in shard:
            n *= d
        total += n * jnp.dtype(a.dtype).itemsize
    return total


def main() -> None:
    from ollama_operator_tpu.models import decoder
    from ollama_operator_tpu.models.config import get_config
    from ollama_operator_tpu.ops.quant import (quantize_params)
    from ollama_operator_tpu.parallel.mesh import MeshPlan, make_mesh
    from ollama_operator_tpu.parallel.sharding import params_sharding_tree

    cfg = get_config("llama2:70b")
    devs = jax.devices()
    assert len(devs) >= 16, f"need 16 virtual devices, have {len(devs)}"
    mesh = make_mesh(MeshPlan(tp=8, dp=2), devs[:16])

    p_bf16 = jax.eval_shape(
        lambda k: decoder.init_params(cfg, k, dtype=jnp.bfloat16),
        jax.random.key(0))

    def quant_avals(bits):
        from ollama_operator_tpu.ops import quant as Q
        return jax.eval_shape(lambda p: Q.quantize_params(p, bits=bits),
                              p_bf16)

    rows = []
    for dtype, bits in (("int8", 8), ("int4", 4)):
        p_q = quant_avals(bits)
        p_sh = params_sharding_tree(p_q, mesh, cfg)
        per_dev_w = leaf_device_bytes(p_q, p_sh)
        # live KV read per step per chip: each slot's window, int8 codes,
        # KvH sharded over tp8 (8 kv heads / 8 ways -> 1 head per chip),
        # batch over dp2
        L, KvH, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        for slots, ctx in ((8, 1024), (32, 1024), (32, 4096)):
            kv_per_dev = (slots // 2) * ctx * L * (KvH // 8) * hd * 2  # int8
            per_dev = per_dev_w + kv_per_dev
            coll = collective_bytes_per_chip(cfg, tp=8, dp=2, slots=slots)
            coll_s_mid = coll / ICI_EFF_BW[1]
            row = {"dtype": dtype, "slots": slots, "ctx": ctx,
                   "per_device_gb": round(per_dev / 1e9, 2),
                   "coll_mb_per_chip_step": round(coll / 1e6, 2),
                   "coll_ms@90GBs": round(coll_s_mid * 1e3, 3)}
            for util in (0.14, 0.30, 0.45, 0.60):
                step_s = per_dev / (V5E_BW * util)
                row[f"tok_s@{int(util*100)}%"] = round(slots / step_s, 1)
                # additive collective term (psum sits on the critical
                # path each layer; no overlap assumed — conservative)
                row[f"tok_s@{int(util*100)}%+coll"] = round(
                    slots / (step_s + coll_s_mid), 1)
            # util needed for 1000 tok/s aggregate, WITH the collective
            # term priced at the 45/90/180 GB/s ICI sensitivity span
            need = (per_dev / V5E_BW) / (slots / 1000.0)
            row["util_for_1000"] = round(need * 100, 1)
            for bw in ICI_EFF_BW:
                budget = slots / 1000.0 - coll / bw
                row[f"util_for_1000+coll@{int(bw/1e9)}GBs"] = (
                    round((per_dev / V5E_BW) / budget * 100, 1)
                    if budget > 0 else None)   # ICI alone blows the budget
            rows.append(row)

    print(json.dumps({"mesh": "tp8xdp2 (v5e-16)", "rows": rows}, indent=1))

    # markdown table for BASELINE.md
    print("\n| dtype | slots | ctx | GB/chip/step | coll MB/chip | "
          "tok/s @30% | @30%+coll | @45% | @45%+coll | util for 1000 | "
          "+coll@45/90/180 GB/s |", file=sys.stderr)
    print("|---|---|---|---|---|---|---|---|---|---|---|", file=sys.stderr)
    for r in rows:
        sens = "/".join(
            str(r[f"util_for_1000+coll@{int(bw/1e9)}GBs"])
            for bw in ICI_EFF_BW)
        print(f"| {r['dtype']} | {r['slots']} | {r['ctx']} | "
              f"{r['per_device_gb']} | {r['coll_mb_per_chip_step']} | "
              f"{r['tok_s@30%']} | {r['tok_s@30%+coll']} | "
              f"{r['tok_s@45%']} | {r['tok_s@45%+coll']} | "
              f"{r['util_for_1000']}% | {sens}% |", file=sys.stderr)


if __name__ == "__main__":
    main()
