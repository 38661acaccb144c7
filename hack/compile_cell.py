"""Does a cell fit the chip? Compiles, for a described v5e (no chip; nothing
runs; weights are shapes only), the programs a benchmark cell's engine builds
at its zero-config resolution, the program that makes its weights and its
reference's ``forward_chosen`` / ``forward_rounded``, from the tree given as
argv[1], and prints each one's ``memory_analysis()``:

    JAX_PLATFORMS=cpu python hack/compile_cell.py /root/repo lfm2-8b-a1b \
        [decode] [admit] [admit_many] [extend] [ref] [probe] [plan]

What the TPU compiler refuses it raises here, at no chip time. It counts one
program at a time, not what else the process keeps on the device (the
probe's two engines hold a cache each). ``probe`` (a contiguous cache only)
is the logits program of ``server_child.probe``: it does not donate the
cache, so its temporaries hold a copy of every cache leaf a decode step
writes, beside the probe engine's own. ``plan`` compiles EVERY program of the
warm plan (each attended and prefill bucket: 56 for a contiguous cell) and
goes on past one the compiler refuses, printing ``FAIL``: one shape of 56 can
fail alone (``glm-5``'s ``decode.(32, 1024)`` did, in VMEM, PR 46), and the
chip's warm plan would find it 15 minutes into a run. Several at once:
``... plan:decode``, ``plan:admit``, ``plan:admit_many``, ``plan:extend``.
~4 min for a 16-layer routed model. With ``HLO_DIR`` in the environment each
engine program's optimized HLO is written there (``<config>.<kind>.<key>.hlo.txt``:
``hack/hlo_copies.py`` lists the loops and re-laying copies the chip will
run, by the computation they run in)."""
import os
import sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
repo, name = sys.argv[1], sys.argv[2]
sys.path.insert(0, repo)
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
jax.default_backend = lambda: "tpu"
from benchmark import server_child as sc
from ollama_operator_tpu.runtime import engine as E
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
def sds(a):
    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
    return a
GB = 1e9
def spy(self, kind, key, jit_fn, *args):
    args = jax.tree_util.tree_map(sds, args)
    try:
        c = jit_fn.lower(*args).compile()
    except Exception as e:      # noqa: BLE001 — the plan goes on past it
        if not any(w.startswith("plan") for w in sys.argv[3:]):
            raise
        print(f"FAIL {kind}.{key}: {str(e)[:400]!r}", flush=True)
        return None
    if os.environ.get("HLO_DIR"):
        os.makedirs(os.environ["HLO_DIR"], exist_ok=True)
        with open(os.path.join(os.environ["HLO_DIR"],
                               f"{name}.{kind}.{key}.hlo.txt"), "w") as f:
            f.write(c.as_text())
    m = c.memory_analysis()
    print(f"{kind}.{key}: args {m.argument_size_in_bytes/GB:.3f} out {m.output_size_in_bytes/GB:.3f} "
          f"alias {m.alias_size_in_bytes/GB:.3f} temp {m.temp_size_in_bytes/GB:.3f} "
          f"peak~ {(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes)/GB:.3f} GB", flush=True)
    return None
E.Engine._compile = spy
conf = sc.load_conf(os.path.join(repo, "benchmark", "configs", name + ".json"), False)
cfg = sc.model_config(conf, False)
dtype, ecfg = sc.resolve(cfg, "tpu", False)
print(name, dtype, ecfg, flush=True)
bits = {"int8": 8, "int4": 4}.get(dtype, 0)
params = jax.eval_shape(sc.weights_program(cfg, bits, jnp.bfloat16, tuple(conf.get("omit_leaves", ()))), jax.random.key(0))
print("weights GB", sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)) / GB)
# the weights program itself
wp = jax.jit(sc.weights_program(cfg, bits, jnp.bfloat16, ())).lower(
    jax.ShapeDtypeStruct((), jax.random.key(0, impl="rbg").dtype, sharding=one)).compile()
m = wp.memory_analysis()
print("weights_program temp", m.temp_size_in_bytes/GB, "out",
      m.output_size_in_bytes/GB, flush=True)
eng = E.Engine(cfg, params, mesh=None, ecfg=ecfg)
what = sys.argv[3:] or ["decode", "admit", "admit_many", "extend", "ref"]
plan = {w.partition(":")[2] or "all" for w in what if w.startswith("plan")}
if plan:
    every = eng._buckets
    for kind in ("decode", "admit", "admit_many", "extend"):
        if not plan & {"all", kind}:
            continue
        for b in every:
            if kind == "decode":
                eng._decode_n_exec(ecfg.decode_chunk, b)
                eng._decode_n_exec(1, b)
            elif kind == "admit":
                eng._admit_exec(b)
            elif kind == "admit_many":
                eng._admit_many_exec(2, b)
                eng._admit_many_exec(4, b)
            else:
                for a in every:
                    if a > b:
                        eng._extend_exec(b, a)
if "decode" in what:
    eng._decode_n_exec(ecfg.decode_chunk, 512)
    eng._decode_n_exec(ecfg.decode_chunk, 4096)
if "admit" in what:
    eng._admit_exec(256)
    eng._admit_exec(4096)
if "admit_many" in what:
    eng._admit_many_exec(4, 256)
if "extend" in what:
    eng._extend_exec(256, 4096)
if "probe" in what:
    from ollama_operator_tpu.models import decoder
    T = min(sc.PROBE_TOKENS, ecfg.max_seq_len // 2)

    def logits_fn(p, kc, vc, tokens, step_tokens, lengths):
        pre, _ks, _vs = decoder.prefill_chunk(p, eng.cfg, tokens)
        dec, _kc, _vc = decoder.forward_with_cache(
            p, eng.cfg, step_tokens, kc, vc, lengths,
            attn_len=eng._attn_bucket(1))
        return pre[0, T - 1], dec[0, 0]
    spy(eng, "probe", "logits_fn", jax.jit(logits_fn), eng.params,
        eng.k_cache, eng.v_cache, jnp.zeros((1, T), jnp.int32),
        jnp.zeros((eng.n_slots, 1), jnp.int32), eng.lengths)
if "ref" in what:
    ref = sc.load_reference(conf)
    p = jax.tree_util.tree_map(sds, params)
if "ref" in what and not hasattr(ref, "forward_chosen"):
    # a model that makes no choice: the probe runs ``forward`` alone
    t = jax.ShapeDtypeStruct((257,), jnp.int32, sharding=one)
    c = jax.jit(lambda p, t: ref.forward(p, conf, t)[-2:]).lower(p, t).compile()
    m = c.memory_analysis()
    print(f"reference.forward: args {m.argument_size_in_bytes/GB:.3f} temp {m.temp_size_in_bytes/GB:.3f} out {m.output_size_in_bytes/GB:.3f}", flush=True)
elif "ref" in what:
    T = 257
    k = conf["num_experts_per_tok"]
    Lr = conf["num_hidden_layers"] - conf.get(
        "num_dense_layers", conf.get("first_k_dense_replace", 0))
    t = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one)
    ch = {"moe.route": jax.ShapeDtypeStruct((Lr, T, k), jnp.int32, sharding=one)}
    c = jax.jit(lambda p, t, c: ref.forward_chosen(p, conf, t, c)).lower(p, t, ch).compile()
    m = c.memory_analysis()
    print(f"reference.forward_chosen: args {m.argument_size_in_bytes/GB:.3f} temp {m.temp_size_in_bytes/GB:.3f} out {m.output_size_in_bytes/GB:.3f}", flush=True)
    c = jax.jit(lambda p, t: ref.forward_rounded(p, conf, t, jnp.float8_e4m3fn)).lower(p, t).compile()
    m = c.memory_analysis()
    print(f"reference.forward_rounded: args {m.argument_size_in_bytes/GB:.3f} temp {m.temp_size_in_bytes/GB:.3f}", flush=True)
