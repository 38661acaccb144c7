"""Is a cell's served program still what the parent serves? Lowers for the TPU
(nothing is compiled or run; weights are shapes only) the decode, admit,
admit_many and extend programs a benchmark cell's engine builds at its
zero-config resolution, from the tree given as argv[1], and writes each
lowered text to argv[2]/<config>.<kind>.txt:

    git archive <parent> | tar -x -C /root/scratch/parent
    ln -sfn /root/scratch/parent /root/scratch/tree
    JAX_PLATFORMS=cpu python hack/lower_cells.py /root/scratch/tree out_a
    (the same for a copy of the change, through the SAME path: file names
    are part of the kernels' debug locations)
    JAX_PLATFORMS=cpu python hack/cmp_lowered.py out_a out_b

Texts that differ only inside the serialized Mosaic bodies of the
``tpu_custom_call``s differ in debug locations (line numbers of callers):
``hack/cmp_lowered.py`` parses both bodies and compares them printed
without locations. The builder's check before a chip run, not a golden
file. Names after the two directories choose the configurations (default:
the two dense cells; ``granite-4.0-h-small`` and ``lfm2-8b-a1b`` lower by
name too, as any file of benchmark/configs does). ``--stub-sample``
among them lowers every program with ``ops/sampling.sample`` replaced by
an argmax: where two trees' texts are equal under it, they differ in the
sampler alone."""
import os
import sys
repo, out = sys.argv[1], sys.argv[2]
stub = "--stub-sample" in sys.argv[3:]
names = [a for a in sys.argv[3:] if a != "--stub-sample"] or [
    "starcoder2-3b", "phi-2"]
sys.path.insert(0, repo)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.makedirs(out, exist_ok=True)
import jax
import jax.numpy as jnp
jax.default_backend = lambda: "tpu"       # the repo's own resolve_* ask this
from benchmark import server_child as sc
from ollama_operator_tpu.runtime import engine as E

texts = {}
def spy(self, kind, key, jit_fn, *args):
    t = jit_fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    texts[f"{kind}.{key}"] = t
    return None
E.Engine._compile = spy
if stub:
    def _argmax(logits, counts, sp, key, mu=None, **_kw):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tok if mu is None else (tok, mu)
    E.sampling.sample = _argmax

for name in names:
    texts.clear()
    conf = sc.load_conf(os.path.join(repo, "benchmark", "configs", name + ".json"), False)
    cfg = sc.model_config(conf, False)
    dtype, ecfg = sc.resolve(cfg, "tpu", False)
    bits = {"int8": 8, "int4": 4}.get(dtype, 0)
    print(name, dtype, ecfg, flush=True)
    params = jax.eval_shape(sc.weights_program(cfg, bits, jnp.bfloat16, tuple(conf.get("omit_leaves", ()))), jax.random.key(0))
    eng = E.Engine(cfg, params, mesh=None, ecfg=ecfg)
    print(" engine cfg kernels", eng.cfg.kernels, eng.cfg.mm_kernels, flush=True)
    eng._decode_n_exec(ecfg.decode_chunk, 512)
    eng._decode_n_exec(1, 256)
    eng._admit_exec(256)
    eng._admit_many_exec(2, 128)
    eng._extend_exec(128, 512)
    for k, t in texts.items():
        open(os.path.join(out, f"{name}.{k}.txt"), "w").write(t)
        print(" ", k, len(t), flush=True)
