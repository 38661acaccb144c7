"""The serving kernels compile for the chip, at tinyllama's real shapes.

Interpret mode on a CPU never meets Mosaic's tiling rules; these compiles
do, for a v5e:2x2 that is described and not attached (the TPU's compiler
ships with the installed jaxlib). Each case lowers one kernel the
zero-config tinyllama server runs (int8 weights, int8 paged KV, 64 slots,
128-token pages, head_dim 64 padded to the 128-lane tile) and asserts the
compiled program really holds a Mosaic kernel (`tpu_custom_call`), not the
einsum a dispatcher would quietly fall back to. Nothing runs: a compile
that passes says nothing about results or times (chip_smoke.py does).

One file on purpose: only one process may load the TPU's library, so these
tests must land on one xdist worker, and the topology is described inside
a fixture, never at import.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.models.config import PRESETS
from ollama_operator_tpu.ops.attention import chunk_attention
from ollama_operator_tpu.ops.pallas.flash import (decode_attention,
                                                  flash_prefill)
from ollama_operator_tpu.ops.pallas.paged import paged_decode_attention
from ollama_operator_tpu.ops.pallas.quant import qmm4_pallas, qmm_pallas
from ollama_operator_tpu.parallel.mesh import AXES, MeshPlan
from ollama_operator_tpu.runtime.engine import (EngineConfig,
                                                resolve_serving_defaults)

CFG = dataclasses.replace(PRESETS["tinyllama"], kernels="pallas")
H, KVH, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
SCALE = 1.0 / HD ** 0.5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def serving(one_chip):
    """The EngineConfig a zero-config tinyllama start resolves on a TPU,
    from the engine's own resolver (the backend probe is steered here, in
    the test): slots, page size and pool pages are read, not guessed."""
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    try:
        ecfg = resolve_serving_defaults(
            EngineConfig(max_slots=0, max_seq_len=4096, decode_chunk=0,
                         cache_dtype=jnp.int8, paged=None, page_size=0,
                         n_pages=None), CFG, None)
    finally:
        mp.undo()
    assert (ecfg.paged, ecfg.max_slots, ecfg.page_size,
            ecfg.decode_chunk) == (True, 64, 128, 32)
    return ecfg


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _pool_shapes(ecfg, sharding, scale_sharding=None, n_pages=None):
    """k/v pool ShapeDtypeStructs in the engine's own layout
    (runtime/engine.py __init__): [L, P, KvH, ps, hd padded to 128] int8
    codes and [L, P, KvH, ps padded to 128] f32 scales."""
    ps = ecfg.page_size
    pages = n_pages if n_pages is not None else ecfg.n_pages + 1
    hd_pool = -(-HD // 128) * 128
    sp_pool = -(-ps // 128) * 128
    q = jax.ShapeDtypeStruct((CFG.n_layers, pages, KVH, ps, hd_pool),
                             jnp.int8, sharding=sharding)
    s = jax.ShapeDtypeStruct((CFG.n_layers, pages, KVH, sp_pool),
                             jnp.float32,
                             sharding=scale_sharding or sharding)
    return {"q": q, "s": s}


# (K, O): tinyllama's MLP projections, the widest matmuls it serves
@pytest.mark.parametrize("K,O", [(2048, 5632), (5632, 2048)])
@pytest.mark.parametrize("bits", [8, 4])
def test_dequant_matmul_compiles(one_chip, serving, K, O, bits):
    B = serving.max_slots
    x = jax.ShapeDtypeStruct((B, K), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((K // 32, O), jnp.float32, sharding=one_chip)
    if bits == 8:
        q = jax.ShapeDtypeStruct((K, O), jnp.int8, sharding=one_chip)
        txt = _compiled_text(qmm_pallas, x, q, s)
    else:
        q = jax.ShapeDtypeStruct((K // 2, O), jnp.uint8, sharding=one_chip)
        txt = _compiled_text(qmm4_pallas, x, q, s)
    assert "tpu_custom_call" in txt


def _qmm_stack_shapes(one_chip, N, K, O, bits, layers=2):
    """x and a stack of ``layers`` quantized [K, O] weights, as the
    decoder's layer scan hands them to the fused kernel."""
    x = jax.ShapeDtypeStruct((N, K), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((layers, K // 32, O), jnp.float32,
                             sharding=one_chip)
    q = jax.ShapeDtypeStruct((layers, K * bits // 8, O),
                             jnp.int8 if bits == 8 else jnp.uint8,
                             sharding=one_chip)
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return x, q, s, layer


# starcoder2-3b's and phi-2's widest matmuls (w_up, w_down, phi-2's head)
# at the rows a saturated decode step has (32, 64 slots), at admit_many's
# 4 x 256, and at the longest prefill bucket: the kernel's row axis keeps
# every one inside the default 16 MiB of VMEM (no vmem_limit_bytes is set)
@pytest.mark.parametrize("K,O", [(3072, 12288), (12288, 3072),
                                 (2560, 51200)])
@pytest.mark.parametrize("N", [32, 64, 256, 1024, 4096])
def test_int8_fused_matmul_compiles_at_served_rows(one_chip, N, K, O):
    txt = _compiled_text(
        lambda x, q, s, l: qmm_pallas(x, q, s, layer=l),
        *_qmm_stack_shapes(one_chip, N, K, O, 8))
    assert "tpu_custom_call" in txt


# Mistral-7B's MLP at the admit bucket PR 23 could not compile
# (`_admit_exec(2048)`: all rows in one block, 16.31M of 16.00M)
@pytest.mark.parametrize("K,O", [(4096, 14336), (14336, 4096)])
@pytest.mark.parametrize("N", [2048, 4096])
def test_int4_fused_matmul_compiles_at_long_admit(one_chip, N, K, O):
    txt = _compiled_text(
        lambda x, q, s, l: qmm4_pallas(x, q, s, layer=l),
        *_qmm_stack_shapes(one_chip, N, K, O, 4))
    assert "tpu_custom_call" in txt


def test_paged_decode_v3_compiles_on_engine_pool(one_chip, serving):
    B, ps = serving.max_slots, serving.page_size
    nblk = CFG.max_seq_len // ps
    pool = _pool_shapes(serving, one_chip)
    q = jax.ShapeDtypeStruct((B, 1, H, HD), jnp.bfloat16, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((B, nblk), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def fn(q, kp, vp, tables, lengths):
        out = paged_decode_attention(
            q, kp, vp, jnp.int32(0), tables, lengths, SCALE, nblk=nblk)
        assert out is not None, "refused the engine's own pool layout"
        return out

    assert "tpu_custom_call" in _compiled_text(fn, q, pool, pool, tables,
                                               lengths)


@pytest.mark.parametrize("T", [64, 2048])   # smallest and largest bucket
def test_flash_prefill_compiles(one_chip, T):
    q = jax.ShapeDtypeStruct((1, T, H, HD), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, KVH, T, HD), jnp.bfloat16,
                              sharding=one_chip)

    def fn(q, k, v):
        out = flash_prefill(q, k, v, SCALE)
        assert out is not None, "flash_prefill refused a prefill bucket"
        return out

    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv)


def test_dense_decode_kernel_compiles(one_chip):
    B, S = 8, CFG.max_seq_len          # the dense default: 8 slots
    q = jax.ShapeDtypeStruct((B, 1, H, HD), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, KVH, S, HD), jnp.bfloat16,
                              sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def fn(q, k, v, pos):
        out = decode_attention(q, k, v, pos, SCALE)
        assert out is not None, "decode_attention refused the dense cache"
        return out

    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv, pos)


@pytest.mark.parametrize("dp,tp", [(1, 4), (2, 2)])
def test_paged_kernel_compiles_inside_manual_shard_map(topo, serving,
                                                       dp, tp):
    """On a mesh the paged kernel runs inside a manual shard_map — the
    tp-manual attend (decoder._paged_attend) or the dp/tp-manual
    write+attend (decoder._paged_write_attend_dp) — each device seeing
    its own heads (and, under dp, its own sub-pool and table rows)."""
    plan = MeshPlan(dp=dp, tp=tp)
    mesh = Mesh(np.array(topo.devices).reshape(plan.dims), AXES)
    B, ps = serving.max_slots, serving.page_size
    nblk = CFG.max_seq_len // ps
    pg = "dp" if dp > 1 else None

    def sh(*spec):
        return NamedSharding(mesh, P(*spec))

    # under dp each shard owns its own sub-pool (own trash page)
    pages = dp * (-(-serving.n_pages // dp) + 1)
    pool = _pool_shapes(serving, sh(None, pg, "tp", None, None),
                        sh(None, pg, "tp", None), n_pages=pages)
    q = jax.ShapeDtypeStruct((B, 1, H, HD), jnp.bfloat16,
                             sharding=sh(pg, None, "tp", None))
    tables = jax.ShapeDtypeStruct((B, nblk), jnp.int32, sharding=sh(pg, None))
    lengths = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sh(pg))
    layer = jnp.int32(0)
    if dp == 1:
        def fn(q, kp, vp, tables, lengths):
            return decoder._paged_attend(
                CFG, q, kp, vp, layer, tables, lengths, None, SCALE, nblk,
                mesh, True)
        txt = _compiled_text(fn, q, pool, pool, tables, lengths)
    else:
        kv = jax.ShapeDtypeStruct((B, 1, KVH, HD), jnp.bfloat16,
                                  sharding=sh("dp", None, "tp", None))
        pos = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=sh("dp", None))
        mask = jax.ShapeDtypeStruct((B, 1, 1, nblk * ps), jnp.float32,
                                    sharding=sh("dp", None, None, None))

        def fn(q, k, v, kp, vp, tables, lengths, pos, mask):
            return decoder._paged_write_attend_dp(
                CFG, q, k, v, kp, vp, layer, tables, lengths, pos, mask,
                SCALE, nblk, True, False, mesh, "tp")
        txt = _compiled_text(fn, q, kv, kv, pool, pool, tables, lengths,
                             pos, mask)
    assert "tpu_custom_call" in txt


def test_paged_write_kernel_compiles_on_a_tp_mesh(topo, serving):
    """The step's K/V write of a tp=4 server: decoder._paged_write runs
    the writer's kernel manual over every axis, one KV head to a device."""
    mesh = Mesh(np.array(topo.devices).reshape(MeshPlan(tp=4).dims), AXES)
    B = serving.max_slots

    def sh(*spec):
        return NamedSharding(mesh, P(*spec))

    pool = _pool_shapes(serving, sh(None, None, "tp", None, None),
                        sh(None, None, "tp", None))
    kv = jax.ShapeDtypeStruct((B, 1, KVH, HD), jnp.bfloat16,
                              sharding=sh(None, None, "tp", None))
    at = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=sh(None, None))

    def fn(kp, vp, k, v, pg, off):
        return decoder._scatter_kv_pools(CFG, kp, vp, jnp.int32(0), k, v,
                                         pg, off, mesh)
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(
        pool, pool, kv, kv, at, at).compile()
    assert "paged_kv_write" in compiled.as_text()


# the benchmark's two paged cells at their resolved engines: (preset,
# slots, pages + the trash page, page size); pools [L, P, KvH, ps, 128]
CELL_POOLS = {"phi-2": ("phi", 32, 160, 64),
              "starcoder2-3b": ("starcoder2", 64, 768, 128)}


def _cell_pool(one_chip, name):
    preset, slots, pages, ps = CELL_POOLS[name]
    cfg = dataclasses.replace(PRESETS[preset], kernels="pallas")
    q = jax.ShapeDtypeStruct((cfg.n_layers, pages, cfg.n_kv_heads, ps, 128),
                             jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((cfg.n_layers, pages, cfg.n_kv_heads, 128),
                             jnp.float32, sharding=one_chip)
    return cfg, slots, ps, {"q": q, "s": s}, q.size


@pytest.mark.parametrize("name", sorted(CELL_POOLS))
def test_paged_decode_kernel_compiles_at_the_cells_pools(one_chip, name):
    """The walk alone at each dense cell's resolved pool, the model's own
    window (starcoder2's 4096 compiles the branch that starts a slot's
    walk late): its page buffers, as deep as the page's bytes make them
    (phi-2's page set is 557 KB, starcoder2's 66 KB), fit the kernel's
    VMEM, and copies that cross a grid step are Mosaic's to take."""
    from ollama_operator_tpu.ops.pallas.paged import _walk_depth
    cfg, B, ps, pool, _ = _cell_pool(one_chip, name)
    nblk = 2048 // ps
    KvH = cfg.n_kv_heads
    assert _walk_depth(2 * KvH * ps * 128 + 2 * KvH * 128 * 4) == 4
    sds = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=one_chip)
    q = sds((B, 1, cfg.n_heads, cfg.head_dim), jnp.bfloat16)

    def fn(q, kp, vp, layer, tables, lengths):
        out = paged_decode_attention(
            q, kp, vp, layer, tables, lengths, 1.0, cfg.attn_softcap,
            cfg.sliding_window, nblk=nblk)
        assert out is not None, "refused the cell's own pool layout"
        return out

    txt = _compiled_text(fn, q, pool, pool, sds((), jnp.int32),
                         sds((B, nblk), jnp.int32), sds((B,), jnp.int32))
    assert "paged_v3" in txt


@pytest.mark.parametrize("name", sorted(CELL_POOLS))
def test_decode_write_in_the_layer_scan_copies_no_pool(one_chip, name):
    """The decode program's writer as the step runs it: inside the layer
    scan, the pools donated, the v3 attention reading them in the same
    layer. The kernel is there, and the program's temporaries are far
    below one code pool: nothing re-lays a pool out or copies it (a
    windowed XLA scatter compiles to two pool copies a layer here)."""
    cfg, B, ps, pool, pool_bytes = _cell_pool(one_chip, name)
    nblk = 2048 // ps
    L, KvH, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    sds = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=one_chip)
    q = sds((B, 1, cfg.n_heads, hd), jnp.bfloat16)
    kv = sds((B, 1, KvH, hd), jnp.bfloat16)
    tables = sds((B, nblk), jnp.int32)
    lengths = sds((B,), jnp.int32)

    def fn(kp, vp, q, k, v, tables, lengths):
        pg = tables[jnp.arange(B), lengths // ps][:, None]
        off = (lengths % ps)[:, None]

        def layer(carry, i):
            kp, vp, acc = carry
            kp, vp = decoder._scatter_kv_pools(cfg, kp, vp, i, k, v, pg, off)
            out = decoder._paged_attend(cfg, q, kp, vp, i, tables, lengths,
                                        None, 1.0, nblk, None, True)
            return (kp, vp, acc + out), None
        return jax.lax.scan(layer, (kp, vp, jnp.zeros_like(q)),
                            jnp.arange(L, dtype=jnp.int32))[0]
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(
        pool, pool, q, kv, kv, tables, lengths).compile()
    txt = compiled.as_text()
    assert "paged_kv_write" in txt and "paged_v3" in txt
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 16


# pools Mosaic's manual copies cannot take (ops/pallas/paged.
# paged_decode_tileable): an int4 pool of 32-token pages (16 stored rows,
# under int8's 32-row tile) and int8 pages whose scale lanes nobody padded
# to 128. Until PR 31 the v2 grid kernel served both on a chip.
REFUSED_POOLS = {
    "int4-32-token-pages": ("q4", 32, 128),
    "unpadded-scale-lanes": ("q", 64, 64),
}


@pytest.mark.parametrize("shape", sorted(REFUSED_POOLS))
def test_refused_pool_decodes_through_the_flagged_fallback(one_chip, shape):
    """A decode step over a pool the kernel refuses still compiles for the
    chip: the route is gather + einsum, chosen once before the layer scan
    and recorded as a fallback, and no Mosaic attention is in the program."""
    from ollama_operator_tpu.ops.attention import record_kernels
    key, ps, sp = REFUSED_POOLS[shape]
    cfg = dataclasses.replace(PRESETS["starcoder2"], kernels="pallas")
    B, pages, nblk = 8, 33, 4
    L, KvH, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    sds = lambda shp, dt: jax.ShapeDtypeStruct(       # noqa: E731
        shp, dt, sharding=one_chip)
    rows = ps // 2 if key == "q4" else ps
    pool = {key: sds((L, pages, KvH, rows, 128), jnp.int8),
            "s": sds((L, pages, KvH, sp), jnp.float32)}
    q = sds((B, 1, cfg.n_heads, hd), jnp.bfloat16)
    tables = sds((B, nblk), jnp.int32)
    lengths = sds((B,), jnp.int32)

    def fn(kp, vp, q, tables, lengths):
        use_kernel = decoder._paged_kernel_usable(cfg, None, 1, kp)
        k_pos = jnp.arange(nblk * ps, dtype=jnp.int32)[None, None, :]
        mask = decoder._causal_window_mask(k_pos, lengths[:, None, None],
                                           cfg.sliding_window)

        def layer(acc, i):
            out = decoder._paged_attend(cfg, q, kp, vp, i, tables, lengths,
                                        mask, 1.0, nblk, None, use_kernel)
            return acc + out, None
        return jax.lax.scan(layer, jnp.zeros_like(q),
                            jnp.arange(L, dtype=jnp.int32))[0]
    with record_kernels() as picked:
        txt = jax.jit(fn).lower(pool, pool, q, tables,
                                lengths).compile().as_text()
    assert picked == [("paged_decode", "gather_einsum", True)]
    assert "paged_v3" not in txt and "tpu_custom_call" not in txt


@pytest.mark.parametrize("bucket", [64, 256])
@pytest.mark.parametrize("name", sorted(CELL_POOLS))
def test_admit_insert_copies_no_pool(one_chip, name, bucket):
    """The admit program's writer (decoder.paged_insert, one window a page)
    on the donated pools: temporaries far below one code pool."""
    cfg, _B, ps, pool, pool_bytes = _cell_pool(one_chip, name)
    fresh = jax.ShapeDtypeStruct(
        (cfg.n_layers, 1, cfg.n_kv_heads, bucket, cfg.head_dim),
        jnp.bfloat16, sharding=one_chip)
    row = jax.ShapeDtypeStruct((2048 // ps,), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def fn(kp, vp, ks, vs, row, n_valid):
        return decoder.paged_insert(cfg, kp, vp, ks, vs, row, n_valid)
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(
        pool, pool, fresh, fresh, row, n).compile()
    # the chunk's own pages, read and written: 128 MiB at phi-2's 256 bucket
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4


def test_flash_prefill_compiles_inside_manual_shard_map(topo):
    """The admit program of a tp=4 server: ops/attention.chunk_attention
    wraps flash_prefill in a manual shard_map, 8 query heads and one KV
    head to a device."""
    mesh = Mesh(np.array(topo.devices).reshape(MeshPlan(tp=4).dims), AXES)
    T = 512
    q = jax.ShapeDtypeStruct(
        (1, T, H, HD), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, None, "tp", None)))
    kv = jax.ShapeDtypeStruct(
        (1, KVH, T, HD), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, "tp", None, None)))

    def fn(q, k, v):
        return chunk_attention(CFG, q, k, v, None, SCALE, mesh=mesh)

    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv)


@pytest.mark.parametrize("B,T", [(32, 1), (4, 256)],
                         ids=["decode-32-slots", "admit_many-4x256"])
def test_delta_mixer_compiles_at_published_widths(one_chip, B, T):
    """The gated delta-rule mixer of one layer as the served programs run it
    (state and convolution inputs donated), at Olmo-Hybrid-7B's widths: the
    decode step holds the kernel that passes over the state once (PR 45) and
    updates the nine layers' state in place, its temporaries far below one
    layer's state; the blocked form of a batched admission compiles with its
    triangular solve and no kernel."""
    cfg = dataclasses.replace(PRESETS["olmo-hybrid-7b"], kernels="pallas")
    Ld = cfg.n_delta_layers
    sds = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=one_chip)
    layers = jax.eval_shape(lambda k: decoder.init_params(cfg, k),
                            jax.random.key(0))["layers"]
    dp = {k: sds(v.shape[1:], v.dtype) for k, v in layers.items()
          if k.startswith("delta_")}
    ssm, conv, _ = (x and sds(x.shape, x.dtype) for x in jax.eval_shape(
        lambda: decoder.empty_state(cfg, B)))
    assert ssm.shape == (Ld, B, 15, 96, 384)    # two heads a row: no padding
    compiled = jax.jit(
        lambda dp, u, ssm, conv, row, nv: decoder._delta_mixer(
            cfg, dp, u, ssm, conv, row, nv), donate_argnums=(2, 3)).lower(
        dp, sds((B, T, cfg.dim), jnp.bfloat16), ssm, conv,
        sds((), jnp.int32), sds((B,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    one_layer = ssm.size * 4 // Ld
    assert mem.alias_size_in_bytes >= ssm.size * 4      # both leaves in place
    assert ("delta_update" in compiled.as_text()) == (T == 1)
    if T == 1:
        assert mem.temp_size_in_bytes < one_layer // 16


@pytest.mark.parametrize("B,T,A,kernels", [
    (64, 1, 4096, "xla"), (64, 1, 1024, "xla"), (1, 256, 4096, "xla"),
    (64, 1, 1024, "pallas"), (64, 1, 2048, "pallas"), (64, 1, 4096, "pallas"),
    (1, 256, 4096, "pallas")],
    ids=["decode-deep", "decode-shallow", "extend", "kernel-1024",
         "kernel-2048", "kernel-4096", "kernel-extend"])
def test_latent_attention_compiles_at_published_widths(one_chip, B, T, A,
                                                       kernels):
    """One latent-attention layer against the int8 cache as the served
    programs run it (both caches donated), at GLM-5's widths: the decode
    step at the bucket where the indexer chooses (a top-k of 2,048 of 4,096)
    and at ones where it does not, and an extend piece; the rows and the
    indexer's keys are written in place. In the einsum form (``xla``, and
    any T > 1) and through the kernel that walks each slot's own rows
    (``pallas`` at T == 1, PR 51: ``latent_decode`` in the optimized program,
    which then holds no copy of the layer's attended window of rows, [64, A,
    640] int8, the einsum form's 3.77 ms a step)."""
    import re
    cfg = dataclasses.replace(PRESETS["glm-5"], kernels=kernels)
    La = 2
    sds = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=one_chip)
    layers = jax.eval_shape(lambda k: decoder.init_params(cfg, k),
                            jax.random.key(0))["layers"]
    ap = {k: sds(v.shape[1:], v.dtype) for k, v in layers.items()
          if k in decoder._ATTN_STACK}
    _, kd, vd = cfg.cache_row_dims
    kc = {"q": sds((La, B, 1, 4096, kd), jnp.int8),
          "s": sds((La, B, 2, 4096), jnp.float32)}
    vc = {"q": sds((La, B, 1, 4096, vd), jnp.int8),
          "s": sds((La, B, 1, 4096), jnp.float32)}

    def layer(ap, h, kc, vc, row, lengths, nv):
        pos = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        cos, sin = decoder.rope_angles(pos, cfg.qk_rope_dim, cfg.rope_theta)
        return decoder._latent_cached(cfg, ap, h, kc, vc, row, pos, nv, A,
                                      cos, sin)

    compiled = jax.jit(layer, donate_argnums=(2, 3)).lower(
        ap, sds((B, T, cfg.dim), jnp.bfloat16), kc, vc, sds((), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    cache = La * B * 4096 * (kd + vd + 12)
    assert mem.alias_size_in_bytes >= cache             # all four leaves
    # the top-k is a sort of the bucket, there only where the indexer chooses
    assert bool(re.search(r"\bsort\(", text)) == (A > 2048)
    kernel = kernels == "pallas" and T == 1
    assert ("latent_decode" in text) == kernel
    if kernel:
        assert "tpu_custom_call" in text
        assert not re.search(rf"s8\[{B},{A},{kd}\]", text)


@pytest.mark.parametrize("B,T,A,kernels", [
    (64, 1, 4096, "xla"), (64, 1, 1024, "pallas"), (64, 1, 4096, "pallas"),
    (1, 256, 4096, "pallas")],
    ids=["decode-deep", "kernel-1024", "kernel-4096", "kernel-extend"])
def test_latent_attention_without_an_indexer_compiles_at_published_widths(
        one_chip, B, T, A, kernels):
    """One latent-attention layer at Kimi-K2.7-Code's widths (64 heads of 128
    + 64 query channels, values 128, the row 576 -> 640) against the int8
    rows as the served programs run it: NOTHING rides where values do, the
    rows' two leaves are written in place (the key's second code where the
    padding was), the rotated channels turn at YaRN's frequencies, no sort
    at any depth, and the decode step reads through ``latent_decode`` with
    no keep mask (PR 53)."""
    import re
    cfg = dataclasses.replace(PRESETS["kimi-k2.7-code"], kernels=kernels)
    La = 2
    sds = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=one_chip)
    layers = jax.eval_shape(lambda k: decoder.init_params(cfg, k),
                            jax.random.key(0))["layers"]
    ap = {k: sds(v.shape[1:], v.dtype) for k, v in layers.items()
          if k in decoder._ATTN_STACK}
    assert not [k for k in ap if k.startswith("idx_")]
    _, kd, vd = cfg.cache_row_dims
    assert (kd, vd) == (640, 0) and cfg.latent_key_residual == 64
    kc = {"q": sds((La, B, 1, 4096, kd), jnp.int8),
          "s": sds((La, B, 2, 4096), jnp.float32)}

    def layer(ap, h, kc, row, lengths, nv):
        pos = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        cos, sin = decoder._latent_rope(cfg, pos)
        out, kc, vc = decoder._latent_cached(cfg, ap, h, kc, None, row, pos,
                                             nv, A, cos, sin)
        assert vc is None
        return out, kc

    compiled = jax.jit(layer, donate_argnums=(2,)).lower(
        ap, sds((B, T, cfg.dim), jnp.bfloat16), kc, sds((), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert mem.alias_size_in_bytes >= La * B * 4096 * (kd + 8)
    assert not re.search(r"\bsort\(", text)
    kernel = kernels == "pallas" and T == 1
    assert ("latent_decode" in text) == kernel
    if kernel:
        assert "tpu_custom_call" in text
        assert not re.search(rf"s8\[{B},{A},{kd}\]", text)


@pytest.mark.parametrize("name,B,T,A,kernels", [
    ("smallthinker-21b-a3b", 64, 1, 1024, "xla"),
    ("smallthinker-21b-a3b", 64, 1, 4096, "xla"),
    ("smallthinker-21b-a3b", 1, 256, 4096, "xla"),
    ("k-exaone-236b-a23b", 64, 1, 1024, "xla"),
    ("smallthinker-21b-a3b", 64, 1, 4096, "pallas"),
    ("smallthinker-21b-a3b", 1, 256, 4096, "pallas"),
    ("k-exaone-236b-a23b", 64, 1, 1024, "pallas")],
    ids=["rows-shallow", "rows-deep", "extend", "select-128", "kernel-deep",
         "kernel-extend", "kernel-select-128"])
def test_ring_attention_compiles_at_published_widths(one_chip, name, B, T, A,
                                                     kernels):
    """One window layer against the int8 rings as the served programs run it
    (the rings donated), at SmallThinker's widths (rings of 4,096: a decode
    step writes a row a slot and its temporaries stay under one layer's
    ring, at either attended depth; an extend piece merges one slot's ring)
    and at K-EXAONE's (rings of 128: the select): every leaf of the rings is
    updated in place. In the einsum form (``xla``) and where the kernels are
    on (``pallas``): a long ring's decode step reads through ``ring_decode``
    (PR 52), a piece and a short ring keep the einsum form."""
    cfg = dataclasses.replace(PRESETS[name], kernels=kernels)
    cfg_w = decoder._kind_cfgs(cfg)[1]
    Lw, W = 2, cfg.sliding_window
    sds = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=one_chip)
    ring = {"q": sds((Lw, B, cfg.n_kv_heads, W, cfg.head_dim), jnp.int8),
            "s": sds((Lw, B, cfg.n_kv_heads, W), jnp.float32)}

    def layer(q, k, v, kr, vr, row, lengths, nv):
        return decoder._ring_attend(cfg_w, q, k, v, (kr, vr), row, lengths,
                                    nv, 0.088, A)

    compiled = jax.jit(layer, donate_argnums=(3, 4)).lower(
        sds((B, T, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        sds((B, T, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16),
        sds((B, T, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16),
        ring, ring, sds((), jnp.int32), sds((B,), jnp.int32),
        sds((B,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    one_layer = B * cfg.n_kv_heads * W * (cfg.head_dim + 4)
    assert mem.alias_size_in_bytes >= 2 * Lw * one_layer    # all four leaves
    if T == 1:
        assert mem.temp_size_in_bytes < one_layer   # no copy of a ring
    kernel = kernels == "pallas" and T == 1 and W > decoder._RING_SELECT_MAX
    assert ("ring_decode" in compiled.as_text()) == kernel


@pytest.mark.parametrize("name", ["granite-4.0-h-small", "lfm2-8b-a1b",
                                  "olmo-hybrid-7b"])
def test_a_decode_steps_convolution_loops_over_no_slots(one_chip, name):
    """``decoder._causal_conv`` with one new position, at the three hybrid
    cells' widths and 32 slots, the leaf donated: the inputs it carries are
    advanced by a select over whole arrays, in place, and the compiled
    program holds no ``while``: the gather a row, which a prefill piece
    keeps, compiled at Olmo-Hybrid-7B's 11,520 channels to a loop of 32
    one-row ``dynamic-update-slice``s a layer, the decode step's largest
    single operation (2.10 ms of 17.73: PERF.md section 6, PR 47)."""
    cfg = PRESETS[name]
    _, conv, _ = jax.eval_shape(lambda: decoder.empty_state(cfg, 32))
    L, B, Km1, C = conv.shape
    sds = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda conv, row, new, w, nv: decoder._causal_conv(
            conv, row, new, w, nv, act=jax.nn.silu), donate_argnums=(0,)
    ).lower(sds(conv.shape, conv.dtype), sds((), jnp.int32),
            sds((B, 1, C), jnp.bfloat16), sds((Km1 + 1, C), jnp.bfloat16),
            sds((B,), jnp.int32)).compile()
    assert " while(" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= conv.size * 4         # in place
    assert mem.temp_size_in_bytes < conv.size * 4 // L * 4  # a few rows


def test_smallthinkers_deepest_decode_program_holds_the_ring_kernel(
        one_chip, monkeypatch):
    """``decode.(32, 4096)`` of the smallthinker cell as the benchmark's
    child resolves it (bfloat16 weights as shapes, int8 cache, 64 slots,
    rings of 4,096), compiled for the described v5e: the window layers' read
    is ``ring_decode`` (PR 52), said so and flagged nothing, and no ring leaf
    is re-laid outside the program's entry: the scales' leaves reach the
    kernel as the row write's scatter leaves them (a kernel that takes them
    as declared has both copied whole, 2 x 25 MB, in front of every window
    layer's call)."""
    import os
    import re

    from benchmark import server_child as sc
    from ollama_operator_tpu.runtime import engine as E
    name = "smallthinker-21b-a3b"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    conf = sc.load_conf(os.path.join(os.path.dirname(sc.__file__), "configs",
                                     name + ".json"), False)
    cfg = sc.model_config(conf, False)
    dtype, ecfg = sc.resolve(cfg, "tpu", False)
    assert (dtype, ecfg.max_slots, ecfg.decode_chunk) == ("bfloat16", 64, 32)
    params = jax.eval_shape(
        sc.weights_program(cfg, 0, jnp.bfloat16, ()), jax.random.key(0))
    texts = {}

    def spy(self, kind, key, jit_fn, *args):
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            if hasattr(a, "shape") and hasattr(a, "dtype") else a, args)
        with E.record_kernels() as picked:
            texts[kind, key] = jit_fn.lower(*args).compile().as_text()
        texts["picked"] = sorted(picked)
    monkeypatch.setattr(E.Engine, "_compile", spy)
    eng = E.Engine(cfg, params, mesh=None, ecfg=ecfg)
    eng._decode_n_exec(ecfg.decode_chunk, 4096)
    text = texts["decode", (32, 4096)]
    assert ("window", "ring_decode", False) in texts["picked"]
    assert not any(fell_back for _, _, fell_back in texts["picked"])
    assert "ring_decode" in text and "tpu_custom_call" in text
    W, KvH, hd = eng.cfg.sliding_window, cfg.n_kv_heads, cfg.head_dim
    leaf = rf"(?:f32\[6,64,{KvH},{W}\]|s8\[6,64,{KvH},{W},{hd}\])"
    entry = False
    for line in text.splitlines():
        if re.match(r"^(ENTRY )?%?[\w.\-]+ \(", line):
            entry = line.startswith("ENTRY")
        assert entry or not re.search(
            rf"= {leaf}\S* (?:copy|copy-start)\(", line), line[:200]
