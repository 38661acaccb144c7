"""The paged pool's writers against the forms they replaced (PR 30).

The decode-step write (``decoder._scatter_kv_pools``: the pallas kernel
``ops/pallas/kv_write.paged_kv_write`` in interpret mode, and the XLA
scatter) and the admission insert (``decoder.paged_insert``, one window a
page) must put the same bytes at the same pool addresses as the per-head
scatters every PR before 30 served. Those live on here as the oracle; the
trash page (page 0) holds whatever collided there last and is left out.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.models.config import PRESETS
from ollama_operator_tpu.ops import quant_cache as QC
from ollama_operator_tpu.ops.attention import record_kernels
from ollama_operator_tpu.ops.pallas import kv_write

BASE = PRESETS["tiny"]
HD, HD_POOL = 80, 128         # phi-2's head_dim in the pool's padded lanes
SP = 128                      # the scale pools' padded lanes


def _cfg(kernels):
    return dataclasses.replace(BASE, kernels=kernels)


def _pools(rng, kind, L, P, KvH, ps):
    """(k_pool, v_pool) of random content in the engine's layout."""
    def one():
        s = jnp.asarray(rng.standard_normal((L, P, KvH, SP)), jnp.float32)
        if kind == "int8":
            return {"q": jnp.asarray(rng.integers(
                -127, 128, (L, P, KvH, ps, HD_POOL)), jnp.int8), "s": s}
        if kind == "int4":
            return {"q4": jnp.asarray(rng.integers(
                -128, 128, (L, P, KvH, ps // 2, HD_POOL)), jnp.int8), "s": s}
        return jnp.asarray(rng.standard_normal((L, P, KvH, ps, HD_POOL)),
                           jnp.bfloat16)
    return one(), one()


def _leaves(pools):
    """Every array of a pool pair outside the trash page, as bytes."""
    return [np.asarray(x[:, 1:]).view(np.uint8)
            for x in jax.tree_util.tree_leaves(pools)]


def _same(got, want):
    got, want = _leaves(got), _leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and np.array_equal(g, w)


# -- the decode-step write ---------------------------------------------------

def _per_head_scatter(pool, i, vals, pg, off):
    """The parent's ``_paged_scatter``: vals [B, KvH, T(, hd)]."""
    hx = jnp.arange(vals.shape[1])[None, :, None]
    return pool.at[i, pg[:, None, :], hx, off[:, None, :]].set(vals)


def _oracle_write(kp, vp, i, k, v, pg, off):
    """The parent's ``_scatter_kv_pools``: k, v head-first [B, KvH, T, hd]."""
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    put = _per_head_scatter
    pad = lambda x: decoder._pad_hd(x, HD_POOL)        # noqa: E731
    if isinstance(kp, dict) and "q4" in kp:
        kq, ksc = QC.quantize_kv4(k)
        vq, vsc = QC.quantize_kv4(v)
        tr = lambda x: x.transpose(0, 2, 1, 3)         # noqa: E731
        return ({"q4": decoder._paged_scatter4(kp["q4"], i, tr(pad(kq)),
                                               pg, off),
                 "s": put(kp["s"], i, ksc, pg, off)},
                {"q4": decoder._paged_scatter4(vp["q4"], i, tr(pad(vq)),
                                               pg, off),
                 "s": put(vp["s"], i, vsc, pg, off)})
    if isinstance(kp, dict):
        kq, ksc = QC.quantize_kv(k)
        vq, vsc = QC.quantize_kv(v)
        return ({"q": put(kp["q"], i, pad(kq), pg, off),
                 "s": put(kp["s"], i, ksc, pg, off)},
                {"q": put(vp["q"], i, pad(vq), pg, off),
                 "s": put(vp["s"], i, vsc, pg, off)})
    return (put(kp, i, pad(k.astype(kp.dtype)), pg, off),
            put(vp, i, pad(v.astype(vp.dtype)), pg, off))


def _write_case(rng, kind, KvH, T, ps=16, B=5, L=3):
    """Pools, fresh K/V and (page, offset) as the paged forward computes
    them: rows 1 and 2 are vacant slots (all-trash table rows, adjacent, so
    two writes to one page follow each other), row 3 over-runs its table
    (out-of-table blocks go to the trash page), and with T > 1 the other
    rows' positions cross a page boundary."""
    nblk = 2
    P = 1 + B * nblk
    kp, vp = _pools(rng, kind, L, P, KvH, ps)
    k = jnp.asarray(rng.standard_normal((B, T, KvH, HD)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, KvH, HD)), jnp.float32)
    tables = 1 + np.arange(B * nblk, dtype=np.int32).reshape(B, nblk)
    tables[1:3] = decoder.TRASH_PAGE
    start = np.array([ps - 2, 0, 3, 2 * ps - T // 2, ps - T // 2 - 1][:B])
    pos = start[:, None] + np.arange(T)[None, :]
    blk = pos // ps
    pg = np.where(blk < nblk, tables[np.arange(B)[:, None],
                                     np.minimum(blk, nblk - 1)],
                  decoder.TRASH_PAGE).astype(np.int32)
    return kp, vp, k, v, jnp.asarray(pg), jnp.asarray(pos % ps, jnp.int32)


@pytest.mark.parametrize("T", [1, 6])
@pytest.mark.parametrize("KvH", [2, 8, 32])
@pytest.mark.parametrize("kind", ["int8", "bfloat16", "int4"])
@pytest.mark.parametrize("kernels", ["interpret", "xla"])
def test_decode_write_bytes_equal_per_head_form(kernels, kind, KvH, T):
    rng = np.random.default_rng(KvH * 10 + T)
    kp, vp, k, v, pg, off = _write_case(rng, kind, KvH, T)
    i = jnp.int32(1)
    want = jax.jit(_oracle_write)(kp, vp, i, k, v, pg, off)
    with record_kernels() as picked:
        got = jax.jit(lambda *a: decoder._scatter_kv_pools(
            _cfg(kernels), *a))(kp, vp, i, k, v, pg, off)
    _same(got, want)
    # layers the write did not name are untouched
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves((kp, vp))):
        assert np.array_equal(np.asarray(g[0]).view(np.uint8),
                              np.asarray(w[0]).view(np.uint8))
    took = "paged_kv_write" if kernels == "interpret" else "xla_scatter"
    assert picked == [("paged_write", took, False)]


def test_kernel_refuses_a_page_the_chip_cannot_tile():
    """Compiled for the chip a 16-row int8 page is half a (32, 128) tile:
    the kernel says so (None) and the writer takes the XLA form, flagged."""
    rng = np.random.default_rng(0)
    kp, vp, k, v, pg, off = _write_case(rng, "int8", 2, 1)
    kq, ksc = QC.quantize_kv(k)
    args = ((kp["q"], kp["s"]), jnp.int32(0), pg, off,
            (decoder._pad_hd(kq, HD_POOL), ksc))
    assert kv_write.paged_kv_write(*args, interpret=False) is None
    assert kv_write.paged_kv_write(*args, interpret=True) is not None


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8])
def test_paged_forward_kernel_write_matches_xla_write(cache_dtype):
    """The whole paged engine, two admissions and nine decode steps that
    cross a page boundary: with the kernel writing (interpret) the tokens
    are the XLA form's and the pools hold what it leaves."""
    from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                    SlotOptions)
    params = decoder.init_params(BASE, jax.random.key(0), jnp.float32)
    ecfg = EngineConfig(max_slots=4, max_seq_len=64, cache_dtype=cache_dtype,
                        min_prefill_bucket=16, paged=True, page_size=8)
    pools = {}
    for kernels in ("xla", "interpret"):
        eng = Engine(_cfg(kernels), params, ecfg=ecfg)
        eng.admit(0, np.arange(3, 14, dtype=np.int32),
                  SlotOptions(temperature=0.0))
        eng.admit(2, np.array([7, 7, 7], np.int32),
                  SlotOptions(temperature=0.0))
        toks = [eng.decode()[[0, 2]].tolist() for _ in range(9)]
        pools[kernels] = (toks, jax.tree_util.tree_leaves(
            (eng.k_cache, eng.v_cache)))
    assert pools["xla"][0] == pools["interpret"][0]
    for g, w in zip(pools["interpret"][1], pools["xla"][1]):
        # the two forwards round their attention differently, so deeper
        # layers' K/V are close, not bit-equal: a code may differ by one
        np.testing.assert_allclose(
            np.asarray(g[:, 1:], np.float32), np.asarray(w[:, 1:], np.float32),
            atol=1.0 if g.dtype == jnp.int8 else 2e-4)


# -- the admission insert ------------------------------------------------------

def _oracle_insert(k_pool, v_pool, ks, vs, table_row, n_valid):
    """The parent's ``paged_insert``: one scatter index per (layer, head,
    position); positions >= n_valid go to the trash page."""
    quant = isinstance(k_pool, dict)
    quant4 = quant and "q4" in k_pool
    arr = (k_pool["q4"] if quant4 else k_pool["q"]) if quant else k_pool
    L, P, KvH, ps, hd = arr.shape
    if quant4:
        ps *= 2
    Tb = ks.shape[3]
    t = jnp.arange(Tb, dtype=jnp.int32)
    pg_row = jnp.where(t < n_valid, table_row[t // ps],
                       jnp.int32(decoder.TRASH_PAGE))
    off = t % ps
    lx = jnp.arange(L)[:, None, None]
    hx = jnp.arange(KvH)[None, :, None]

    def put(pool, vals, pg=pg_row, off=off):
        return pool.at[lx, pg[None, None, :], hx, off[None, None, :]
                       ].set(vals)

    pad = lambda x: decoder._pad_hd(x, hd)             # noqa: E731
    if quant4:
        kq, ksc = QC.quantize_kv4(ks)
        vq, vsc = QC.quantize_kv4(vs)
        put4 = lambda pool, vals: put(                 # noqa: E731
            pool, vals, pg_row[0::2], off[0::2] // 2)
        return ({"q4": put4(k_pool["q4"], QC.pack_kv4(pad(kq[:, 0]))),
                 "s": put(k_pool["s"], ksc[:, 0])},
                {"q4": put4(v_pool["q4"], QC.pack_kv4(pad(vq[:, 0]))),
                 "s": put(v_pool["s"], vsc[:, 0])})
    if quant:
        kq, ksc = QC.quantize_kv(ks)
        vq, vsc = QC.quantize_kv(vs)
        return ({"q": put(k_pool["q"], pad(kq[:, 0])),
                 "s": put(k_pool["s"], ksc[:, 0])},
                {"q": put(v_pool["q"], pad(vq[:, 0])),
                 "s": put(v_pool["s"], vsc[:, 0])})
    return (put(k_pool, pad(ks[:, 0].astype(arr.dtype))),
            put(v_pool, pad(vs[:, 0].astype(arr.dtype))))


def _insert_case(kind, Tb, ps, n_valid, KvH=2, L=2):
    rng = np.random.default_rng(Tb + ps + n_valid)
    nblk = 256 // ps
    P = 1 + nblk + 2
    k_pool, v_pool = _pools(rng, kind, L, P, KvH, ps)
    ks = jnp.asarray(rng.standard_normal((L, 1, KvH, Tb, HD)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((L, 1, KvH, Tb, HD)), jnp.float32)
    # the slot owns pages for its real tokens only; the rest of its row is
    # the trash page, as runtime/paged.PageTable leaves it
    row = np.zeros((nblk,), np.int32)
    own = -(-n_valid // ps)
    row[:own] = rng.permutation(np.arange(1, P))[:own]
    return k_pool, v_pool, ks, vs, jnp.asarray(row), jnp.int32(n_valid)


@pytest.mark.parametrize("which", ["1", "ps-1", "ps", "Tb"])
@pytest.mark.parametrize("ps", [64, 128])
@pytest.mark.parametrize("Tb", [64, 128, 256])
def test_paged_insert_bytes_equal_per_head_form(Tb, ps, which):
    n_valid = min(Tb, {"1": 1, "ps-1": ps - 1, "ps": ps, "Tb": Tb}[which])
    args = _insert_case("int8", Tb, ps, n_valid)
    want = jax.jit(_oracle_insert)(*args)
    got = jax.jit(lambda *a: decoder.paged_insert(BASE, *a))(*args)
    _same(got, want)


@pytest.mark.parametrize("kind,Tb,ps,n_valid", [
    ("bfloat16", 128, 64, 65), ("bfloat16", 64, 128, 40),
    ("int4", 128, 64, 65), ("int4", 128, 64, 64), ("int4", 64, 128, 41),
    ("int8", 32, 64, 17),
])
def test_paged_insert_other_pools(kind, Tb, ps, n_valid):
    args = _insert_case(kind, Tb, ps, n_valid, KvH=8)
    want = jax.jit(_oracle_insert)(*args)
    got = jax.jit(lambda *a: decoder.paged_insert(BASE, *a))(*args)
    _same(got, want)


# -- the per-layer metric that reads the write -----------------------------------

@pytest.mark.parametrize("scopes,want", [
    ({"attn.kv_write": 4.0e9, "attn.core": 8.0e9}, 1.0),   # 4 ms of 4 steps
    ({"attn.core": 8.0e9, "(no scope)": 4.0e9}, None),     # scatters unscoped
    (None, None),                                          # no trace at all
])
def test_decode_kv_write_metric_reads_the_scope(monkeypatch, scopes, want):
    """``decode_kv_write_ms_per_step`` is the decode module's self time under
    ``attn.kv_write`` over the steps of its runs; a program whose write
    carries no scope (the parent's scatter fusions), or no trace, reads
    None and never raises."""
    import types

    from benchmark import run, trace_spans
    red = None if scopes is None else {"device": {
        "named": True,
        "modules": {"jit__decode_n": {"scopes": scopes, "ops": {}}},
        "runs": {"jit__decode_n": [{"dur": 12.0e9, "scopes": scopes}]}}}
    monkeypatch.setattr(trace_spans, "reduce", lambda where=None: red)
    ctx = types.SimpleNamespace(resolved={"decode_chunk": 4}, notes={})
    got = run.layer_reader("decode_kv_write_ms_per_step").read(ctx)
    assert got == (None if want is None else pytest.approx(want))
