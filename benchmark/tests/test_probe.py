"""``correct`` (a) for a model that makes choices, on a fixture configuration
that is in no cell (``fixtures/routed.json``: the program's ``tiny-moe`` with a
shared expert). Run by hand with the yardstick's other checks: ``python -m
pytest benchmark/tests -q`` from the root of the repo, on the CPU (about seven
minutes; tier-1 collects ``tests/`` only, and this PR may add nothing there).

(i) the judgement alone, fed by the fixture's reference run twice, plain and
with every activation rounded through bfloat16 (the program runs float32 on the
CPU and never flips a choice, so the rounded reference stands in for the chip);
(ii) the plumbing: ``probe()`` itself on the program under ``--rehearse``;
(iii) planted faults, each failing by the line meant to catch it; (iv) the
dense configurations take the parent's path, and the program traces as it did
once a recorder has closed.
"""

import dataclasses
import functools
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import server_child as sc  # noqa: E402
from benchmark import work  # noqa: E402
from benchmark.choices import SITE, record_choices  # noqa: E402
from ollama_operator_tpu.models import decoder  # noqa: E402
from ollama_operator_tpu.models.config import get_config  # noqa: E402

ROUTED = os.path.join(HERE, "fixtures", "routed.json")


@pytest.fixture
def lines(monkeypatch):
    """Every line the child would print, as dicts."""
    got = []
    monkeypatch.setattr(sc, "say", lambda **rec: got.append(rec))
    return got


def of(lines, phase, compared=None):
    return [r for r in lines if r.get("phase") == phase
            and (compared is None or r.get("compared") == compared)]


# -- (i) the judgement alone -------------------------------------------------

@functools.lru_cache(maxsize=None)
def full_width():
    """The fixture at the sizes its file states (hidden 512, 64 experts, 4 a
    token, 4 layers), its reference and the three programs of it."""
    conf = work.load_conf(ROUTED)
    cfg = dataclasses.replace(
        get_config(conf["preset"]),
        **{ours: conf[theirs] for ours, theirs in conf["holds"]})
    ref = sc.load_reference(conf)
    return conf, cfg, ref, {
        "plain": jax.jit(lambda p, t: ref.forward(p, conf, t)),
        "bfloat16": jax.jit(lambda p, t: ref.forward_rounded(
            p, conf, t, jnp.bfloat16)),
        "float8": jax.jit(lambda p, t: ref.forward_rounded(
            p, conf, t, jnp.float8_e4m3fn))}


def rounded_against_plain(seed, how, lines, T=32):
    """(the old comparison's largest reading, judge()'s verdict) for the
    reference rounded through ``how`` in the program's place."""
    conf, cfg, ref, run = full_width()
    params = sc.make_weights(cfg, seed, 0, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        3, cfg.vocab_size, (T,)), jnp.int32)
    own = np.asarray(run["plain"](params, tokens))[-2:]
    logits, sets = run[how](params, tokens)
    logits = np.asarray(logits)[-2:]
    old = float((np.abs(logits - own).max(1) / np.abs(own).max(1)).max())
    del lines[:]
    ok = sc.judge("rounded", logits, {SITE: np.asarray(sets[SITE])}, ref,
                  params, conf, tokens)
    return old, ok


def test_the_judgement_passes_every_seed_where_the_old_comparison_fails_some(
        lines):
    old_fails, shortfalls, news = [], [], []
    for seed in range(64):
        old, ok = rounded_against_plain(seed, "bfloat16", lines)
        choice, = of(lines, "choices")
        assert ok, (seed, lines)
        shortfalls.append(choice["shortfall_max"])
        news.append(max(r["rel"] for r in of(lines, "logits")))
        if old > sc.LOGITS_TOL:
            old_fails.append(seed)
    print(f"\nold comparison over LOGITS_TOL on {len(old_fails)} of 64 seeds "
          f"{old_fails}; under the path's own sets the largest reading is "
          f"{max(news):.4f}, the largest shortfall {max(shortfalls):.5f}")
    assert old_fails, "no seed flipped: the fixture no longer shows the fault"
    assert max(shortfalls) < sc.CHOICE_TOL / 2
    assert max(news) < sc.LOGITS_TOL / 3


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_control_one_precision_lower_fails_the_judgement(seed, lines):
    """The reference in the program's place, every activation through
    float8 (e4m3): the step below bfloat16 that would tempt a later PR."""
    _old, ok = rounded_against_plain(seed, "float8", lines)
    assert not ok
    assert min(r["rel"] for r in of(lines, "logits")) > 2 * sc.LOGITS_TOL


# -- (ii) the plumbing: probe() on the program under --rehearse ---------------

def rehearsal(path, seed, kernels="interpret", paged=True):
    conf = sc.load_conf(path, True)
    cfg = sc.model_config(conf, True)
    cfg = dataclasses.replace(cfg, kernels=kernels, mm_kernels=kernels)
    _dtype, ecfg = sc.resolve(cfg, "cpu", True)
    # a model with experts resolves to a contiguous cache on the chip today
    # (resolve_paged_default); the probe drives whichever the engine serves
    ecfg = dataclasses.replace(ecfg, paged=paged)
    params = sc.make_weights(cfg, seed, 8, jnp.float32,
                             tuple(conf.get("omit_leaves", ())))
    if "router" in params["layers"]:
        # normal(0, 0.02) weights at hidden 64 spread a router's scores by
        # 0.16, and every gate is all but 1/k; at hidden 4096 they spread by
        # 1.28. Scale the toy's router so that its gates differ as they would
        params["layers"]["router"] = params["layers"]["router"] * 8.0
    return conf, cfg, ecfg, params


def run_probe(path, seed, lines, **kw):
    conf, cfg, ecfg, params = rehearsal(path, seed, **kw)
    del lines[:]
    return sc.probe(cfg, ecfg, params, conf, seed), cfg


@pytest.fixture
def short_probe(monkeypatch):
    monkeypatch.setattr(sc, "PROBE_TOKENS", 48)
    return 48


@pytest.mark.parametrize("paged", [True, False],
                         ids=["paged", "contiguous"])
@pytest.mark.parametrize("seed", [2 ** 31 + 5, 1, 2, 3, 4, 5, 6, 7])
def test_probe_passes_on_the_routed_fixture_and_brings_the_sets_out(
        seed, paged, lines, short_probe):
    ok, cfg = run_probe(ROUTED, seed, lines, paged=paged)
    assert ok, lines
    T = short_probe
    choices = of(lines, "choices")
    assert [r["compared"] for r in choices] == [
        "served vs reference", "program plain vs reference"]
    for path in choices:
        # prefill's [L, T, k] and the decode step's row of slot 0 as
        # position T
        assert path["sets_recorded"] == cfg.n_layers * (T + 1)
        assert path["positions"] == T + 1 and path["sites"] == [SITE]
        # float32 against float32: the program's sets ARE the reference's
        assert path["positions_not_the_references_own"] == 0
        assert path["shortfall_max"] == 0.0
    logits = of(lines, "logits")
    assert [r["compared"] for r in logits] == [
        "prefill: served vs reference", "decode: served vs reference",
        "prefill: program plain vs reference",
        "decode: program plain vs reference",
        "prefill: served vs program plain", "decode: served vs program plain"]
    assert not any("skipped" in r for r in logits)
    assert sorted(sc.COMPARED) == sorted(
        [r["compared"].replace(": ", "_").replace(" ", "_") for r in logits]
        + ["shortfall_served_vs_reference",
           "shortfall_program_plain_vs_reference"])


def flip_one_set(rows, at):
    """``_moe_gates`` of the plain path only, and of its call over ``rows``
    tokens only (the prompt's 48 or the decode step's 8 slots), with the
    weakest kept expert of one row swapped for the best not kept, in every
    layer: the other side of a tie."""
    inner = decoder._moe_gates

    def gates(cfg, lp, xf):
        g = inner(cfg, lp, xf)
        if cfg.kernels != "xla" or xf.shape[0] != rows:
            return g
        row = g[at]
        kept = row > 0
        out = jnp.argmin(jnp.where(kept, row, jnp.inf))
        logits = xf[at] @ lp["router"].astype(xf.dtype)
        into = jnp.argmax(jnp.where(kept, -jnp.inf, logits))
        return g.at[at, into].set(row[out]).at[at, out].set(0.0)
    return gates


@pytest.mark.parametrize("rows, at, skipped", [
    (48, 5, ["prefill", "decode"]),     # an early position feeds both
    (48, 47, ["prefill", "decode"]),    # the prompt's last
    (8, 0, ["decode"]),                 # the decode step's row of slot 0
])
def test_paths_that_chose_differently_are_not_compared_with_each_other(
        rows, at, skipped, lines, short_probe, monkeypatch):
    """The plain path on the other side of a tie at ONE position, not the
    compared one: its keys and values feed every later position, so served
    and plain are not held to each other from there on, and each still
    passes against the reference under its own sets."""
    monkeypatch.setattr(decoder, "_moe_gates", flip_one_set(rows, at))
    ok, _cfg = run_probe(ROUTED, 3, lines)
    between = [r for r in of(lines, "logits")
               if r["compared"].endswith("served vs program plain")]
    assert len(between) == 2
    assert [r["compared"].split(":")[0] for r in between
            if r.get("skipped") == "chose differently"] == skipped
    # the swapped position, and whichever later ones its keys and values
    # then moved across a tie of their own
    assert all(r["positions_that_differ"] >= 1 for r in between
               if "skipped" in r)
    plain, = of(lines, "choices", "program plain vs reference")
    assert plain["positions_not_the_references_own"] == 1
    # the swap reaches for the best expert not kept: a near tie on some
    # seeds, a wrong choice on others. Either way each path is judged under
    # its own sets, and the plain path's logits agree with the reference
    assert all(r["ok"] for r in of(lines, "logits"))
    assert ok == (plain["shortfall_max"] <= sc.CHOICE_TOL)


def test_the_recorder_hands_out_prefills_and_the_decode_steps_shapes():
    conf, cfg, ecfg, params = rehearsal(ROUTED, 11)
    ref = sc.load_reference(conf)
    tokens = jnp.arange(3, 43, dtype=jnp.int32)
    with record_choices() as chosen:
        jax.jit(lambda p, t: decoder.prefill_chunk(p, cfg, t)[0])(
            params, tokens[None])
        (sets,) = chosen.calls()
    assert sets.shape == (cfg.n_layers, 40, cfg.n_experts_used)
    own = ref.run(params, conf, tokens)[1]
    assert np.array_equal(sets, np.asarray(own))
    assert decoder._moe_gates.__module__ == decoder.__name__
    assert not hasattr(decoder._moe_gates, "__wrapped__")


# -- (iii) planted faults -----------------------------------------------------

def keep_the_smallest(cfg, lp, xf):
    logits = jnp.einsum("nd,de->ne", xf, lp["router"],
                        preferred_element_type=jnp.float32)
    topw, topi = jax.lax.top_k(-logits, cfg.n_experts_used)
    rows = jnp.arange(xf.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, topi].set(
        jax.nn.softmax(-topw, axis=-1))


def uniform_gates(inner):
    def gates(cfg, lp, xf):
        g = inner(cfg, lp, xf)
        return jnp.where(g > 0, 1.0 / cfg.n_experts_used, 0.0)
    return gates


def drop_the_best_expert(inner):
    def experts(cfg, lp, xf, gates):
        rows = jnp.arange(xf.shape[0])
        return inner(cfg, lp, xf, gates.at[rows, gates.argmax(1)].set(0.0))
    return experts


def drop_the_shared_expert(inner):
    def experts(cfg, lp, xf, gates):
        return inner(cfg, {k: v for k, v in lp.items()
                           if not k.startswith(("we_sh_", "sh_gate"))},
                     xf, gates)
    return experts


def float8_activations(inner):
    def norm(cfg, x, w, b=None):
        y = inner(cfg, x, w, b)
        return y.astype(jnp.float8_e4m3fn).astype(y.dtype)
    return norm


FAULTS = {
    # name: (function of the program, its replacement, the line that fails)
    "router keeps the k smallest": (
        "_moe_gates", lambda inner: keep_the_smallest, "choices"),
    "gates uniform": ("_moe_gates", uniform_gates, "logits"),
    "one chosen expert's output zeroed": (
        "_moe_experts", drop_the_best_expert, "logits"),
    "the shared expert dropped": (
        "_moe_experts", drop_the_shared_expert, "logits"),
    "activations through float8": ("_norm", float8_activations, "logits"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_by_the_line_meant_to_catch_it(
        fault, lines, short_probe, monkeypatch):
    name, make, caught_by = FAULTS[fault]
    monkeypatch.setattr(decoder, name, make(getattr(decoder, name)))
    ok, _cfg = run_probe(ROUTED, 3, lines)
    assert not ok
    served = "served vs reference"
    failed = {r["phase"] for r in lines
              if r.get("ok") is False and served in r.get("compared", "")}
    assert caught_by in failed, lines
    if caught_by == "choices":
        # wrong members, rightly gated and summed: the logits agree with the
        # reference under those sets, and only the shortfall tells
        assert failed == {"choices"}
        assert of(lines, "choices", served)[0]["shortfall_max"] > 0.25


# -- (iv) what has no choice to record takes the parent's path ---------------

@pytest.mark.parametrize("name", ["starcoder2-3b", "phi-2"])
def test_a_dense_configuration_is_probed_as_before(name, lines, short_probe):
    path = os.path.join(BENCH, "configs", name + ".json")
    ok, _cfg = run_probe(path, 9, lines)
    assert ok
    first = of(lines, "logits")
    assert len(first) == 4 and not of(lines, "choices")
    with record_choices():
        ok, _cfg = run_probe(path, 9, lines)
    assert ok and of(lines, "logits") == first


def test_a_dense_toy_and_its_held_sizes_are_the_parents():
    conf = sc.load_conf(os.path.join(BENCH, "configs", "phi-2.json"), True)
    assert sc.toy_fields(conf) == dict(
        dim=64, n_layers=2, n_heads=8, head_dim=16, ffn_dim=128,
        vocab_size=4096, max_seq_len=512, n_kv_heads=8, sliding_window=0)
    assert {k: conf[k] for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "max_position_embeddings", "max_seq_len")} == dict(
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=8, num_key_value_heads=8, head_dim=16,
        vocab_size=4096, max_position_embeddings=512, max_seq_len=512)
    gqa = sc.load_conf(os.path.join(BENCH, "configs", "starcoder2-3b.json"),
                       True)
    assert sc.toy_fields(gqa)["n_kv_heads"] == 2 == gqa["num_key_value_heads"]
    for name in ("phi-2", "starcoder2-3b"):
        full = sc.load_conf(os.path.join(BENCH, "configs", name + ".json"),
                            False)
        cfg = sc.model_config(full, False)
        assert cfg.dim == full["hidden_size"]


def test_the_fixtures_own_holds_and_toy_are_used():
    with pytest.raises(sc.ChildFailure, match="dim=64 is not"):
        sc.model_config(sc.load_conf(ROUTED, False), False)
    conf = sc.load_conf(ROUTED, True)
    cfg = sc.model_config(conf, True)
    assert (cfg.n_experts, cfg.n_experts_used, cfg.n_shared_ffn) == (16, 4, 32)
    assert conf["n_routed_experts"] == 16 and conf["hidden_size"] == 64
    conf["n_routed_experts"] = 8
    with pytest.raises(sc.ChildFailure, match="n_experts=16 is not"):
        sc.model_config(conf, True)


def test_the_program_traces_as_before_once_a_recorder_has_closed():
    """With no recorder open the lowered text of tiny-moe's prefill and paged
    decode is what it was before one was ever opened; inside a block it
    carries the callback."""
    from ollama_operator_tpu.runtime.engine import Engine
    conf, cfg, ecfg, params = rehearsal(ROUTED, 1, kernels="xla")
    eng = Engine(cfg, params, mesh=None,
                 ecfg=dataclasses.replace(ecfg, n_pages=sc.PROBE_PAGES))
    tokens = np.arange(3, 35, dtype=np.int32)
    eng.admit(0, tokens)
    assert not eng.prepare_decode(1)
    nblk = -(-eng.max_seq // eng.ecfg.page_size)
    step = np.full((eng.n_slots, 1), 7, np.int32)
    args = (eng.params, eng.k_cache, eng.v_cache, eng._gr(tokens[None]),
            eng._g(step, eng._slot_sh2), eng._tables_dev(), eng.lengths)

    def text():
        def both(p, kc, vc, toks, step_tokens, tables, lengths):
            pre = decoder.prefill_chunk(p, eng.cfg, toks)[0]
            dec = decoder.forward_with_cache_paged(
                p, eng.cfg, step_tokens, kc, vc, tables, lengths, nblk)[0]
            return pre, dec
        return jax.jit(both).lower(*args).as_text()

    before = text()
    with record_choices():
        inside = text()
    assert "callback" in inside and "callback" not in before
    assert text() == before
