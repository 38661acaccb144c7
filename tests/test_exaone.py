"""A stack of window and full attention (exaone_moe): window layers whose keys
and values are a ring of ``sliding_window`` positions a slot beside
full-attention layers whose rows are full length, in the hybrid scan; one
leading dense layer; a sigmoid router with a scaling factor and a shared
expert, of whose experts the chip holds a share. CPU, the toy of the same
shape (``tiny-exaone``: window 8, so a few dozen positions wrap the rings
several times), seeded weights; the plain reference is the benchmark's
(``benchmark/configs/k-exaone-236b-a23b.reference.py``: full-length keys under
a window mask, no ring), read at the toy's sizes through the configuration
file's own ``holds``."""

import dataclasses
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.ops import quant_cache as QC
from ollama_operator_tpu.runtime import accounting
from ollama_operator_tpu.runtime import engine as englib
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

from benchmark import server_child, work
from test_hybrid import drain, make_stack, manual, run_to_end, uninterrupted

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONF_PATH = os.path.join(REPO, "benchmark", "configs",
                         "k-exaone-236b-a23b.json")
CELL = "k-exaone-236b-a23b.decode-long"
CFG = cfglib.PRESETS["tiny-exaone"]
BIG = cfglib.PRESETS["k-exaone-236b-a23b"]
W = CFG.sliding_window
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
SEEDED = SlotOptions(temperature=0.9, seed=1234, repeat_penalty=1.0)


def conf_of(cfg):
    """The configuration file's dict at ``cfg``'s sizes: each key the file
    holds the preset to, read back from the config."""
    conf = work.load_conf(CONF_PATH)
    for ours, theirs in conf["holds"]:
        conf[theirs] = getattr(cfg, ours)
    conf["layer_types"] = ["full_attention" if c == "A"
                           else "sliding_attention" for c in cfg.layer_kinds]
    return conf


@pytest.fixture(scope="module")
def ref():
    return server_child.load_reference(work.load_conf(CONF_PATH))


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, CFG.vocab_size, (n,)
                                                ).astype(np.int32)


def make_engine(params, slots=4, cache=jnp.float32, **kw):
    return Engine(CFG, params, ecfg=EngineConfig(
        max_slots=slots, max_seq_len=128, cache_dtype=cache, decode_chunk=4,
        min_prefill_bucket=16, **kw))


def rings_of(eng, slot):
    """Every leaf of one slot's rings (keys then values; codes and scales
    where the cache is int8), as host arrays."""
    win = decoder.split_state(eng.k_cache, eng.v_cache)[2][2]
    return [np.asarray(a[:, slot]) for a in jax.tree_util.tree_leaves(win)]


def empty_cache(B, S, cache="float32"):
    """(K, V) trees of ``B`` empty slots of ``S`` positions."""
    La = CFG.n_full_layers
    if cache == "int8":
        kc, vc = (QC.empty_cache(La, B, CFG.n_kv_heads, S, CFG.head_dim)
                  for _ in range(2))
    else:
        kc = vc = jnp.zeros((La, B, CFG.n_kv_heads, S, CFG.head_dim))
    return decoder.join_state(kc, vc, decoder.empty_state(
        CFG, B, jnp.int8 if cache == "int8" else jnp.float32))


# -- the configuration ---------------------------------------------------

def test_preset_is_the_published_shape():
    """The served preset against the configuration's file, key by key (the
    benchmark's own check), every width against the catalog's row, the cut's
    floors and the issue's arithmetic."""
    conf = server_child.load_conf(CONF_PATH, False)
    cfg = server_child.model_config(conf, False)
    assert cfg is BIG and cfg.layer_kinds == "wwwAwwwA"
    assert (cfg.n_window_layers, cfg.n_full_layers, cfg.n_attn_layers) == (
        6, 2, 8)
    assert (cfg.n_dense_layers, cfg.n_routed_layers) == (1, 7)
    assert cfg.rope and cfg.rope_kinds == "w" and cfg.qk_norm
    assert not cfg.tie_embeddings and not cfg.shared_gate
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.moe_scale) == (
        "sigmoid", True, 2.5)
    # every width is the published one
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        6144, 64, 8, 128)
    assert (cfg.dense_ffn_dim, cfg.ffn_dim, cfg.n_shared_ffn) == (
        18432, 2048, 2048)
    assert (cfg.n_experts, cfg.n_experts_used, cfg.sliding_window) == (
        128, 8, 128)
    # whole periods of the published pattern, and the floors of a cut
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert conf["layer_types"] == period * 2
    assert conf["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert conf["sliding_windows"] == [128, 128, 128, 0] * 2
    assert conf["published"]["num_hidden_layers"] == 48
    assert cfg.n_routed_layers >= 4 and cfg.experts_held >= 8
    assert sorted(conf["reduced"]) == sorted([
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "sliding_windows", "num_experts", "vocab_size",
        "max_position_embeddings", "num_nextn_predict_layers"])
    assert set(conf["reduced"]) <= set(conf["reduced_why"])
    # the issue's count: attention 113.25M, an expert 37.75M, 16 held, the
    # shared expert, the router; the dense layer; the held rows twice
    attn = 2 * 6144 * 8192 + 2 * 6144 * 1024
    expert = 3 * 6144 * 2048
    assert cfg.n_params == (8 * attn + 3 * 6144 * 18432
                            + 7 * (17 * expert + 6144 * 128)
                            + 2 * 19200 * 6144)
    assert 11.95e9 < 2 * cfg.n_params < 11.97e9


def test_n_params_counts_what_init_params_makes():
    """The sizing formula against the leaves themselves, for the three
    hybrid toys: every matrix of this stack exactly (the leading dense
    layer's at its own width); what it leaves out elsewhere is the
    convolutions' taps and the norms' vectors (under a fiftieth)."""
    for name in ("tiny-exaone", "tiny-lfm2", "tiny-hybrid"):
        cfg = cfglib.PRESETS[name]
        shapes = jax.eval_shape(
            lambda k, cfg=cfg: decoder.init_params(cfg, k),
            jax.random.PRNGKey(0))
        every = sum(int(np.prod(a.shape))
                    for a in jax.tree_util.tree_leaves(shapes))
        matrices = sum(
            int(np.prod(a.shape)) for k, a in shapes["layers"].items()
            if a.ndim >= 3) + sum(
            int(np.prod(a.shape)) for k, a in shapes.items()
            if k != "layers" and a.ndim == 2)
        if name == "tiny-exaone":
            assert cfg.n_params == matrices
        assert abs(cfg.n_params - every) < 0.02 * every, name


def test_validate_accepts_and_refuses():
    """``w`` beside ``A`` and nothing else, with a window; every other
    refusal stands and names the field it refused."""
    ok = dataclasses.replace(CFG, layer_kinds="wAwAwwwA").validate()
    assert ok.n_window_layers == 5
    for bad, msg in (
            (dict(sliding_window=0), "need sliding_window"),
            (dict(layer_kinds="wwwAcwwA"), "beside full attention alone"),
            (dict(layer_kinds="wwwAmwwA", ssm_heads=4), "beside full attention alone"),
            (dict(layer_kinds="wwwwwwww"), "no attention layer"),
            (dict(layer_kinds="AAAAAAAA"), 'has no "w"'),
            (dict(post_norms=True), "post_norms is set"),
            (dict(parallel_block=True), "parallel_block is set"),
            (dict(altern_sliding=True), "altern_sliding is set"),
            (dict(rope_kinds="c"), "rope_kinds")):
        with pytest.raises(AssertionError, match=msg):
            dataclasses.replace(CFG, **bad).validate()
    with pytest.raises(AssertionError, match="rope_kinds"):
        dataclasses.replace(cfglib.PRESETS["tiny"], rope_kinds="w").validate()
    with pytest.raises(AssertionError, match="sliding_window in a hybrid"):
        dataclasses.replace(cfglib.PRESETS["tiny-lfm2"],
                            sliding_window=8).validate()


# -- the model against the reference -----------------------------------

@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_prefill_then_decode_through_the_rings(ref, params, cache):
    """Prefill 20 positions (the rings already wrapped twice at a window of
    8), then 28 decode steps, each position's logits against the
    reference's full forward pass over full-length keys under a window mask.
    Float32 on both sides differs by the order of sums: 2e-4 of the largest
    logit, a fiftieth of the least that leaving out a part moves (the test
    below). Through the int8 cache keys and values carry 1/254 of their
    row's largest entry: 3e-2, lfm2's stated tolerance."""
    toks = tokens(48)
    want = np.asarray(ref.forward(params, conf_of(CFG), jnp.asarray(toks)))
    scale = np.abs(want).max()
    logits, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None, :20])
    assert set(ks) == set(vs) == {"kv", "win"}
    assert ks["kv"].shape[0] == CFG.n_full_layers
    assert ks["win"].shape == (CFG.n_window_layers, 1, CFG.n_kv_heads, W,
                               CFG.head_dim)
    assert np.abs(np.asarray(logits[0]) - want[:20]).max() < 2e-4 * scale
    K, V = empty_cache(1, 64, cache)
    kc, vc, state = decoder.split_state(ks, vs)
    if cache == "int8":
        for c, new in ((K, kc), (V, vc)):
            q, s = QC.quantize_kv(new)
            c["q"] = c["q"].at[:, :, :, :20].set(q)
            c["s"] = c["s"].at[:, :, :, :20].set(s)
        K, V = decoder.join_state(
            {"q": K["q"], "s": K["s"]}, {"q": V["q"], "s": V["s"]},
            decoder.quantize_rings(state))
        tol = 3e-2
    else:
        K, V = decoder.join_state(K["kv"].at[:, :, :, :20].set(kc),
                                  V["kv"].at[:, :, :, :20].set(vc), state)
        tol = 2e-4
    step = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
        p, CFG, t, K, V, n))
    for i in range(20, 48):
        lg, K, V = step(params, toks[None, i:i + 1], K, V,
                        jnp.array([i], jnp.int32))
        assert np.abs(np.asarray(lg[0, 0]) - want[i]).max() < tol * scale, i


def test_each_new_part_moves_the_logits(ref, params):
    """The tolerance above can tell: a window one position wider, rotary
    embedding on the full layers too or on none, full attention in every
    layer, or no scaling of the gates, lies far outside it."""
    toks = jnp.asarray(tokens(40, seed=15))
    conf = conf_of(CFG)
    want = np.asarray(ref.forward(params, conf, toks))
    scale = np.abs(want).max()
    wider = ref.forward(params, {**conf, "sliding_window": W + 1}, toks)
    all_full = ref.forward(params, {**conf, "layer_types":
                                    ["full_attention"] * 8}, toks)
    unscaled = ref.forward(params, {**conf, "routed_scaling_factor": 1.0},
                           toks)
    for other in (wider, all_full, unscaled):
        assert np.abs(np.asarray(other) - want).max() > 1e-2 * scale
    # and the program's own switches: both kinds rotating, neither
    run = jax.jit(lambda p, t, cfg: decoder.prefill_chunk(p, cfg, t)[0],
                  static_argnums=2)
    for other in (dataclasses.replace(CFG, rope_kinds=""),
                  dataclasses.replace(CFG, rope=False)):
        got = np.asarray(run(params, toks[None], other)[0])
        assert np.abs(got - want).max() > 1e-2 * scale
    assert np.abs(np.asarray(run(params, toks[None], CFG)[0]) - want
                  ).max() < 2e-4 * scale


@pytest.mark.parametrize("pieces", [(44,), (20, 24), (5, 3, 36), (8, 8, 28),
                                    (1, 1, 42), (17, 1, 9, 17), (30, 14)])
def test_extends_that_cross_a_wrap_equal_one_prefill(params, pieces):
    """One prefill, and the same prompt through extends of the cache: pieces
    shorter than the window, of exactly the window, longer than it, of one
    position: the rings and the last logits agree."""
    toks = tokens(44, seed=1)
    want_l, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None])
    K, V = empty_cache(1, 64)
    at = 0
    for n in pieces:
        lg, K, V = decoder.forward_with_cache(
            params, CFG, toks[None, at:at + n], K, V,
            jnp.array([at], jnp.int32))
        at += n
    assert np.allclose(lg[0, -1], want_l[0, -1], atol=2e-6)
    assert np.allclose(K["win"], ks["win"], atol=1e-5)
    assert np.allclose(V["win"], vs["win"], atol=1e-5)


@pytest.mark.parametrize("n_valid", [1, 5, 8, 9, 16, 31])
def test_padded_positions_never_reach_a_ring(params, n_valid):
    """A prefill bucket pads the prompt: the rings and the last real
    position's logits are those of the unpadded prompt, and the padding's
    content is nothing to the rings, to the bit. An extend's padding
    likewise."""
    toks = tokens(32, seed=2)
    f = jax.jit(lambda p, t, n: decoder.prefill_chunk(p, CFG, t, n_valid=n))
    lg, ks, vs = f(params, toks[None], jnp.int32(n_valid))
    lg0, ks0, vs0 = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, toks[None, :n_valid])
    assert np.allclose(ks["win"], ks0["win"], atol=1e-6)
    assert np.allclose(vs["win"], vs0["win"], atol=1e-6)
    assert np.allclose(lg[0, 0], lg0[0, -1], atol=2e-6)
    other = toks.copy()
    other[n_valid:] = (other[n_valid:] + 7) % CFG.vocab_size
    _, ks1, vs1 = f(params, other[None], jnp.int32(n_valid))
    assert np.array_equal(ks["win"], ks1["win"])
    assert np.array_equal(vs["win"], vs1["win"])
    # an extend's padded bucket, 6 positions into the sequence: the block
    # past its n_valid real positions is nothing to the rings either
    K, V = empty_cache(1, 64)
    _, K, V = decoder.forward_with_cache(params, CFG, toks[None, :6], K, V,
                                         jnp.array([0], jnp.int32))
    g = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
        p, CFG, t, K, V, jnp.array([6], jnp.int32), n_valid=n))
    n = min(n_valid, 20)
    block, noise = toks[6:], toks[6:].copy()
    noise[n:] = (noise[n:] + 7) % CFG.vocab_size
    _, Ka, Va = g(params, block[None], K, V, jnp.array([n]))
    _, Kb, Vb = g(params, noise[None], K, V, jnp.array([n]))
    assert not np.array_equal(block, noise)
    assert np.array_equal(Ka["win"], Kb["win"])
    assert np.array_equal(Va["win"], Vb["win"])
    assert not np.array_equal(Ka["win"], K["win"])


@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_a_decode_step_leaves_inactive_slots_rings_alone(params, cache):
    """Slot 0 decodes; slot 1 is parked between prefill pieces, slot 2 was
    released, slot 3 never held anything: their rings keep their bits
    through a whole chunk, codes and scales."""
    eng = make_engine(params, cache=getattr(jnp, cache))
    eng.admit(0, tokens(10), GREEDY)
    eng.admit(1, tokens(16, seed=6), GREEDY)
    eng.release(1, park=True)
    eng.admit(2, tokens(5, seed=7), GREEDY)
    eng.release(2)
    before = [rings_of(eng, s) for s in range(4)]
    eng.decode_n(4)
    after = [rings_of(eng, s) for s in range(4)]
    for s in (1, 2, 3):
        for a, b in zip(before[s], after[s]):
            assert np.array_equal(a, b), s
    assert not all(np.array_equal(a, b)
                   for a, b in zip(before[0], after[0]))
    # and the parked slot goes on as if nothing had happened in between
    t = eng.extend(1, tokens(30, seed=6), 16, GREEDY)
    fresh = make_engine(params, cache=getattr(jnp, cache))
    t_fresh = fresh.admit(1, tokens(30, seed=6), GREEDY)
    exact = cache == "float32"
    assert t == t_fresh or not exact
    for a, b in zip(rings_of(eng, 1), rings_of(fresh, 1)):
        if a.dtype == np.int8:
            assert np.abs(a.astype(int) - b.astype(int)).max() <= 8
        else:
            assert np.allclose(a, b, atol=1e-6 if exact else 1e-2)


def test_admit_many_rows_keep_their_own_rings(params):
    """Batched admission: each row's rings end at its own prompt's end."""
    eng = make_engine(params)
    a, b = tokens(9, seed=4), tokens(14, seed=5)
    eng.admit_many([0, 2], [a, b], [GREEDY, GREEDY])
    one = make_engine(params)
    one.admit(1, b, GREEDY)
    for x, y in zip(rings_of(eng, 2), rings_of(one, 1)):
        assert np.allclose(x, y, atol=1e-6)
    assert not np.allclose(rings_of(eng, 0)[0], rings_of(eng, 2)[0],
                           atol=1e-3)


def test_the_engine_serves_the_references_greedy_stream(ref, params):
    """admit + chunked decode through the engine's own programs, three
    wraps of the rings: the greedy stream is the reference's, token by
    token."""
    eng = make_engine(params)
    prompt = tokens(21, seed=3)
    got = [eng.admit(1, prompt, GREEDY)]
    for _ in range(6):
        got += [int(t) for t in eng.decode_n(4)[:, 1]]
    conf = conf_of(CFG)
    fwd = jax.jit(lambda p, t: ref.forward(p, conf, t))
    seq, want = np.zeros((48,), np.int32), []
    seq[:21] = prompt
    for n in range(21, 21 + len(got)):
        # the model is causal: position n - 1 of a padded sequence reads
        # what the sequence of n would
        want.append(int(jnp.argmax(fwd(params, jnp.asarray(seq))[n - 1])))
        seq[n] = want[-1]
    assert got == want


def test_extend_refuses_to_cut_the_rings_back(params):
    eng = make_engine(params)
    eng.admit(0, tokens(20), GREEDY)
    eng.release(0, park=True)
    with pytest.raises(ValueError, match="cannot be cut back"):
        eng.extend(0, tokens(30), 12, GREEDY)


def test_the_benchmarks_probe_passes_on_the_toy():
    """``server_child.probe`` as the cell runs it (both paths under their own
    sets, the decode step through the engine's own cache trees and rings),
    on the CPU at the toy's sizes: the calling convention the harness
    fixes."""
    conf = server_child.load_conf(CONF_PATH, True)
    cfg = server_child.model_config(conf, True)
    assert cfg.sliding_window == 8 and conf["sliding_window"] == 8
    p = decoder.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=4, max_seq_len=128, decode_chunk=4,
                        cache_dtype=jnp.int8, paged=False,
                        min_prefill_bucket=16)
    assert server_child.probe(cfg, ecfg, p, conf, seed=7)
    said = server_child.COMPARED
    assert said["shortfall_served_vs_reference"]["value"] <= 0.08
    assert said["prefill_served_vs_reference"]["value"] < 0.03


@pytest.mark.parametrize("program", ["prefill", "decode", "extend"])
def test_lowered_programs_carry_the_new_scope(params, program):
    """``attn.window`` around the ring's write and attention, ``attn.core``
    and ``attn.kv_write`` around the full layers', beside the expert
    scopes: what ``window_spans.py`` and ``trace_spans.py`` find."""
    from ollama_operator_tpu.runtime.trace import DEVICE_SCOPES
    if program == "prefill":
        low = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t)).lower(
            params, tokens(16)[None])
    else:
        T = 1 if program == "decode" else 4
        K, V = empty_cache(2, 32, "int8")
        low = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
            p, CFG, t, K, V, n, route_live=n)).lower(
            params, tokens(2 * T).reshape(2, T), K, V,
            jnp.array([3, 0], jnp.int32))
    text = low.as_text(debug_info=True)
    found = {s for s in DEVICE_SCOPES
             if re.search(r'[/"]' + re.escape(s) + r'[/"]', text)}
    assert found >= {"attn.window", "attn.qkv", "attn.core", "attn.out",
                     "mlp", "moe.route", "moe.experts", "lm_head", "embed"}
    assert ("attn.kv_write" in found) == (program != "prefill")
    assert not {s for s in found if s.startswith(("ssm.", "conv."))}


def test_one_stack_of_projections_serves_both_kinds(params):
    """``w`` and ``A`` layers read rows of ONE [8, ...] stack in layer
    order; the cache has two full rows and six rings; the dense layer's
    scan and the routed layers' each get their own feed-forward."""
    assert params["layers"]["wq"].shape[0] == 8
    assert params["layers"]["we_gate"].shape[:2] == (7, CFG.experts_held)
    assert params["layers"]["w_gate"].shape == (1, CFG.dim,
                                                CFG.dense_ffn_dim)
    assert decoder._hybrid_rows(CFG) == (
        [False, False, False, True] * 2, [0, 1, 2, 0, 3, 4, 5, 1],
        list(range(8)))
    assert decoder._hybrid_rows(cfglib.PRESETS["tiny-lfm2"])[2] is None
    jaxpr = jax.make_jaxpr(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, tokens(8)[None])
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [1, 7]


# -- the chip's share -----------------------------------------------------

@pytest.mark.parametrize("who", ["program", "reference"])
@pytest.mark.parametrize("shares", [2, 8])
def test_the_shares_add_up_to_the_uncut_layer(ref, who, shares):
    """The toy's 8 experts in ``shares`` equal shares (8 shares of one
    expert: the deployment's count), each with the shared expert added
    whole: their sum, the shared expert counted once, is the uncut layer of
    the reference. Attention is replicated, so it is counted once by
    construction: a share's attention is the layer's."""
    full = dataclasses.replace(CFG, n_experts_held=CFG.n_experts)
    p = decoder.init_params(full, jax.random.PRNGKey(2), dtype=jnp.float32)
    lp_all, i, r = p["layers"], 3, 2
    h = jax.random.normal(jax.random.PRNGKey(3), (11, CFG.dim), jnp.float32)
    want, _, _ = ref.expert_layer(lp_all, conf_of(full), h, i, r)
    held = CFG.n_experts // shares

    def share(first):
        cfg = dataclasses.replace(CFG, n_experts_held=held,
                                  expert_first=first)
        cut = {k: (v[:, first:first + held]
                   if k in ("we_gate", "we_up", "we_down") else v)
               for k, v in lp_all.items()}
        if who == "reference":
            return ref.expert_layer(cut, conf_of(cfg), h, i, r)[0]
        lp = {k: v[r] for k, v in cut.items()
              if v.shape[0] == CFG.n_routed_layers}
        u = decoder._norm(cfg, h[None], lp_all["mlp_norm_w"][i])
        return decoder._moe_mlp(cfg, lp, u)[0]

    u = np.asarray(decoder._norm(CFG, h, lp_all["mlp_norm_w"][i]))
    shared = (jax.nn.silu(u @ lp_all["we_sh_gate"][r])
              * (u @ lp_all["we_sh_up"][r])) @ lp_all["we_sh_down"][r]
    parts = [share(first) for first in range(0, CFG.n_experts, held)]
    got = sum(parts) - (shares - 1) * shared
    assert np.abs(np.asarray(got - want)).max() < 1e-5 * np.abs(want).max()
    # and a share alone is not the layer
    assert np.abs(np.asarray(parts[0] - want)).max() > 1e-3 * np.abs(
        want).max()


# -- the scheduler ------------------------------------------------------

@pytest.fixture(scope="module")
def shared_engine(params):
    """One two-slot engine for the scheduler tests: its programs compile
    once; every test leaves its slots released."""
    return make_engine(params, slots=2)


@pytest.mark.parametrize("opts", [GREEDY, SEEDED], ids=["greedy", "seeded"])
def test_preempt_and_resume_give_the_uninterrupted_stream(shared_engine, opts):
    prompt = tokens(9, seed=8)
    want = uninterrupted(shared_engine, prompt, opts, 30)
    eng, sched = make_stack(shared_engine)
    manual(sched)
    try:
        r = sched.submit(prompt, opts, max_tokens=30)
        got = {r: []}
        for _ in range(3):
            sched._step()
        sched._drain_pending()
        got[r] += drain(r)
        assert 0 < len(got[r]) < 30
        sched._preempt_slot(r.slot, cause="test")
        run_to_end(sched, [r], got)
        assert sched.n_preemptions == 1
        assert got[r] == want
    finally:
        sched.shutdown()


def test_chunked_prefill_through_the_scheduler(shared_engine):
    """A prompt admitted in 16-token pieces (two windows each), decode
    dispatches of another stream in between: the one-shot stream."""
    long, short = tokens(50, seed=10), tokens(6, seed=11)
    want = uninterrupted(shared_engine, long, GREEDY, 8)
    eng, sched = make_stack(shared_engine, prefill_chunk=16)
    try:
        other = sched.submit(short, GREEDY, max_tokens=40)
        r = sched.submit(long, GREEDY, max_tokens=8)
        assert list(r.tokens()) == want
        list(other.tokens())
    finally:
        sched.shutdown()


def test_the_rules_of_a_state_that_only_advances_apply(shared_engine, params):
    """No page pool, no mesh, a parked prefix reused only whole: the rules
    a recurrent stack has, unchanged."""
    with pytest.raises(ValueError, match="contiguous cache"):
        make_engine(params, paged=True, page_size=16)
    eng, sched = make_stack(shared_engine)
    manual(sched)
    try:
        assert eng.recurrent
        base = [int(t) for t in tokens(24, seed=12)]
        req = type("R", (), {})()
        req.embeds = None
        sched.min_prefix_reuse = 4
        eng.admit(0, np.asarray(base, np.int32), GREEDY)
        eng.release(0, park=True)
        sched._parked[0] = base
        req.admit_ids = base + [5, 6, 7]
        assert sched._best_prefix(req) == (0, 24)
        req.admit_ids = base[:20] + [9, 9, 9, 9, 9, 9]
        assert sched._best_prefix(req) == (None, 0)
    finally:
        sched._parked.clear()
        eng.release(0)
        sched.shutdown()


# -- serving defaults, accounting, metrics ------------------------------

def test_zero_config_resolution_on_the_chip(monkeypatch):
    """bfloat16 weights, int8 contiguous cache, chunk 32 and the slots
    ``_recurrent_slots`` gives from the model alone: four tokens an expert
    a step at 8 of 128 kept is 64, and 64 rings are 104 MB."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert englib.resolve_engine_dtype(BIG, "tpu") == "bfloat16"
    ecfg = englib.resolve_serving_defaults(
        EngineConfig(max_slots=0, max_seq_len=4096, decode_chunk=0,
                     paged=None, page_size=0, n_pages=None,
                     cache_dtype=jnp.int8), BIG, None)
    assert (ecfg.paged, ecfg.max_slots, ecfg.decode_chunk) == (False, 64, 32)
    assert englib._recurrent_slots(BIG) == 64
    assert BIG.window_ring_bytes == 6 * 128 * 2112 and BIG.ssm_state_bytes == 0
    # rings that would not fit an eighth of the chip halve the slots, as a
    # recurrent state does
    huge = dataclasses.replace(BIG, sliding_window=32768, max_seq_len=65536)
    assert 64 * huge.window_ring_bytes > 2 << 30
    assert englib._recurrent_slots(huge) < 64
    conf = work.load_conf(CONF_PATH)
    want = conf["expected_resolution"]
    assert (want["paged"], want["max_slots"], want["decode_chunk"]) == (
        ecfg.paged, ecfg.max_slots, ecfg.decode_chunk)
    assert conf["saturating_clients"] == ecfg.max_slots


def test_the_presets_cache_is_two_full_rows_and_six_rings():
    """The served cache by shapes alone (nothing is allocated): a slot holds
    2 x 4,096 + 6 x 128 positions of 2 KiB of int8 keys and values and 64
    bytes of scales; a window layer with a full row would make it 69 MB."""
    full = jax.eval_shape(lambda: QC.empty_cache(
        BIG.n_full_layers, 64, BIG.n_kv_heads, 4096, BIG.head_dim))
    rings = jax.eval_shape(lambda: decoder.empty_state(BIG, 64, jnp.int8))

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree))
    assert rings[0] is None and rings[1] is None
    a_position = 2048 + 64
    assert 2 * nbytes(full) == 64 * 2 * 4096 * a_position
    assert nbytes(rings) == 64 * 6 * 128 * a_position == 64 * BIG.window_ring_bytes
    per_slot = (2 * nbytes(full) + nbytes(rings)) / 64
    assert per_slot == (2 * 4096 + 6 * 128) * a_position == 18_923_520
    assert 8 * 4096 * a_position == 69_206_016


def test_accounting_prices_the_two_kinds():
    d = 6144
    attn = 2 * (2 * d * 8192 + 2 * d * 1024)
    moe = (8 * 16 / 128) * 6 * d * 2048 + 2 * d * 128 + 6 * d * 2048
    assert accounting.per_token_flops(BIG) == pytest.approx(
        8 * attn + 6 * d * 18432 + 7 * moe + 2 * d * 19200)
    # two full layers see every position, six window layers 128 at most
    assert accounting._layer_split(BIG) == (2, 6)
    assert accounting.attn_span_flops(BIG, 0, 1) == 8 * 4.0 * 8192
    assert accounting.attn_span_flops(BIG, 1000, 1) == (
        2 * 1001 + 6 * 128) * 4.0 * 8192
    # the stacks that were there keep their split
    assert accounting._layer_split(cfglib.PRESETS["lfm2-8b-a1b"]) == (4, 0)
    assert accounting._layer_split(cfglib.PRESETS["mistral"]) == (0, 32)


def test_cache_gauge_and_ps_details(params, monkeypatch):
    monkeypatch.setenv("TPU_WARM_BUCKETS", "0")   # nothing is served here
    from ollama_operator_tpu.runtime.service import LoadedModel
    from ollama_operator_tpu.tokenizer.tokenizer import Tokenizer
    tok = Tokenizer("llama", [f"t{i}" for i in range(CFG.vocab_size)],
                    bos_id=1, eos_id=2)
    lm = LoadedModel("exaone", CFG, params, tok, ecfg=EngineConfig(
        max_slots=2, max_seq_len=64, cache_dtype=jnp.int8,
        min_prefill_bucket=16))
    try:
        a_position = 2 * CFG.n_kv_heads * (CFG.head_dim + 4)
        want = {"full": 2 * CFG.n_full_layers * 64 * a_position,
                "window": 2 * CFG.n_window_layers * W * a_position,
                "state": 0}
        assert want["window"] == 2 * CFG.window_ring_bytes
        assert lm.engine.cache_bytes == want
        assert lm.engine.state_bytes == want["window"]
        assert lm.engine.kv_bytes == want["full"] + want["window"]
        text = METRICS.render().replace(".0", "")
        for kind, n in want.items():
            assert f'tpu_model_cache_bytes{{kind="{kind}"}} {n}' in text
    finally:
        lm.unload()
    assert not re.search(r"^tpu_model_cache_bytes", METRICS.render(), re.M)


# -- the benchmark's readers and arithmetic ------------------------------

NEW_READERS = ("decode_window_attn_ms_per_step", "window_attn_roofline",
               "kv_cache_mb_per_slot")


def reader_ctx(conf, before=None, after=None):
    return types.SimpleNamespace(
        conf=conf, notes={}, resolved={"decode_chunk": 2, "max_slots": 4,
                                       "weights": "bfloat16",
                                       "kv_dtype": "int8"},
        peaks={"hbm_bytes_per_s": 819e9}, trace_before=before or {},
        trace_after=after or {}, before=before or {}, after=after or {})


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_none_on_the_parents_program(name, tmp_path,
                                                    monkeypatch):
    """The driver runs the new readers on the parent's program too, which has
    neither the scope nor the gauge: nothing to read is None, no error."""
    from benchmark import run, trace_spans
    monkeypatch.setattr(trace_spans.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    assert run.layer_reader(name).read(
        reader_ctx(work.load_conf(CONF_PATH))) is None


def test_window_spans_and_the_roofline_read_a_trace(tmp_path, monkeypatch):
    """Two complete runs of a decode module of two steps each: self time
    under ``attn.window`` over the steps, and the rings' bytes over it; the
    full layers' ``attn.core`` is not among it; a trace without the scope
    reads None."""
    from benchmark import prom, run, trace_spans, window_spans
    from ollama_operator_tpu.server.metrics import Metrics
    meta = {1: ("jit__decode_n(7)", ""),
            2: ("%fusion.1 = f32[] fusion()",
                "jit(_decode_n)/attn.window/dot_general"),
            3: ("%fusion.2 = f32[] fusion()",
                "jit(_decode_n)/attn.window/scatter"),
            4: ("%fusion.3 = f32[] fusion()",
                "jit(_decode_n)/attn.core/dot_general")}

    def planes(with_window):
        ops = []
        for t0 in (0, 2000):
            ops += [(t0 + 100, t0 + 400, 2 if with_window else 4),
                    (t0 + 400, t0 + 600, 3 if with_window else 4),
                    (t0 + 600, t0 + 900, 4)]
        return [{"name": "/device:TPU:0", "meta": meta, "lines": [
            {"name": "XLA Modules", "events": [(0, 1000, 1), (2000, 3000, 1)]},
            {"name": "XLA Ops", "events": ops}]}]

    # four decode dispatches of two steps that advanced 3 sequences each
    reg = Metrics()
    before = prom.parse(reg.render())
    for _ in range(4):
        reg.observe("tpu_model_dispatch_seconds", 0.01, '{kind="decode"}')
    reg.inc("tpu_model_useful_tokens_total", 24.0, '{kind="decode"}')
    after = prom.parse(reg.render())
    conf = work.load_conf(CONF_PATH)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    for with_window in (True, False):
        window_spans._CACHE.clear()
        pl = planes(with_window)
        monkeypatch.setattr(trace_spans, "find_trace", lambda w=None: str(path))
        monkeypatch.setattr(trace_spans, "reduce",
                            lambda w=None, pl=pl: trace_spans.reduce_planes(pl))
        monkeypatch.setattr(trace_spans, "read_planes", lambda p, pl=pl: pl)
        got = window_spans.step_seconds(2)
        ctx = reader_ctx(conf, before, after)
        ms = run.layer_reader("decode_window_attn_ms_per_step").read(ctx)
        share = run.layer_reader("window_attn_roofline").read(ctx)
        if with_window:
            assert got == pytest.approx({"attn.window": 250e-12})
            assert ms == pytest.approx(250e-9)
            ring = 3 * 6 * 128 * 2112
            assert ctx.notes["window_attn_roofline"]["ring_bytes"] == ring
            assert share == pytest.approx(100 * ring / 819e9 / 250e-12)
        else:
            assert got is None and ms is None and share is None


def test_kv_cache_mb_per_slot_reads_the_gauge():
    """The reader over a scrape of a real registry's text: full rows and
    rings over the resolved slots; the state's bytes are not keys and
    values."""
    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    for kind, n in (("full", 64e6), ("window", 8e6), ("state", 1e9)):
        reg.gauge_fn("tpu_model_cache_bytes", lambda n=n: n,
                     f'{{kind="{kind}"}}')
    ctx = reader_ctx({}, after=prom.parse(reg.render()))
    assert run.layer_reader("kv_cache_mb_per_slot").read(ctx) == 18.0


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    """One configuration, one cell under the new mix, the three new metrics
    on it alone, and the accepted expert metrics extended to it."""
    from benchmark import run
    cell = run.find_cell(CELL)
    assert (cell.chips, cell.mix_name) == (1, "decode-long")
    assert cell.conf["preset"] == "k-exaone-236b-a23b"
    assert cell.mix["clients"] == "saturating_clients"
    assert cell.conf["saturating_clients"] == 64
    # every context is at or past the window on every decode step; the
    # mix is the issue's: prompts to 512 tokens, two prefill pieces of the
    # zero-config server, within a step's budget of pieces for every slot
    assert cell.mix["prompt_tokens"]["lo"] >= cell.conf["sliding_window"]
    assert cell.mix["prompt_tokens"] == {"dist": "loguniform", "lo": 128,
                                         "hi": 512}
    assert cell.mix["output_tokens"] == {"dist": "uniform", "lo": 256,
                                         "hi": 768}
    piece = 8 * cell.conf["expected_resolution"]["decode_chunk"]
    assert piece < cell.mix["prompt_tokens"]["hi"] <= 2 * piece
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) | {"decode_moe_ms_per_step",
                               "moe_experts_roofline",
                               "moe_expert_load_spread"} <= names
    # the two full layers write their rows under attn.kv_write
    assert "decode_kv_write_ms_per_step" in names
    assert not {"decode_ssm_ms_per_step", "ssm_state_roofline",
                "decode_conv_ms_per_step"} & names
    for other in ("granite-4.0-h-small.decode-saturated",
                  "lfm2-8b-a1b.decode-saturated",
                  "starcoder2-3b.decode-saturated"):
        assert not set(NEW_READERS) & {
            m["name"] for m in run.find_cell(other).per_layer}


def test_the_configurations_work_arithmetic():
    """The sizes the issue reckons with, from the configuration's own file."""
    conf = work.load_conf(CONF_PATH)
    w = work.load_module(os.path.join(conf["_dir"], conf["work"]))
    assert (w.n_window(conf), w.n_full(conf), w.n_routed(conf)) == (6, 2, 7)
    assert w.attention_params(conf) == 113_246_208
    assert w.expert_params(conf) == w.shared_params(conf) == 37_748_736
    assert w.dense_params(conf) == 339_738_624
    assert w.router_params(conf) == 786_432
    # one position of one layer: 2 KiB of codes and 64 bytes of scales
    assert w.position_bytes(conf, "int8") == 2112
    assert w.window_bytes_step(conf, 56, "int8") == 56 * 6 * 128 * 2112
    # 56 tokens of 8 picks over 128 experts touch 97.3% of the 16 held
    assert w.distinct_experts(conf, 56) == pytest.approx(
        16 * (1 - (1 - 8 / 128) ** 56))
    assert w.experts_bytes_step(conf, 1e9, "bfloat16") == pytest.approx(
        7 * 16 * 37_748_736 * 2)
    total = work.weight_bytes_step(conf, 1e9, "bfloat16") \
        - w.window_bytes_step(conf, 1e9, "int8")
    assert total == pytest.approx(2 * (BIG.n_params - 19200 * 6144),
                                  rel=1e-9)        # the embedding is a lookup
    assert work.kv_bytes_per_token(conf, "int8") == 2 * 2112
    assert work.attn_flops_per_pair(conf) == 4 * 2 * 64 * 128
    # a token keeps 8 of 128 and one in eight of those is held here
    assert work.matmul_flops_per_token(conf) == pytest.approx(
        2 * (w.fixed_params(conf) + 7 * 1.0 * w.expert_params(conf)))
    # the step the issue reckons with: 56 sequences of about 700 positions
    step = work.decode_step(conf, 56.0, 56 * 700.0, "bfloat16", "int8")
    assert 11.7e9 < step["bytes"] < 12.1e9
