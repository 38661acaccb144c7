"""SmallThinker (smallthinker): a stack of full attention without positions and
window attention with rotary embedding, in that order (``A w w w``), whose
router reads the layer's INPUT ahead of attention and whose experts are
ReLU-gated; window layers whose rings are long, so a decode step writes them
a row a slot and reads them as deep as the live contexts reach. CPU, the toy
of the same shape (``tiny-smallthinker``: window 8, so sequences of 40 and
more wrap the rings several times), seeded weights; the plain reference is the
benchmark's (``benchmark/configs/smallthinker-21b-a3b.reference.py``:
full-length keys under a window mask, no ring, the router on the un-normed
layer input), read at the toy's sizes through the configuration file's own
``holds``."""

import dataclasses
import json
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
from ollama_operator_tpu.runtime import engine as englib
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

from benchmark import server_child, work
from test_hybrid import make_stack, uninterrupted

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONF_PATH = os.path.join(REPO, "benchmark", "configs",
                         "smallthinker-21b-a3b.json")
CELL = "smallthinker-21b-a3b.decode-deep"
CFG = cfglib.PRESETS["tiny-smallthinker"]
BIG = cfglib.PRESETS["smallthinker-21b-a3b"]
W = CFG.sliding_window
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)

# Float32 on both sides differs by the order of sums alone: 2e-4 of the
# largest logit (read: 5e-7), a three-hundredth of what either control below
# moves (6.6% and 6.8%). Through the int8 cache keys and values carry 1/254
# of their row's largest entry: 3e-2, the other hybrid stacks' stated
# tolerance, half of what the controls move.
TOL = {"float32": 2e-4, "int8": 3e-2}


def conf_of(cfg):
    """The configuration file's dict at ``cfg``'s sizes: each key the file
    holds the preset to, read back from the config."""
    conf = work.load_conf(CONF_PATH)
    for ours, theirs in conf["holds"]:
        conf[theirs] = getattr(cfg, ours)
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


def make_engine(params, cfg=CFG, slots=4, cache=jnp.float32, seq=128, **kw):
    return Engine(cfg, params, ecfg=EngineConfig(
        max_slots=slots, max_seq_len=seq, cache_dtype=cache, decode_chunk=4,
        min_prefill_bucket=16, **kw))


def rings_of(eng, slot):
    win = decoder.split_state(eng.k_cache, eng.v_cache)[2][2]
    return [np.asarray(a[:, slot]) for a in jax.tree_util.tree_leaves(win)]


def empty_cache(cfg, B, S, cache="float32"):
    La = cfg.n_full_layers
    if cache == "int8":
        kc, vc = (QC.empty_cache(La, B, cfg.n_kv_heads, S, cfg.head_dim)
                  for _ in range(2))
    else:
        kc = vc = jnp.zeros((La, B, cfg.n_kv_heads, S, cfg.head_dim))
    return decoder.join_state(kc, vc, decoder.empty_state(
        cfg, B, jnp.int8 if cache == "int8" else jnp.float32))


def filled_cache(cfg, params, toks, n, S, cache):
    """One slot's cache trees after a prefill of ``toks[:n]``."""
    _, ks, vs = jax.jit(lambda p, t: decoder.prefill_chunk(p, cfg, t))(
        params, toks[None, :n])
    K, V = empty_cache(cfg, 1, S, cache)
    kc, vc, state = decoder.split_state(ks, vs)
    if cache == "int8":
        for c, new in ((K, kc), (V, vc)):
            q, s = QC.quantize_kv(new)
            c["q"] = c["q"].at[:, :, :, :n].set(q)
            c["s"] = c["s"].at[:, :, :, :n].set(s)
        return decoder.join_state(
            {"q": K["q"], "s": K["s"]}, {"q": V["q"], "s": V["s"]},
            decoder.quantize_rings(state))
    return decoder.join_state(K["kv"].at[:, :, :, :n].set(kc),
                              V["kv"].at[:, :, :, :n].set(vc), state)


# -- the configuration ---------------------------------------------------

def test_preset_is_the_published_shape():
    """The served preset against the configuration's file, key by key (the
    benchmark's own check), every width against the catalog's row, the cut's
    floors and the issue's arithmetic."""
    conf = server_child.load_conf(CONF_PATH, False)
    cfg = server_child.model_config(conf, False)
    assert cfg is BIG and cfg.layer_kinds == "AwwwAwww"
    assert (cfg.n_window_layers, cfg.n_full_layers) == (6, 2)
    assert (cfg.n_dense_layers, cfg.n_shared_ffn, cfg.experts_held) == (
        0, 0, 64)
    assert cfg.rope and cfg.rope_kinds == "w" and not cfg.qk_norm
    assert not cfg.tie_embeddings
    assert (cfg.moe_score, cfg.moe_renorm, cfg.moe_router_input, cfg.act) == (
        "softmax", True, "block", "relu")
    # every width is the published one
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2560, 28, 4, 128)
    assert (cfg.ffn_dim, cfg.n_experts, cfg.n_experts_used) == (768, 64, 6)
    assert (cfg.sliding_window, cfg.vocab_size) == (4096, 151936)
    assert cfg.rope_theta == 1.5e6 and cfg.norm_eps == 1e-6
    # whole periods of the published layouts, which agree entry for entry
    assert conf["sliding_window_layout"] == conf["rope_layout"] == [
        0, 1, 1, 1] * 2
    assert "".join("w" if w else "A"
                   for w in conf["sliding_window_layout"]) == cfg.layer_kinds
    assert conf["published"]["num_hidden_layers"] == 52
    assert sorted(conf["reduced"]) == sorted([
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "max_position_embeddings"])
    assert set(conf["reduced"]) <= set(conf["reduced_why"])
    for key in ("router_input", "expert_act", "secondary_experts"):
        assert key in conf["assumed"]
    held = {ours for ours, _ in conf["holds"]}
    assert {"moe_router_input", "act", "moe_score", "sliding_window",
            "rope_kinds", "layer_kinds"} <= held
    # every number of the catalog's row under the same key, but the four
    # the cut reduces
    assert conf["source"].startswith(
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/")
    for key, value in dict(
            head_dim=128, hidden_size=2560, moe_ffn_hidden_size=768,
            moe_num_active_primary_experts=6, moe_num_primary_experts=64,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True,
            num_attention_heads=28, num_key_value_heads=4,
            rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1500000,
            sliding_window_size=4096, tie_word_embeddings=False,
            vocab_size=151936,
            model_name="smallthinker_21b_instruct").items():
        assert conf[key] == value, key
    # the issue's count: attention 20.97M, router 0.16M, 64 experts of 5.898M
    attn = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
    layer = attn + 2560 * 64 + 64 * 3 * 2560 * 768
    assert cfg.n_params == 8 * layer + 2 * 151936 * 2560 == 3_966_894_080
    assert 7.92e9 < 2 * cfg.n_params < 7.94e9


def test_validate_accepts_and_refuses():
    """The router's input stream is the hybrid scan's to honour; ReLU is an
    activation like the others."""
    with pytest.raises(AssertionError, match="hybrid stacks' scan"):
        dataclasses.replace(cfglib.PRESETS["tiny"], n_experts=4,
                            moe_router_input="block").validate()
    with pytest.raises(AssertionError):
        dataclasses.replace(CFG, moe_router_input="attn").validate()
    with pytest.raises(AssertionError):
        dataclasses.replace(CFG, act="relu2").validate()
    assert dataclasses.replace(cfglib.PRESETS["tiny"], act="relu").validate()


# -- the model against the reference -----------------------------------

def test_prefill_logits_are_the_references(ref, params):
    """44 positions, five and a half windows: every position's logits."""
    toks = tokens(44, seed=2)
    want = np.asarray(ref.forward(params, conf_of(CFG), jnp.asarray(toks)))
    logits, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None])
    assert ks["win"].shape == (CFG.n_window_layers, 1, CFG.n_kv_heads, W,
                               CFG.head_dim)
    assert ks["kv"].shape[0] == CFG.n_full_layers == 2
    assert np.abs(np.asarray(logits[0]) - want).max() < (
        TOL["float32"] * np.abs(want).max())


@pytest.mark.parametrize("form", ["select", "rows"])
@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_prefill_then_decode_through_the_rings(ref, params, cache, form,
                                               monkeypatch):
    """Prefill 20 positions (the rings already wrapped twice at a window of
    8), then 28 decode steps through the cache, each position's logits
    against the reference's full forward pass over full-length keys under a
    window mask; the ring advanced by the select (a short ring's form) and a
    row a slot (a long ring's)."""
    monkeypatch.setattr(decoder, "_RING_SELECT_MAX",
                        0 if form == "rows" else W)
    toks = tokens(48)
    want = np.asarray(ref.forward(params, conf_of(CFG), jnp.asarray(toks)))
    scale = np.abs(want).max()
    K, V = filled_cache(CFG, params, toks, 20, 64, cache)
    step = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
        p, CFG, t, K, V, n))
    for i in range(20, 48):
        lg, K, V = step(params, toks[None, i:i + 1], K, V,
                        jnp.array([i], jnp.int32))
        assert np.abs(np.asarray(lg[0, 0]) - want[i]).max() < (
            TOL[cache] * scale), i


@pytest.mark.parametrize("control", ["router_on_the_normed_mlp_input",
                                     "silu_for_relu"])
def test_the_controls_fail_the_same_tolerance(ref, params, control):
    """The tolerance can tell the placements apart: the reference routed
    from the normed post-attention stream (every other routed stack's
    placement), or gated by SiLU, lies far outside it, and so does the
    program under the same switch."""
    toks = jnp.asarray(tokens(40, seed=15))
    conf = conf_of(CFG)
    want = np.asarray(ref.forward(params, conf, toks))
    scale = np.abs(want).max()
    if control == "silu_for_relu":
        other = ref.run(params, conf, toks, gate_act=jax.nn.silu)[0]
        cfg = dataclasses.replace(CFG, act="silu")
    else:
        other = ref.run(params, conf, toks, router_stream="mlp")[0]
        cfg = dataclasses.replace(CFG, moe_router_input="mlp")
    run = jax.jit(lambda p, t, cfg: decoder.prefill_chunk(p, cfg, t)[0],
                  static_argnums=2)
    assert np.abs(np.asarray(other) - want).max() > 1e-2 * scale
    switched = np.asarray(run(params, toks[None], cfg)[0])
    assert np.abs(switched - want).max() > 1e-2 * scale
    # the switched program is the switched reference: the switch is the
    # whole difference
    assert np.abs(switched - np.asarray(other)).max() < 2e-4 * scale
    assert np.abs(np.asarray(run(params, toks[None], CFG)[0]) - want
                  ).max() < 2e-4 * scale


def test_the_router_is_called_once_a_layer_ahead_of_attention(params):
    """``_moe_gates`` keeps its name and signature and is traced once a
    program (the scan's body); in the lowered program the router's scope
    comes before the projections' within the layer."""
    calls = []
    inner = decoder._moe_gates

    def spy(cfg, lp, xf):
        calls.append(xf.shape)
        return inner(cfg, lp, xf)
    decoder._moe_gates = spy
    try:
        low = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t)).lower(
            params, tokens(16)[None])
    finally:
        decoder._moe_gates = inner
    assert calls == [(16, CFG.dim)]
    text = low.as_text(debug_info=True)
    route = text.index("moe.route")
    assert route < text.index("attn.qkv") < text.index("moe.experts")
    assert "attn.window" in text and "attn.core" in text


# -- the rings: a row a slot, read to the live depth ----------------------

def ring_stacks(cache, B=3, Lw=2, KvH=2, Wr=8, hd=4, seed=0):
    rng = np.random.default_rng(seed)
    codes = rng.integers(-127, 128, (Lw, B, KvH, Wr, hd))
    if cache == "int8":
        return {"q": jnp.asarray(codes, jnp.int8),
                "s": jnp.asarray(rng.random((Lw, B, KvH, Wr)), jnp.float32)}
    return jnp.asarray(codes, jnp.float32)


@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_a_row_written_ring_is_the_selects_bit_for_bit(cache):
    """``_ring_put`` against ``_ring_merge`` on one new position a slot, a
    ring that has not wrapped, one that has, and an inactive slot: the same
    bits in every slot of the ring, and the inactive slot's ring and every
    other layer's untouched."""
    ring = ring_stacks(cache)
    new = jax.tree_util.tree_map(lambda a: a[0, :, :, :1] * 0 + 7, ring)
    lengths = jnp.array([3, 21, 13], jnp.int32)
    live = jnp.array([1, 1, 0], jnp.int32)
    row = jnp.int32(1)
    put = jax.jit(lambda r, x: jax.tree_util.tree_map(
        lambda a, b: decoder._ring_put(a, row, b, lengths % 8, live), r, x))(
        ring, new)
    merged = jax.tree_util.tree_map(
        lambda a, b: decoder._ring_merge(a[1], b, lengths, live), ring, new)
    for got, want, was in zip(jax.tree_util.tree_leaves(put),
                              jax.tree_util.tree_leaves(merged),
                              jax.tree_util.tree_leaves(ring)):
        assert np.array_equal(got[1], want)
        assert np.array_equal(got[0], was[0])             # the other layer
        assert np.array_equal(got[1, 2], was[1, 2])       # the inactive slot
        assert not np.array_equal(got[1, 0], was[1, 0])
        assert np.array_equal(np.asarray(got[1, 1, :, 21 % 8]),
                              np.asarray(want[1, :, 21 % 8]))


@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_a_decode_step_leaves_inactive_slots_rings_alone(params, cache,
                                                         monkeypatch):
    """The row form through the engine's own chunk: slot 0 decodes; slot 1 is
    parked between prefill pieces, slot 2 was released, slot 3 never held
    anything: their rings keep their bits, codes and scales, and the parked
    slot goes on as if nothing had happened in between."""
    monkeypatch.setattr(decoder, "_RING_SELECT_MAX", 0)
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


@pytest.mark.parametrize("form", ["select", "rows"])
@pytest.mark.parametrize("depth", [16, 32, 64], ids=["below", "at", "above"])
@pytest.mark.parametrize("T", [1, 5])
def test_a_ring_read_to_the_live_depth_is_the_whole_rings(params, depth, T,
                                                          form, monkeypatch):
    """A window of 32: contexts under an attended prefix of 16 (no ring has
    wrapped: slot j is position j), of 32 (the ring's length) and of 64 (the
    whole ring is the window, wrapped) give the logits and the rings of the
    masked read of the whole ring, for one new position and for a piece."""
    cfg = dataclasses.replace(CFG, sliding_window=32)
    monkeypatch.setattr(decoder, "_RING_SELECT_MAX",
                        0 if form == "rows" else 32)
    n = {16: 9, 32: 26, 64: 50}[depth]          # n + T <= depth
    toks = tokens(n + T, seed=depth)
    K, V = filled_cache(cfg, params, toks, n, 64, "float32")
    step = jax.jit(lambda p, t, K, V, a: decoder.forward_with_cache(
        p, cfg, t, K, V, jnp.array([n], jnp.int32), attn_len=a),
        static_argnums=4)
    whole = step(params, toks[None, n:], K, V, None)
    bounded = step(params, toks[None, n:], K, V, depth)
    scale = np.abs(np.asarray(whole[0])).max()
    assert np.abs(np.asarray(bounded[0]) - np.asarray(whole[0])).max() < (
        1e-5 * scale)
    # (a later layer's keys carry the earlier layers' order of sums)
    for a, b in zip(jax.tree_util.tree_leaves(bounded[1:]),
                    jax.tree_util.tree_leaves(whole[1:])):
        assert np.allclose(a, b, atol=1e-5)


def test_the_decode_program_reads_no_deeper_than_its_bucket(params,
                                                            monkeypatch):
    """What must hold of the traffic: the lowered decode step of a long ring
    slices the ring to the attended prefix and never touches the whole ring
    by a select."""
    cfg = dataclasses.replace(CFG, sliding_window=64)
    monkeypatch.setattr(decoder, "_RING_SELECT_MAX", 0)
    K, V = empty_cache(cfg, 2, 128, "float32")

    def lowered(depth):
        return jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
            p, cfg, t, K, V, n, attn_len=depth)).lower(
            params, tokens(2).reshape(2, 1), K, V,
            jnp.array([3, 0], jnp.int32)).as_text()
    ring = r"tensor<6x2x2x64x16xf32>"
    shallow, deep = lowered(16), lowered(128)
    # the ring is read by a slice of the attended depth (16 of 64 slots)
    assert re.search(r"dynamic_slice.*" + ring + r".*tensor<1x2x2x16x16xf32>",
                     shallow)
    assert re.search(r"dynamic_slice.*" + ring + r".*tensor<1x2x2x64x16xf32>",
                     deep)
    assert not re.search(r"tensor<1x2x2x64x16xf32>", shallow)


# -- the engine ----------------------------------------------------------

def test_the_engine_serves_the_references_greedy_stream(ref, params,
                                                        monkeypatch):
    """admit + chunked decode through the engine's own programs with the
    rings written a row a slot, three wraps: the greedy stream is the
    reference's, token by token."""
    monkeypatch.setattr(decoder, "_RING_SELECT_MAX", 0)
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
        want.append(int(jnp.argmax(fwd(params, jnp.asarray(seq))[n - 1])))
        seq[n] = want[-1]
    assert got == want


def test_chunked_admission_of_a_prompt_longer_than_the_window(params):
    """A prompt of 50 tokens (six windows) admitted in 16-token pieces
    through the scheduler, decode dispatches of another stream in between:
    the one-shot stream."""
    eng0 = make_engine(params, slots=2)
    long, short = tokens(50, seed=10), tokens(6, seed=11)
    want = uninterrupted(eng0, long, GREEDY, 8)
    eng, sched = make_stack(eng0, prefill_chunk=16)
    try:
        other = sched.submit(short, GREEDY, max_tokens=40)
        r = sched.submit(long, GREEDY, max_tokens=8)
        assert list(r.tokens()) == want
        list(other.tokens())
    finally:
        sched.shutdown()


def test_a_ring_is_no_longer_than_the_served_context(params):
    """A window of 64 served at 32 positions: the rings are 32 long (a bare
    Model CR at 2,048 positions does not pay for 4,096), the engine's config
    says so, and the stream is the one an engine with whole rings gives."""
    cfg = dataclasses.replace(CFG, sliding_window=64)
    short = make_engine(params, cfg=cfg, seq=32)
    whole = make_engine(params, cfg=cfg, seq=128)
    assert short.cfg.sliding_window == 32 and whole.cfg.sliding_window == 64
    assert rings_of(short, 0)[0].shape[2] == 32
    assert rings_of(whole, 0)[0].shape[2] == 64
    assert 2 * short.cache_bytes["window"] == whole.cache_bytes["window"]
    assert short.ring_positions["allocated"] == 6 * 4 * 32
    prompt = tokens(13, seed=9)
    streams = []
    for eng in (short, whole):
        got = [eng.admit(0, prompt, GREEDY)]
        for _ in range(4):
            got += [int(t) for t in eng.decode_n(4)[:, 0]]
        streams.append(got)
    assert streams[0] == streams[1]
    # the published window against the harness's served context: equal
    assert min(BIG.sliding_window, 4096) == 4096


def test_zero_config_resolution_on_the_chip(monkeypatch):
    """bfloat16 weights, int8 contiguous cache, chunk 32 and the slots
    ``_recurrent_slots`` gives from the model alone as the rule stands: four
    tokens an expert a step at 6 of 64 kept wants 42.7, so 64, and 64 slots'
    rings are 1.66 GB, under the 2 GiB an eighth of the chip allows."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert englib.resolve_engine_dtype(BIG, "tpu") == "bfloat16"
    ecfg = englib.resolve_serving_defaults(
        EngineConfig(max_slots=0, max_seq_len=4096, decode_chunk=0,
                     paged=None, page_size=0, n_pages=None,
                     cache_dtype=jnp.int8), BIG, None)
    assert (ecfg.paged, ecfg.max_slots, ecfg.decode_chunk) == (False, 64, 32)
    assert englib._recurrent_slots(BIG) == 64
    assert BIG.window_ring_bytes == 6 * 4096 * 1056 == 25_952_256
    assert 64 * BIG.window_ring_bytes < 2 << 30
    # a third period's rings (nine window layers) would halve the slots
    three = dataclasses.replace(BIG, n_layers=12,
                                layer_kinds="AwwwAwwwAwww")
    assert englib._recurrent_slots(three) == 32
    conf = work.load_conf(CONF_PATH)
    want = conf["expected_resolution"]
    assert (want["paged"], want["max_slots"], want["decode_chunk"]) == (
        ecfg.paged, ecfg.max_slots, ecfg.decode_chunk)
    assert conf["saturating_clients"] == ecfg.max_slots


def test_the_presets_cache_is_two_full_rows_and_six_rings():
    """The served cache by shapes alone (nothing is allocated): a slot holds
    (2 + 6) x 4,096 positions of 1,056 B: 34.6 MB, three quarters of it
    rings."""
    full = jax.eval_shape(lambda: QC.empty_cache(
        BIG.n_full_layers, 64, BIG.n_kv_heads, 4096, BIG.head_dim))
    rings = jax.eval_shape(lambda: decoder.empty_state(BIG, 64, jnp.int8))

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree))
    assert 2 * nbytes(full) == 64 * 2 * 4096 * 1056
    assert nbytes(rings) == 64 * BIG.window_ring_bytes
    per_slot = (2 * nbytes(full) + nbytes(rings)) / 64
    assert per_slot == 8 * 4096 * 1056 == 34_603_008


def test_ring_positions_gauge(params, monkeypatch):
    """``tpu_model_ring_positions{what}``: live = min(length, W) a slot a
    window layer from the host's lengths, allocated = what the rings hold;
    gone with the model."""
    monkeypatch.setenv("TPU_WARM_BUCKETS", "0")   # nothing is served here
    from ollama_operator_tpu.runtime.service import LoadedModel
    from ollama_operator_tpu.tokenizer.tokenizer import Tokenizer
    tok = Tokenizer("llama", [f"t{i}" for i in range(CFG.vocab_size)],
                    bos_id=1, eos_id=2)
    lm = LoadedModel("smallthinker", CFG, params, tok, ecfg=EngineConfig(
        max_slots=2, max_seq_len=64, cache_dtype=jnp.int8,
        min_prefill_bucket=16))
    try:
        eng = lm.engine
        assert eng.ring_positions == {"live": 0, "allocated": 6 * 2 * W}
        eng.admit(0, tokens(5), GREEDY)
        eng.admit(1, tokens(20, seed=1), GREEDY)
        assert eng.ring_positions["live"] == 6 * (5 + W)
        eng.decode_n(4)
        assert eng.ring_positions["live"] == 6 * (W + W)
        text = METRICS.render().replace(".0", "")
        assert f'tpu_model_ring_positions{{what="live"}} {6 * 2 * W}' in text
        assert (f'tpu_model_ring_positions{{what="allocated"}} {6 * 2 * W}'
                in text)
        eng.release(1)
        assert eng.ring_positions["live"] == 6 * W
    finally:
        lm.unload()
    assert not re.search(r"^tpu_model_ring_positions", METRICS.render(),
                         re.M)
    # a model without window layers has no such gauge
    plain = Engine(cfglib.PRESETS["tiny"], decoder.init_params(
        cfglib.PRESETS["tiny"], jax.random.PRNGKey(0), dtype=jnp.float32),
        ecfg=EngineConfig(max_slots=2, max_seq_len=32))
    assert plain.ring_positions == {}


def test_the_benchmarks_probe_passes_on_the_toy():
    """``server_child.probe`` as the cell runs it (both paths under their own
    sets, the decode step through the engine's own cache trees and rings),
    on the CPU at the rehearsal's sizes: the calling convention the harness
    fixes, the router tapped once a program ahead of attention."""
    conf = server_child.load_conf(CONF_PATH, True)
    cfg = server_child.model_config(conf, True)
    assert cfg.sliding_window == 8 and conf["sliding_window_size"] == 8
    assert (cfg.n_experts, cfg.n_experts_used, cfg.vocab_size) == (8, 3, 512)
    p = decoder.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=4, max_seq_len=128, decode_chunk=4,
                        cache_dtype=jnp.int8, paged=False,
                        min_prefill_bucket=16)
    assert server_child.probe(cfg, ecfg, p, conf, seed=7)
    said = server_child.COMPARED
    assert said["shortfall_served_vs_reference"]["value"] <= 0.08
    assert said["prefill_served_vs_reference"]["value"] < 0.03


# -- the benchmark's readers and arithmetic ------------------------------

NEW_READERS = ("ring_attn_roofline", "ring_live_share")


def reader_ctx(conf, after=None, live_tokens=None):
    return types.SimpleNamespace(
        conf=conf, notes={}, live_tokens=live_tokens,
        resolved={"decode_chunk": 2, "max_slots": 4, "weights": "bfloat16",
                  "kv_dtype": "int8"},
        peaks={"hbm_bytes_per_s": 819e9}, trace_before={}, trace_after={},
        before={}, after=after or {})


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_none_on_the_parents_program(name, tmp_path,
                                                    monkeypatch):
    """The driver runs the new readers on the parent's program too, which has
    neither the gauge nor this configuration: nothing to read is None, no
    error; and K-EXAONE's work file has no live-position price."""
    from benchmark import run, trace_spans
    monkeypatch.setattr(trace_spans.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    reader = run.layer_reader(name)
    assert reader.read(reader_ctx(work.load_conf(CONF_PATH),
                                  live_tokens=9e4)) is None
    exaone = work.load_conf(os.path.join(
        os.path.dirname(CONF_PATH), "k-exaone-236b-a23b.json"))
    assert reader.read(reader_ctx(exaone, live_tokens=9e4)) is None


def test_ring_live_share_reads_the_gauge():
    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    for what, n in (("live", 393216.0), ("allocated", 1572864.0)):
        reg.gauge_fn("tpu_model_ring_positions", lambda n=n: n,
                     f'{{what="{what}"}}')
    scrape = prom.parse(reg.render())
    ctx = reader_ctx(work.load_conf(CONF_PATH), after=scrape)
    assert run.layer_reader("ring_live_share").read(ctx) == pytest.approx(25.0)
    # the scrape that ends the trace, where there is one, is the one read
    ctx.trace_after = prom.parse(reg.render().replace("393216", "786432"))
    assert run.layer_reader("ring_live_share").read(ctx) == pytest.approx(50.0)


def test_ring_attn_roofline_prices_live_positions(monkeypatch):
    """live tokens x 6 x 1,056 B over the HBM rate over the scope's time."""
    from benchmark import run, window_spans
    monkeypatch.setattr(window_spans, "step_ms", lambda ctx: 2.0)
    ctx = reader_ctx(work.load_conf(CONF_PATH), live_tokens=90_000.0)
    got = run.layer_reader("ring_attn_roofline").read(ctx)
    least_ms = 1e3 * 90_000 * 6 * 1056 / 819e9
    assert got == pytest.approx(100.0 * least_ms / 2.0)
    assert ctx.notes["ring_attn_roofline"]["ring_bytes"] == 90_000 * 6336
    assert 0 < got < 100


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b", "decode-deep", 1)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "smallthinker-21b-a3b")
    conf = work.load_conf(CONF_PATH)
    assert entry["reduced"] == conf["reduced"]
    assert entry["source"] == conf["source"]
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert by[name]["workloads"] == [CELL]
        assert by[name]["moves"] == "out_tok_s"
    for name in ("decode_moe_ms_per_step", "moe_experts_roofline",
                 "moe_expert_load_spread", "decode_kv_write_ms_per_step",
                 "decode_window_attn_ms_per_step", "kv_cache_mb_per_slot",
                 "pass_filled_share", "late_launch_share"):
        # appended in PR 50's turn: only what PR 53 appended stands behind it
        cells = by[name]["workloads"]
        assert cells[cells.index(CELL) + 1:] in (
            [], ["kimi-k2.7-code.decode-deep"])
    # whole rings by batch would read over 100% where a ring is read to the
    # live depth: this cell is not on that list, and its file has no hook
    assert CELL not in by["window_attn_roofline"]["workloads"]
    assert work.own(conf, "window_bytes_step") is None
    from benchmark import run
    found = run.find_cell(CELL)
    assert found.mix["clients"] == "saturating_clients"
    assert conf["saturating_clients"] == 64


def test_the_configurations_work_arithmetic():
    conf = work.load_conf(CONF_PATH)
    own = lambda name: work.own(conf, name)     # noqa: E731
    assert own("ring_bytes_per_live_position")(conf, "int8") == 6 * 1056
    assert work.kv_bytes_per_token(conf, "int8") == 2 * 1056
    assert work.attn_flops_per_pair(conf) == 4 * 2 * 28 * 128
    expert = 3 * 2560 * 768
    attn = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
    fixed = 8 * (attn + 2560 * 64) + 2560 * 151936
    assert work.matmul_flops_per_token(conf) == 2.0 * (fixed + 8 * 6 * expert)
    assert work.layer_matmul_params(conf) == attn + 2560 * 64 + 64 * expert
    # 64 tokens x 6 picks touch 99.8% of 64 experts; a step's weights are
    # 7.2 GB, 6.0 of them experts, and no ring byte is among them
    touched = own("distinct_experts")(conf, 64)
    assert 63.8 < touched < 64
    assert own("experts_bytes_step")(conf, 64, "bfloat16") == pytest.approx(
        8 * touched * expert * 2)
    step = work.weight_bytes_step(conf, 64, "bfloat16")
    assert step == pytest.approx(2 * fixed + 8 * touched * expert * 2)
    assert 7.1e9 < step < 7.3e9
