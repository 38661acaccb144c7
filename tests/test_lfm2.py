"""A hybrid stack whose recurrent mixer is a gated short convolution (lfm2_moe):
convolution layers beside rotary attention with q/k norms in the hybrid scan,
two leading dense layers before the routed ones, and a sigmoid router with a
selection bias. CPU, the toy of the same shape (``tiny-lfm2``), seeded weights;
the plain reference is the benchmark's
(``benchmark/configs/lfm2-8b-a1b.reference.py``), read at the toy's sizes
through the configuration file's own ``holds``.

No share of a layer is cut here (depth alone: every expert and the whole
vocabulary are held), so the guide's "the shares add up to the uncut layer"
test has no subject; ``tests/test_hybrid.py`` keeps it for the configuration
that cuts one."""

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
from ollama_operator_tpu.runtime import accounting
from ollama_operator_tpu.runtime import engine as englib
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

from benchmark import server_child, work
from test_hybrid import (drain, make_stack, manual, run_to_end,
                         uninterrupted)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONF_PATH = os.path.join(REPO, "benchmark", "configs", "lfm2-8b-a1b.json")
CFG = cfglib.PRESETS["tiny-lfm2"]
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
SEEDED = SlotOptions(temperature=0.9, seed=1234, repeat_penalty=1.0)
EXPERT_TOKENS = "tpu_model_moe_expert_tokens_total"


def conf_of(cfg):
    """The configuration file's dict at ``cfg``'s sizes: each key the file
    holds the preset to, read back from the config."""
    conf = work.load_conf(CONF_PATH)
    for ours, theirs in conf["holds"]:
        conf[theirs] = getattr(cfg, ours)
    conf["layer_types"] = ["full_attention" if c == "A" else "conv"
                           for c in cfg.layer_kinds]
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


def state_of(eng, slot):
    """The convolution inputs one slot carries, as a host array."""
    _, _, (ssm, conv, _) = decoder.split_state(eng.k_cache, eng.v_cache)
    assert ssm is None
    return np.asarray(conv[:, slot])


# -- the model against the reference -----------------------------------

def test_preset_is_the_published_shape():
    """The served preset against the configuration's file, key by key (the
    benchmark's own check), the cut's floors and the issue's arithmetic."""
    conf = server_child.load_conf(CONF_PATH, False)
    cfg = server_child.model_config(conf, False)
    assert cfg.layer_kinds == "ccAcccAcccAcccAc"
    assert (cfg.n_conv_layers, cfg.n_attn_layers, cfg.n_ssm_layers) == (12, 4,
                                                                        0)
    assert (cfg.n_dense_layers, cfg.n_routed_layers) == (2, 14)
    assert cfg.rope and cfg.qk_norm and cfg.tie_embeddings
    assert cfg.moe_score == "sigmoid" and cfg.moe_select_bias
    # whole periods of the published pattern, and the floors of a cut
    published = ("conv conv full_attention conv " * 5
                 + "conv full_attention conv conv").split()
    assert conf["layer_types"] == published[:16]
    assert conf["published"]["num_hidden_layers"] == len(published) == 24
    assert cfg.n_routed_layers >= 4 and cfg.n_experts >= 8
    assert sorted(conf["reduced"]) == ["layer_types",
                                       "max_position_embeddings",
                                       "num_hidden_layers"]
    # 12 x 2 x 2048 float32 a sequence; 5.399B parameters, 10.80 GB
    assert cfg.ssm_state_bytes == 12 * 2 * 2048 * 4 == 196608
    assert cfg.n_params == (14 * (32 * 3 * 2048 * 1792 + 2048 * 32)
                            + 2 * 3 * 2048 * 7168
                            + 4 * (2 * 2048 * 2048 + 2 * 2048 * 512)
                            + 12 * 4 * 2048 * 2048 + 65536 * 2048)
    assert 10.79e9 < 2 * cfg.n_params < 10.81e9


@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_prefill_then_decode_against_the_reference(ref, params, cache):
    """Prefill 24 positions, then 16 decode steps through the cache, each
    position's logits against the reference's full forward pass. Float32
    weights on both sides, so what differs is the order of sums (the
    program's matmuls at the CPU's default precision against the reference's
    highest, a cache position at a time against all at once): 2e-4 of the
    largest logit, a hundred times float32's step and a fiftieth of the
    least that leaving out a part moves (the test below). Through the int8 cache the keys and
    values carry 1/254 of their row's largest entry: 3e-2."""
    toks = tokens(40)
    want = np.asarray(ref.forward(params, conf_of(CFG), jnp.asarray(toks)))
    scale = np.abs(want).max()
    logits, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None, :24])
    assert "ssm" not in ks and set(vs) == {"kv", "conv"}
    assert np.abs(np.asarray(logits[0]) - want[:24]).max() < 2e-4 * scale
    S, La = 64, CFG.n_attn_layers
    if cache == "int8":
        from ollama_operator_tpu.ops import quant_cache as QC
        kc, vc = QC.empty_cache(La, 1, CFG.n_kv_heads, S, CFG.head_dim), \
            QC.empty_cache(La, 1, CFG.n_kv_heads, S, CFG.head_dim)
        for c, new in ((kc, ks["kv"]), (vc, vs["kv"])):
            q, s = QC.quantize_kv(new)
            c["q"] = c["q"].at[:, :, :, :24].set(q)
            c["s"] = c["s"].at[:, :, :, :24].set(s)
        tol = 3e-2
    else:
        kc = jnp.zeros((La, 1, CFG.n_kv_heads, S, CFG.head_dim))
        kc, vc = (kc.at[:, :, :, :24].set(ks["kv"]),
                  kc.at[:, :, :, :24].set(vs["kv"]))
        tol = 2e-4
    K, V = decoder.join_state(kc, vc, (None, vs["conv"], None))
    step = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
        p, CFG, t, K, V, n))
    for i in range(24, 40):
        lg, K, V = step(params, toks[None, i:i + 1], K, V,
                        jnp.array([i], jnp.int32))
        assert np.abs(np.asarray(lg[0, 0]) - want[i]).max() < tol * scale, i


def test_each_new_part_moves_the_logits(ref, params):
    """The tolerance above can tell: the reference without the selection
    bias, with rotary positions off by one, or with the convolution's oldest
    tap dropped, lies far outside it."""
    toks = jnp.asarray(tokens(24, seed=15))
    conf = conf_of(CFG)
    want = np.asarray(ref.forward(params, conf, toks))
    scale = np.abs(want).max()
    layers = params["layers"]

    def off(**leaves):
        return {**params, "layers": {**layers, **leaves}}

    no_bias = ref.forward(off(router_bias=layers["router_bias"] * 0), conf,
                          toks)
    no_tap = ref.forward(off(conv_w=layers["conv_w"].at[:, 0].set(0.0)), conf,
                         toks)
    theta = ref.forward(params, {**conf, "rope_theta": 100.0}, toks)
    for other in (no_bias, no_tap, theta):
        assert np.abs(np.asarray(other) - want).max() > 1e-2 * scale


def test_the_engine_serves_the_references_greedy_stream(ref, params):
    """admit + chunked decode through the engine's own programs: the greedy
    stream is the reference's, token by token."""
    eng = make_engine(params)
    prompt = tokens(21, seed=3)
    got = [eng.admit(1, prompt, GREEDY)]
    for _ in range(3):
        got += [int(t) for t in eng.decode_n(4)[:, 1]]
    conf = conf_of(CFG)
    # one compile: the model is causal, so position n - 1 of a padded
    # sequence reads what the sequence of n would
    fwd = jax.jit(lambda p, t: ref.forward(p, conf, t))
    seq, want = np.zeros((40,), np.int32), []
    seq[:21] = prompt
    for n in range(21, 21 + len(got)):
        want.append(int(jnp.argmax(fwd(params, jnp.asarray(seq))[n - 1])))
        seq[n] = want[-1]
    assert got == want


def test_the_benchmarks_probe_passes_on_the_toy():
    """``server_child.probe`` as the cell runs it (both paths under their own
    sets, the decode step through the engine's own cache trees), on the CPU
    at the toy's sizes: the calling convention the harness fixes."""
    conf = server_child.load_conf(CONF_PATH, True)
    cfg = server_child.model_config(conf, True)
    p = decoder.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=4, max_seq_len=128, decode_chunk=4,
                        cache_dtype=jnp.int8, paged=False,
                        min_prefill_bucket=16)
    assert server_child.probe(cfg, ecfg, p, conf, seed=7)
    said = server_child.COMPARED
    assert said["shortfall_served_vs_reference"]["value"] <= 0.08
    assert said["prefill_served_vs_reference"]["value"] < 0.03


def test_the_router_is_traced_once_a_forward_pass(params):
    """benchmark/choices.py needs exactly two traced calls of _moe_gates, the
    prefill's [Lr, T, k] and the decode step's [Lr, B, k], in layer order:
    the dense layers' scan traces no router, the routed layers' one."""
    from benchmark.choices import record_choices
    eng = make_engine(params)
    eng.admit(0, tokens(12))

    def both(p, kc, vc, t, st, n):
        a = decoder.prefill_chunk(p, CFG, t)[0]
        b = decoder.forward_with_cache(p, CFG, st, kc, vc, n,
                                       attn_len=eng._attn_bucket(1))[0]
        return a[0, -1], b[0, 0]

    with record_choices() as chosen:
        jax.jit(both)(eng.params, eng.k_cache, eng.v_cache,
                      jnp.asarray(tokens(12))[None],
                      jnp.full((eng.n_slots, 1), 5, jnp.int32), eng.lengths)
        calls = chosen.calls()
    assert [c.shape for c in calls] == [
        (CFG.n_routed_layers, 12, CFG.n_experts_used),
        (CFG.n_routed_layers, eng.n_slots, CFG.n_experts_used)]


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_lowered_programs_carry_the_new_scopes(params, program):
    """``conv.*`` around the three parts of the short convolution, beside
    the attention and expert scopes that were there: what
    ``benchmark/conv_spans.py`` and ``trace_spans.py`` find in a trace."""
    from ollama_operator_tpu.runtime.trace import DEVICE_SCOPES
    if program == "prefill":
        low = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t)).lower(
            params, tokens(16)[None])
    else:
        kc = jnp.zeros((CFG.n_attn_layers, 2, CFG.n_kv_heads, 32,
                        CFG.head_dim))
        K, V = decoder.join_state(kc, kc, decoder.empty_state(CFG, 2))
        low = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
            p, CFG, t, K, V, n, route_live=n)).lower(
            params, tokens(2)[:, None], K, V, jnp.array([3, 0], jnp.int32))
    text = low.as_text(debug_info=True)
    found = {s for s in DEVICE_SCOPES
             if re.search(r'[/"]' + re.escape(s) + r'[/"]', text)}
    assert found >= {"conv.in_proj", "conv.conv", "conv.out", "attn.qkv",
                     "attn.core", "attn.out", "mlp", "moe.route",
                     "moe.experts", "lm_head", "embed"}
    assert not {s for s in found if s.startswith("ssm.")}


# -- the router ---------------------------------------------------------

@pytest.fixture(scope="module")
def routed(params):
    """(lp of routed layer 1, normed hidden states [64, D])."""
    lp = {k: v[1] for k, v in params["layers"].items()
          if k in ("router", "router_bias")}
    x = jax.random.normal(jax.random.PRNGKey(5), (64, CFG.dim), jnp.float32)
    return lp, x


def test_the_bias_selects_and_never_weighs(routed):
    """Where the bias changes the kept set, the kept experts' gates are
    still their UNBIASED scores over those scores' sum: b takes part in the
    selection only. The seeded b is wide enough to change some sets."""
    lp, x = routed
    k = CFG.n_experts_used
    gates = np.asarray(decoder._moe_gates(CFG, lp, x))
    plain = np.asarray(decoder._moe_gates(
        dataclasses.replace(CFG, moe_select_bias=False), lp, x))
    score = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
    pick = score + np.asarray(lp["router_bias"])
    changed = ((gates > 0) != (plain > 0)).any(axis=1)
    assert 5 <= changed.sum() < 64, "the bias has to change some kept sets"
    for n in range(64):
        kept = np.flatnonzero(gates[n])
        assert set(kept) == set(np.argsort(-pick[n])[:k])
        assert np.allclose(gates[n, kept], score[n, kept]
                           / (score[n, kept].sum() + 1e-6), rtol=1e-5)


@pytest.mark.parametrize("scale", [1.0, 2.5])
@pytest.mark.parametrize("renorm", [True, False])
def test_gates_sum_to_the_scaling_factor(routed, scale, renorm):
    lp, x = routed
    cfg = dataclasses.replace(CFG, moe_scale=scale, moe_renorm=renorm)
    gates = np.asarray(decoder._moe_gates(cfg, lp, x))
    assert ((gates > 0).sum(axis=1) == CFG.n_experts_used).all()
    if renorm:
        assert np.allclose(gates.sum(axis=1), scale, rtol=1e-5)
    else:
        score = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
        assert np.allclose(gates[gates > 0], scale * score[gates > 0],
                           rtol=1e-5)


def test_dense_layers_trace_no_router(params, monkeypatch):
    """Layers 0-1 run their dense MLP in a scan of their own: the router is
    traced once, for the routed layers' scan, over their own leaves."""
    seen = []
    inner = decoder._moe_gates
    monkeypatch.setattr(decoder, "_moe_gates", lambda cfg, lp, xf: (
        seen.append(lp["router"].shape), inner(cfg, lp, xf))[1])
    jaxpr = jax.make_jaxpr(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, tokens(8)[None])
    assert seen == [(CFG.dim, CFG.n_experts)]
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [
        CFG.n_dense_layers, CFG.n_routed_layers]
    # neither scan carries the other's feed-forward: the dense one is handed
    # [Ld, D, Fd] stacks and no expert stack, the routed one the reverse
    shapes = [{tuple(v.aval.shape) for v in e.invars} for e in scans]
    dense = (CFG.n_dense_layers, CFG.dim, CFG.dense_ffn_dim)
    experts = (CFG.n_routed_layers, CFG.n_experts, CFG.dim, CFG.ffn_dim)
    assert dense in shapes[0] and experts not in shapes[0]
    assert experts in shapes[1] and dense not in shapes[1]


def test_a_stack_has_one_recurrent_kind():
    with pytest.raises(AssertionError, match="one recurrent kind"):
        dataclasses.replace(CFG, layer_kinds="cmAcccAc", ssm_heads=4
                            ).validate()


# -- the state: pieces, padding, inactive slots --------------------------

@pytest.mark.parametrize("pieces", [(40,), (16, 24), (16, 16, 8), (24, 16),
                                    (1, 1, 38), (7, 33)])
def test_prefill_in_pieces_equals_one_piece(params, pieces):
    """One prefill, and the same prompt through extends of the cache (pieces
    shorter than the convolution's reach among them): state and last logits
    agree."""
    toks = tokens(40, seed=1)
    want_l, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None])
    kc = jnp.zeros((CFG.n_attn_layers, 1, CFG.n_kv_heads, 64, CFG.head_dim))
    K, V = decoder.join_state(kc, kc, decoder.empty_state(CFG, 1))
    at = 0
    for n in pieces:
        lg, K, V = decoder.forward_with_cache(
            params, CFG, toks[None, at:at + n], K, V,
            jnp.array([at], jnp.int32))
        at += n
    assert np.allclose(lg[0, -1], want_l[0, -1], atol=2e-6)
    assert np.allclose(V["conv"], vs["conv"], atol=1e-6)


@pytest.mark.parametrize("n_valid", [1, 2, 5, 16, 31])
def test_padded_positions_never_alter_the_state(params, n_valid):
    """A prefill bucket pads the prompt: the state and the last real
    position's logits are those of the unpadded prompt, and the padding's
    content is nothing to the state, to the bit."""
    toks = tokens(32, seed=2)
    f = jax.jit(lambda p, t, n: decoder.prefill_chunk(p, CFG, t, n_valid=n))
    lg, ks, vs = f(params, toks[None], jnp.int32(n_valid))
    lg0, ks0, vs0 = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, toks[None, :n_valid])
    assert np.allclose(vs["conv"], vs0["conv"], atol=1e-6)
    assert np.allclose(lg[0, 0], lg0[0, -1], atol=2e-6)
    other = toks.copy()
    other[n_valid:] = (other[n_valid:] + 7) % CFG.vocab_size
    _, _, vs1 = f(params, other[None], jnp.int32(n_valid))
    assert np.array_equal(vs["conv"], vs1["conv"])


def test_admit_many_rows_keep_their_own_lengths(params):
    """Batched admission: each row's state ends at its own prompt's end."""
    eng = make_engine(params)
    a, b = tokens(9, seed=4), tokens(14, seed=5)
    eng.admit_many([0, 2], [a, b], [GREEDY, GREEDY])
    one = make_engine(params)
    one.admit(1, b, GREEDY)
    assert np.allclose(state_of(eng, 2), state_of(one, 1), atol=1e-6)
    assert not np.allclose(state_of(eng, 0), state_of(eng, 2), atol=1e-3)


@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_a_decode_step_leaves_inactive_slots_alone(params, cache):
    """Slot 0 decodes; slot 1 is parked between prefill pieces, slot 2 was
    released, slot 3 never held anything: their states keep their bits
    through a whole chunk."""
    eng = make_engine(params, cache=getattr(jnp, cache))
    eng.admit(0, tokens(10), GREEDY)
    eng.admit(1, tokens(16, seed=6), GREEDY)
    eng.release(1, park=True)
    eng.admit(2, tokens(5, seed=7), GREEDY)
    eng.release(2)
    before = [state_of(eng, s) for s in range(4)]
    eng.decode_n(4)
    after = [state_of(eng, s) for s in range(4)]
    for s in (1, 2, 3):
        assert np.array_equal(before[s], after[s]), s
    assert not np.array_equal(before[0], after[0])
    # and the parked slot goes on as if nothing had happened in between
    t = eng.extend(1, tokens(30, seed=6), 16, GREEDY)
    fresh = make_engine(params, cache=getattr(jnp, cache))
    t_fresh = fresh.admit(1, tokens(30, seed=6), GREEDY)
    # the tail read the first piece's keys and values back from the cache:
    # through int8 they are not what a one-piece prefill attends to
    exact = cache == "float32"
    assert t == t_fresh or not exact
    assert np.allclose(state_of(eng, 1), state_of(fresh, 1),
                       atol=1e-6 if exact else 1e-2)


def test_extend_refuses_to_cut_a_state_back(params):
    eng = make_engine(params)
    eng.admit(0, tokens(20), GREEDY)
    eng.release(0, park=True)
    with pytest.raises(ValueError, match="cannot be cut back"):
        eng.extend(0, tokens(30), 12, GREEDY)


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
        # land the dispatch in flight first, as the loop does before it
        # hands a slot on: its tokens belong to this stream
        sched._drain_pending()
        got[r] += drain(r)
        assert 0 < len(got[r]) < 30
        sched._preempt_slot(r.slot, cause="test")
        run_to_end(sched, [r], got)
        assert sched.n_preemptions == 1
        assert got[r] == want
    finally:
        sched.shutdown()


@pytest.mark.parametrize("opts", [GREEDY, SEEDED], ids=["greedy", "seeded"])
def test_restart_replay_gives_the_uninterrupted_stream(shared_engine, opts):
    """A mid-stream engine failure with replay on: the rebuilt slot is
    prefilled with prompt + generated and ends in the same state."""
    prompt = tokens(9, seed=9)
    want = uninterrupted(shared_engine, prompt, opts, 24)
    eng, sched = make_stack(shared_engine, restart_backoff=0.001)
    calls = {"n": 0}
    real, real_launch = eng.decode_n, eng.decode_n_launch

    def flaky(fn):
        def call(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("injected mid-stream failure")
            return fn(*a, **kw)
        return call

    eng.decode_n, eng.decode_n_launch = flaky(real), flaky(real_launch)
    try:
        r = sched.submit(prompt, opts, max_tokens=24)
        assert list(r.tokens()) == want
        assert r.error is None and sched.n_replays == 1
    finally:
        sched.shutdown()
        eng.decode_n, eng.decode_n_launch = real, real_launch


def test_chunked_prefill_through_the_scheduler(shared_engine):
    """A prompt admitted in 16-token pieces, decode dispatches of another
    stream in between: the one-shot stream."""
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


def test_paging_is_refused_as_for_any_recurrent_stack(shared_engine,
                                                      params):
    """No page pool: the message the Mamba stack gets."""
    assert shared_engine.recurrent
    with pytest.raises(ValueError, match="contiguous cache"):
        make_engine(params, paged=True, page_size=16)


# -- serving defaults, accounting, metrics ------------------------------

def test_zero_config_resolution_on_the_chip(monkeypatch):
    """bfloat16 weights, int8 contiguous cache, chunk 32 and the slots
    ``_recurrent_slots`` gives from the model alone: four tokens an expert
    a step at 4 of 32 kept is 32."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = cfglib.PRESETS["lfm2-8b-a1b"]
    assert englib.resolve_engine_dtype(cfg, "tpu") == "bfloat16"
    ecfg = englib.resolve_serving_defaults(
        EngineConfig(max_slots=0, max_seq_len=4096, decode_chunk=0,
                     paged=None, page_size=0, n_pages=None,
                     cache_dtype=jnp.int8), cfg, None)
    assert (ecfg.paged, ecfg.max_slots, ecfg.decode_chunk) == (False, 32, 32)
    assert englib._recurrent_slots(cfg) == 32
    conf = work.load_conf(CONF_PATH)
    want = conf["expected_resolution"]
    assert (want["paged"], want["max_slots"], want["decode_chunk"]) == (
        ecfg.paged, ecfg.max_slots, ecfg.decode_chunk)
    assert conf["saturating_clients"] == ecfg.max_slots


def test_accounting_prices_the_new_layers():
    cfg = cfglib.PRESETS["lfm2-8b-a1b"]
    d = 2048
    conv = 2 * d * 3 * d + 2 * d * d + 2 * 5 * d
    attn = 2 * (2 * d * 2048 + 2 * d * 512)
    moe = 4 * 6 * d * 1792 + 2 * d * 32
    assert accounting.per_token_flops(cfg) == pytest.approx(
        12 * conv + 4 * attn + 2 * 6 * d * 7168 + 14 * moe + 2 * d * 65536)
    # four attention layers' span, not sixteen
    assert accounting.attn_span_flops(cfg, 0, 1) == 4 * 4.0 * 2048


def test_state_gauge_and_ps_details(params, monkeypatch):
    monkeypatch.setenv("TPU_WARM_BUCKETS", "0")   # nothing is served here
    from ollama_operator_tpu.runtime.service import LoadedModel
    from ollama_operator_tpu.tokenizer.tokenizer import Tokenizer
    tok = Tokenizer("llama", [f"t{i}" for i in range(CFG.vocab_size)],
                    bos_id=1, eos_id=2)
    lm = LoadedModel("lfm2", CFG, params, tok, ecfg=EngineConfig(
        max_slots=2, max_seq_len=64, cache_dtype=jnp.float32,
        min_prefill_bucket=16))
    try:
        # 6 convolution layers x 2 inputs x 64 channels float32 a slot
        want = 2 * CFG.ssm_state_bytes
        assert want == 2 * 6 * 2 * 64 * 4
        assert lm.engine.state_bytes == want
        assert lm.engine.kv_bytes > want
        assert f'tpu_model_cache_bytes{{kind="state"}} {want}' in \
            METRICS.render().replace(".0", "")
    finally:
        lm.unload()
    assert not re.search(r"^tpu_model_cache_bytes\S* \d",
                         METRICS.render(), re.M)


@pytest.mark.parametrize("preset, plan", [
    ("tiny-lfm2", None), ("tiny-hybrid", None), ("tiny-moe", None),
    ("tiny-moe", dict(dp=1, sp=1, tp=2, ep=2))],
    ids=["lfm2", "granite", "moe", "moe-on-a-mesh"])
def test_expert_tokens_are_counted_once_a_chunk(preset, plan):
    """Every routed model, on one device or a mesh: a series a router
    output, seeded at 0 when the engine is built; a decode chunk adds
    (steps x active slots x routed layers x kept) picks, inactive slots
    none."""
    from ollama_operator_tpu import parallel
    cfg = cfglib.PRESETS[preset]
    p = decoder.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    mesh = plan and parallel.make_mesh(parallel.MeshPlan(**plan))
    eng = Engine(cfg, p, mesh=mesh, ecfg=EngineConfig(
        max_slots=4, max_seq_len=64, cache_dtype=jnp.float32,
        decode_chunk=4, min_prefill_bucket=16))

    def read():
        text = METRICS.render()
        return [float(re.search(
            rf'^{EXPERT_TOKENS}{{expert="{e}"}} ([0-9.]+)$', text, re.M
        ).group(1)) for e in range(cfg.n_experts)]

    before = read()                   # every label is there before a token
    eng.admit(0, tokens(9) % cfg.vocab_size, GREEDY)
    eng.admit(2, tokens(5) % cfg.vocab_size, GREEDY)
    assert read() == before           # admissions are not counted
    eng.decode_n(4)
    took = np.subtract(read(), before)
    layers = cfg.n_routed_layers
    assert took.sum() == 4 * 2 * layers * cfg.n_experts_used
    assert took.max() <= 4 * 2 * layers


# -- the benchmark's readers and arithmetic ------------------------------

NEW_READERS = ("decode_conv_ms_per_step", "moe_expert_load_spread")


def reader_ctx(conf, before=None, after=None):
    return types.SimpleNamespace(
        conf=conf, notes={}, resolved={"decode_chunk": 2,
                                       "weights": "bfloat16"},
        peaks={"hbm_bytes_per_s": 819e9}, trace_before=before or {},
        trace_after=after or {})


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_none_without_a_trace(name, tmp_path, monkeypatch):
    """The driver runs the new readers on the parent's program too, which has
    neither the scopes nor the counter: nothing to read is None, no error."""
    from benchmark import run, trace_spans
    monkeypatch.setattr(trace_spans.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    assert run.layer_reader(name).read(
        reader_ctx(work.load_conf(CONF_PATH))) is None


def test_conv_spans_reads_its_scopes_from_a_trace(tmp_path, monkeypatch):
    """Two complete runs of a decode module of two steps each: self time under
    each ``conv.*`` scope over the steps; a trace without them reads None."""
    from benchmark import conv_spans, trace_spans
    meta = {1: ("jit__decode_n(7)", ""),
            2: ("%fusion.1 = f32[] fusion()", "jit(_decode_n)/conv.conv/mul"),
            3: ("%fusion.2 = f32[] fusion()",
                "jit(_decode_n)/conv.in_proj/dot"),
            4: ("%fusion.3 = f32[] fusion()",
                "jit(_decode_n)/mlp/moe.experts/dot")}

    def planes(with_conv):
        ops = []
        for t0 in (0, 2000):
            ops += [(t0 + 100, t0 + 400, 2 if with_conv else 4),
                    (t0 + 400, t0 + 600, 3 if with_conv else 4),
                    (t0 + 600, t0 + 900, 4)]
        return [{"name": "/device:TPU:0", "meta": meta, "lines": [
            {"name": "XLA Modules", "events": [(0, 1000, 1), (2000, 3000, 1)]},
            {"name": "XLA Ops", "events": ops}]}]

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    for with_conv in (True, False):
        conv_spans._CACHE.clear()
        pl = planes(with_conv)
        monkeypatch.setattr(trace_spans, "find_trace", lambda w=None: str(path))
        monkeypatch.setattr(trace_spans, "reduce",
                            lambda w=None, pl=pl: trace_spans.reduce_planes(pl))
        monkeypatch.setattr(trace_spans, "read_planes", lambda p, pl=pl: pl)
        got = conv_spans.step_seconds(2)
        ctx = reader_ctx({})
        if with_conv:
            assert got == pytest.approx({"conv.conv": 150e-12,
                                         "conv.in_proj": 100e-12})
            assert conv_spans.step_ms(ctx) == pytest.approx(250e-9)
        else:
            assert got is None and conv_spans.step_ms(ctx) is None


@pytest.mark.parametrize("took,want", [
    (None, None),                          # the parent: no such counter
    ([0, 0, 0, 0], None),                  # no token routed in between
    ([10, 10, 10, 10], 0.0), ([30, 10, 0, 0], 200.0)])
def test_load_spread_reads_the_engines_own_count(took, want):
    """The reader over two scrapes of the real registry's text: the most
    loaded expert's tokens over the mean, less one."""
    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    reg.inc("tpu_model_generated_tokens_total", 5.0)
    for e in range(len(took or ())):
        reg.inc(EXPERT_TOKENS, 3.0 * e, f'{{expert="{e}"}}')
    before = prom.parse(reg.render())
    for e, n in enumerate(took or ()):
        reg.inc(EXPERT_TOKENS, float(n), f'{{expert="{e}"}}')
    got = run.layer_reader("moe_expert_load_spread").read(
        reader_ctx({}, before, prom.parse(reg.render())))
    assert got == (None if want is None else pytest.approx(want))


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    """One configuration, one cell under the mix that stands, the two new
    metrics on it alone, and the accepted expert metrics extended to it."""
    from benchmark import run
    cell = run.find_cell("lfm2-8b-a1b.decode-saturated")
    assert (cell.chips, cell.mix_name) == (1, "decode-saturated")
    assert cell.conf["preset"] == "lfm2-8b-a1b"
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) | {"decode_moe_ms_per_step",
                               "moe_experts_roofline"} <= names
    assert not {"decode_ssm_ms_per_step", "ssm_state_roofline",
                "decode_kv_write_ms_per_step"} & names
    other = run.find_cell("granite-4.0-h-small.decode-saturated")
    assert not set(NEW_READERS) & {m["name"] for m in other.per_layer}


def test_the_configurations_work_arithmetic():
    """The sizes the issue reckons with, from the configuration's own file."""
    conf = work.load_conf(CONF_PATH)
    w = work.load_module(os.path.join(conf["_dir"], conf["work"]))
    assert (w.n_conv(conf), w.n_attention(conf), w.n_routed(conf)) == (12, 4,
                                                                       14)
    assert w.conv_params(conf) == 4 * 2048 * 2048
    assert w.attention_params(conf) == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert w.dense_params(conf) == 3 * 2048 * 7168
    assert w.expert_params(conf) == 3 * 2048 * 1792
    # one sequence, one convolution layer: 2 inputs of 2048, float32
    assert w.conv_state_bytes(conf) == 4 * 2 * 2048
    assert w.conv_state_bytes_step(conf, 32) == 32 * 12 * 2 * 16384
    # 28 tokens of 4 picks over 32 experts touch 97.6% of them
    assert w.distinct_experts(conf, 28) == pytest.approx(
        32 * (1 - 0.875 ** 28))
    assert w.experts_bytes_step(conf, 1e9, "bfloat16") == pytest.approx(
        14 * 32 * 3 * 2048 * 1792 * 2)
    total = work.weight_bytes_step(conf, 1e9, "bfloat16") \
        - w.conv_state_bytes_step(conf, 1e9)
    assert total == pytest.approx(
        2 * cfglib.PRESETS["lfm2-8b-a1b"].n_params, rel=1e-9)
    assert work.kv_bytes_per_token(conf, "int8") == 2 * 4 * 8 * (64 + 4)
    assert work.attn_flops_per_pair(conf) == 4 * 4 * 32 * 64
    # a token is multiplied by 1.56B parameters of the published 24 layers;
    # of this cut's 16, by the fixed ones and 4 experts in 14 layers
    assert work.matmul_flops_per_token(conf) == pytest.approx(
        2 * (w.fixed_params(conf) + 14 * 4 * w.expert_params(conf)))
    step = work.decode_step(conf, 28.0, 28 * 200.0, "bfloat16", "int8")
    assert 10.4e9 < step["bytes"] < 10.8e9
