"""A hybrid stack whose recurrent mixer is the gated delta rule (olmo_hybrid):
linear-attention layers beside multi-head attention without positions in the
hybrid scan, in a dense stack (no experts). The state a slot carries a layer
is a matrix a head, updated by a rank-one correction of itself, and the last
inputs of a causal convolution. CPU, the toy of the same shape
(``tiny-olmo-hybrid``), seeded weights; the plain reference is the benchmark's
(``benchmark/configs/olmo-hybrid-7b.reference.py``), read at the toy's sizes
through the configuration file's own ``holds``.

No share of a layer is cut here (depth alone: every head and the whole
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
CONF_PATH = os.path.join(REPO, "benchmark", "configs", "olmo-hybrid-7b.json")
PRESET = cfglib.PRESETS["olmo-hybrid-7b"]
CFG = cfglib.PRESETS["tiny-olmo-hybrid"]
CELL = "olmo-hybrid-7b.decode-saturated"
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
SEEDED = SlotOptions(temperature=0.9, seed=1234, repeat_penalty=1.0)


def conf_of(cfg):
    """The configuration file's dict at ``cfg``'s sizes: each key the file
    holds the preset to, read back from the config."""
    conf = work.load_conf(CONF_PATH)
    for ours, theirs in conf["holds"]:
        conf[theirs] = getattr(cfg, ours)
    conf["layer_types"] = ["full_attention" if c == "A"
                           else "linear_attention" for c in cfg.layer_kinds]
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
    """(state matrices, convolution inputs) one slot carries, host arrays."""
    _, _, (ssm, conv, win) = decoder.split_state(eng.k_cache, eng.v_cache)
    assert win is None
    return np.asarray(ssm[:, slot]), np.asarray(conv[:, slot])


def empty_cache(B, S=64):
    kc = jnp.zeros((CFG.n_full_layers, B, CFG.n_kv_heads, S, CFG.head_dim))
    return decoder.join_state(kc, kc, decoder.empty_state(CFG, B))


# -- the model against the reference -----------------------------------

def test_preset_is_the_published_shape():
    """The served preset against the configuration's file, key by key (the
    benchmark's own check), the cut's floors and the issue's arithmetic."""
    conf = server_child.load_conf(CONF_PATH, False)
    cfg = server_child.model_config(conf, False)
    periods = cfg.n_layers // 4
    assert periods == 3     # layers 0-11: four periods do not fit the probe
    assert cfg is PRESET and cfg.layer_kinds == "dddA" * periods
    assert (cfg.n_delta_layers, cfg.n_full_layers) == (3 * periods, periods)
    assert cfg.n_attn_layers == periods and not cfg.n_experts
    assert not (cfg.n_ssm_layers or cfg.n_conv_layers or cfg.n_window_layers)
    assert not cfg.rope and not cfg.qk_norm and not cfg.tie_embeddings
    # the published widths, uncut
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        3840, 30, 30, 128)
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.delta_conv, cfg.delta_neg_eigval) == (30, 96, 192, 4, True)
    assert (cfg.ffn_dim, cfg.vocab_size) == (11008, 100352)
    assert cfg.delta_conv_dim == 11520
    # whole periods of the published pattern, and the floors of a cut
    published = ("linear_attention linear_attention linear_attention "
                 "full_attention " * 8).split()
    assert conf["layer_types"] == published[:cfg.n_layers]
    assert conf["published"]["num_hidden_layers"] == len(published) == 32
    assert cfg.n_layers >= 4 and cfg.n_layers % 4 == 0
    assert sorted(conf["reduced"]) == ["layer_types",
                                       "max_position_embeddings",
                                       "num_hidden_layers"]
    for what in ("head_dim", "state", "weights", "block", "qk_norm",
                 "positions", "checkpoint"):
        assert what in conf["assumed"], what
    # a linear layer 215.5M, a full layer 185.8M, embedding and head 385.4M
    linear = 3840 * (2880 + 2880 + 5760 + 5760 + 30 + 30) + 5760 * 3840
    mlp, full, emb = 3 * 3840 * 11008, 4 * 3840 * 3840, 100352 * 3840
    assert cfg.n_params == periods * (3 * linear + full + 4 * mlp) + 2 * emb
    assert round((linear + mlp) / 1e6, 1) == 215.5
    assert round((full + mlp) / 1e6, 1) == 185.8
    # a slot: 2.21 MB of state and 0.14 MB of convolution inputs a layer
    layer = 4 * (30 * 96 * 192 + 3 * 11520)
    assert cfg.ssm_state_bytes == 3 * periods * layer
    assert round(12 * layer / 1e6, 1) == 28.2


@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_prefill_then_decode_against_the_reference(ref, params, cache):
    """Prefill 24 positions (three blocks of the blocked form), then 16
    decode steps through the cache (the recurrence as written), each
    position's logits against the reference's full forward pass. Float32
    weights on both sides, so what differs is the order of sums: 2e-4 of the
    largest logit, a fiftieth of the least that leaving out a part moves (the
    test below). Through the int8 cache the full layers' keys and values
    carry 1/254 of their row's largest entry: 3e-2."""
    toks = tokens(40)
    want = np.asarray(ref.forward(params, conf_of(CFG), jnp.asarray(toks)))
    scale = np.abs(want).max()
    logits, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None, :24])
    assert set(ks) == {"kv", "ssm"} and set(vs) == {"kv", "conv"}
    assert ks["ssm"].shape == (6, 1, 4, 8, 16)
    assert vs["conv"].shape == (6, 1, 3, 4 * (8 + 8 + 16))
    assert np.abs(np.asarray(logits[0]) - want[:24]).max() < 2e-4 * scale
    S, La = 64, CFG.n_full_layers
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
    K, V = decoder.join_state(kc, vc, (ks["ssm"], vs["conv"], None))
    step = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
        p, CFG, t, K, V, n))
    for i in range(24, 40):
        lg, K, V = step(params, toks[None, i:i + 1], K, V,
                        jnp.array([i], jnp.int32))
        assert np.abs(np.asarray(lg[0, 0]) - want[i]).max() < tol * scale, i


def test_each_new_part_moves_the_logits(ref, params):
    """The tolerance above can tell: the reference with beta not doubled,
    without the decay, with the convolution's oldest tap dropped, or with
    the output gate's norm weight halved, lies far outside it."""
    toks = jnp.asarray(tokens(24, seed=15))
    conf = conf_of(CFG)
    want = np.asarray(ref.forward(params, conf, toks))
    scale = np.abs(want).max()
    layers = params["layers"]

    def off(**leaves):
        return {**params, "layers": {**layers, **leaves}}

    others = (
        ref.forward(params, {**conf, "linear_allow_neg_eigval": False}, toks),
        ref.forward(off(delta_a_log=layers["delta_a_log"] - 30.0), conf,
                    toks),
        ref.forward(off(delta_conv_w=layers["delta_conv_w"].at[:, 0].set(
            0.0)), conf, toks),
        ref.forward(off(delta_norm_w=layers["delta_norm_w"] * 0.5), conf,
                    toks))
    for other in others:
        assert np.abs(np.asarray(other) - want).max() > 1e-2 * scale


def delta_inputs(B, T, seed, n_valid=None):
    """Seeded inputs of ``_delta_rule`` at the toy's sizes: unit keys, decays
    in (0, 1), beta in (0, 2); zeros at and past ``n_valid``."""
    H, dk, dv = CFG.delta_heads, CFG.delta_key_dim, CFG.delta_value_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, H, dk)) * dk ** -0.5
    k = jax.random.normal(ks[1], (B, T, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S0 = jax.random.normal(ks[5], (B, H, dk, dv)) * 0.3
    if n_valid is not None:
        live = (jnp.arange(T)[None, :] < jnp.asarray(n_valid)[:, None]
                )[..., None]
        g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    return S0, q, k, v, g, beta


def by_recurrence(S0, q, k, v, g, beta):
    """The recurrence as written, position by position."""
    out, S = [], S0
    for t in range(q.shape[1]):
        o, S = decoder._delta_rule(CFG, S, q[:, t:t + 1], k[:, t:t + 1],
                                   v[:, t:t + 1], g[:, t:t + 1],
                                   beta[:, t:t + 1])
        out.append(o)
    return jnp.concatenate(out, axis=1), S


@pytest.mark.parametrize("T, chunk", [(16, 8), (24, 8), (19, 8), (5, 8),
                                      (9, 4), (33, 16), (64, 64), (70, 64)])
def test_the_blocked_form_is_the_recurrence(T, chunk):
    """Blocks that divide T and blocks that do not, one block and many, from
    a state that is not empty: outputs and final state of the blocked form
    against the recurrence as written, to float32's rounding. No term is
    dropped: the same numbers, regrouped."""
    cfg = dataclasses.replace(CFG, delta_chunk=chunk)
    args = delta_inputs(2, T, seed=T)
    with jax.default_matmul_precision("highest"):
        want_o, want_S = by_recurrence(*args)
        got_o, got_S = jax.jit(
            lambda *a: decoder._delta_rule(cfg, *a))(*args)
    assert got_o.shape == want_o.shape
    assert np.allclose(got_o, want_o, atol=2e-5, rtol=1e-5)
    assert np.allclose(got_S, want_S, atol=2e-5, rtol=1e-5)


def test_positions_that_are_not_real_pass_the_state_through():
    """g = 0 and beta = 0 past n_valid: the blocked form ends in the state
    the recurrence reaches over the real positions alone."""
    S0, *seqs = delta_inputs(2, 24, seed=3, n_valid=[13, 24])
    with jax.default_matmul_precision("highest"):
        _, got = decoder._delta_rule(CFG, S0, *seqs)
        _, want0 = by_recurrence(S0[:1], *(x[:1, :13] for x in seqs))
    assert np.allclose(got[0], want0[0], atol=2e-5)


def test_the_engine_serves_the_references_greedy_stream(ref, params):
    """admit + chunked decode through the engine's own programs: the greedy
    stream is the reference's, token by token."""
    eng = make_engine(params)
    prompt = tokens(21, seed=3)
    got = [eng.admit(1, prompt, GREEDY)]
    for _ in range(3):
        got += [int(t) for t in eng.decode_n(4)[:, 1]]
    conf = conf_of(CFG)
    fwd = jax.jit(lambda p, t: ref.forward(p, conf, t))
    seq, want = np.zeros((40,), np.int32), []
    seq[:21] = prompt
    for n in range(21, 21 + len(got)):
        want.append(int(jnp.argmax(fwd(params, jnp.asarray(seq))[n - 1])))
        seq[n] = want[-1]
    assert got == want


def test_the_benchmarks_probe_passes_on_the_toy():
    """``server_child.probe`` as the cell runs it (both paths, the decode
    step through the engine's own cache trees), on the CPU at the toy's
    sizes: the calling convention the harness fixes. The reference has no
    ``forward_chosen`` (the model makes no choice), so all four comparisons
    are of logits."""
    conf = server_child.load_conf(CONF_PATH, True)
    cfg = server_child.model_config(conf, True)
    assert not hasattr(server_child.load_reference(conf), "forward_chosen")
    p = decoder.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=4, max_seq_len=128, decode_chunk=4,
                        cache_dtype=jnp.int8, paged=False,
                        min_prefill_bucket=16)
    assert server_child.probe(cfg, ecfg, p, conf, seed=7)
    said = server_child.COMPARED
    assert set(said) == {"prefill_served_vs_reference",
                         "decode_served_vs_reference",
                         "prefill_served_vs_program_plain",
                         "decode_served_vs_program_plain"}
    assert all(v["value"] < 0.03 for v in said.values())


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_lowered_programs_carry_the_new_scopes(params, program):
    """``delta.*`` around the five parts of the mixer, beside the attention
    and MLP scopes that were there: what ``benchmark/delta_spans.py`` and
    ``trace_spans.py`` find in a trace."""
    from ollama_operator_tpu.runtime.trace import DEVICE_SCOPES
    if program == "prefill":
        low = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t)).lower(
            params, tokens(16)[None])
    else:
        K, V = empty_cache(2, 32)
        low = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
            p, CFG, t, K, V, n)).lower(
            params, tokens(2)[:, None], K, V, jnp.array([3, 0], jnp.int32))
    text = low.as_text(debug_info=True)
    found = {s for s in DEVICE_SCOPES
             if re.search(r'[/"]' + re.escape(s) + r'[/"]', text)}
    assert found >= {"delta.in_proj", "delta.conv", "delta.update",
                     "delta.gate_norm", "delta.out", "attn.qkv", "attn.core",
                     "attn.out", "mlp", "lm_head", "embed"}
    assert not {s for s in found if s.startswith(("ssm.", "conv.", "moe."))}


def _eqns(jaxpr):
    """Every equation of a jaxpr, the nested ones too."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_bfloat16_weights_meet_float32_activations_in_the_mxu_alone(
        params, program):
    """Served bfloat16, a delta stack carries its residual stream and what
    its mixers and MLPs hand on float32: every matrix goes into its matmul
    as it is stored (no matrix is promoted to float32), the matmuls behind
    the residual stream come out float32, attention keeps the weights'
    type, and the state stays float32."""
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    if program == "prefill":
        fn, args = (lambda p, t: decoder.prefill_chunk(p, CFG, t)), (
            p16, tokens(16)[None])
    else:
        kc = jnp.zeros((CFG.n_full_layers, 2, CFG.n_kv_heads, 32,
                        CFG.head_dim), jnp.bfloat16)
        K, V = decoder.join_state(kc, kc, decoder.empty_state(CFG, 2))
        fn, args = (lambda p, t, K, V, n: decoder.forward_with_cache(
            p, CFG, t, K, V, n)), (p16, tokens(2)[:, None], K, V,
                                   jnp.array([3, 0], jnp.int32))
    closed = jax.make_jaxpr(fn)(*args)
    matrix = CFG.dim * CFG.dim
    dots = [e for e in _eqns(closed.jaxpr) if e.primitive.name == "dot_general"]
    for e in dots:
        kinds = {v.aval.dtype for v in e.invars}
        big = [v for v in e.invars if v.aval.size >= matrix]
        # a stored matrix is read as stored, beside an operand of its type
        assert not big or kinds == {jnp.dtype(jnp.bfloat16)}, e
    assert any(e.outvars[0].aval.dtype == jnp.float32 for e in dots)
    for e in _eqns(closed.jaxpr):
        if e.primitive.name == "convert_element_type":
            assert not (e.invars[0].aval.size >= matrix
                        and e.params["new_dtype"] == jnp.float32), e
    out = jax.eval_shape(fn, *args)
    assert out[0].dtype == jnp.float32                      # logits
    assert out[1]["ssm"].dtype == out[2]["conv"].dtype == jnp.float32
    assert out[1]["kv"].dtype == jnp.bfloat16


@pytest.mark.parametrize("preset, want", [
    ("tiny-olmo-hybrid", jnp.float32), ("tiny-hybrid", jnp.bfloat16),
    ("tiny-lfm2", jnp.bfloat16), ("tiny-exaone", jnp.bfloat16)])
def test_the_float32_residual_stream_is_the_delta_stacks_alone(preset, want):
    """What the layer scan carries from layer to layer: float32 for a delta
    stack, the weights' type for the three other hybrid stacks."""
    cfg = cfglib.PRESETS[preset]
    p16 = jax.eval_shape(lambda k: decoder.init_params(cfg, k, jnp.bfloat16),
                         jax.random.PRNGKey(0))
    closed = jax.make_jaxpr(lambda p, t: decoder.prefill_chunk(p, cfg, t))(
        p16, tokens(16)[None])
    carried = [v.aval.dtype for e in _eqns(closed.jaxpr)
               if e.primitive.name == "scan" for v in e.outvars
               if v.aval.shape == (1, 16, cfg.dim)]
    assert carried and set(carried) == {jnp.dtype(want)}


def test_a_stack_has_one_recurrent_kind():
    for kinds, extra in (("dmAdddAd", dict(ssm_heads=4)), ("dcAdddAd", {}),
                         ("dwAdddAd", dict(sliding_window=8))):
        with pytest.raises(AssertionError, match="recurrent kind"):
            dataclasses.replace(CFG, layer_kinds=kinds, **extra).validate()
    with pytest.raises(AssertionError):
        dataclasses.replace(CFG, delta_heads=0).validate()


# -- the state: pieces, padding, inactive slots --------------------------

@pytest.mark.parametrize("pieces", [(40,), (16, 24), (16, 16, 8), (24, 16),
                                    (1, 1, 38), (7, 33)])
def test_prefill_in_pieces_equals_one_piece(params, pieces):
    """One prefill, and the same prompt through extends of the cache (pieces
    shorter than the convolution's reach and than a block among them): state
    and last logits agree."""
    toks = tokens(40, seed=1)
    want_l, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None])
    K, V = empty_cache(1)
    at = 0
    for n in pieces:
        lg, K, V = decoder.forward_with_cache(
            params, CFG, toks[None, at:at + n], K, V,
            jnp.array([at], jnp.int32))
        at += n
    # float32's rounding through eight layers, in another order of sums
    assert np.allclose(lg[0, -1], want_l[0, -1], atol=1e-5)
    assert np.allclose(K["ssm"], ks["ssm"], atol=1e-5)
    assert np.allclose(V["conv"], vs["conv"], atol=1e-5)


@pytest.mark.parametrize("n_valid", [1, 2, 5, 16, 31])
def test_padded_positions_never_alter_the_state(params, n_valid):
    """A prefill bucket pads the prompt: state, convolution inputs and the
    last real position's logits are those of the unpadded prompt, and the
    padding's content is nothing to either, to the bit."""
    toks = tokens(32, seed=2)
    f = jax.jit(lambda p, t, n: decoder.prefill_chunk(p, CFG, t, n_valid=n))
    lg, ks, vs = f(params, toks[None], jnp.int32(n_valid))
    lg0, ks0, vs0 = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, toks[None, :n_valid])
    assert np.allclose(ks["ssm"], ks0["ssm"], atol=1e-5)
    assert np.allclose(vs["conv"], vs0["conv"], atol=1e-5)
    assert np.allclose(lg[0, 0], lg0[0, -1], atol=1e-5)
    other = toks.copy()
    other[n_valid:] = (other[n_valid:] + 7) % CFG.vocab_size
    _, ks1, vs1 = f(params, other[None], jnp.int32(n_valid))
    assert np.array_equal(ks["ssm"], ks1["ssm"])
    assert np.array_equal(vs["conv"], vs1["conv"])


def test_a_row_with_nothing_real_keeps_its_bits(params):
    """An extend whose row has no real position (n_valid 0) beside one that
    has: the first row's state and convolution inputs keep their very bits."""
    K, V = empty_cache(2)
    _, K, V = decoder.forward_with_cache(
        params, CFG, tokens(16, seed=12).reshape(2, 8), K, V,
        jnp.zeros((2,), jnp.int32))
    _, K1, V1 = decoder.forward_with_cache(
        params, CFG, tokens(16, seed=13).reshape(2, 8), K, V,
        jnp.full((2,), 8, jnp.int32), n_valid=jnp.array([0, 5], jnp.int32))
    assert np.array_equal(K1["ssm"][:, 0], K["ssm"][:, 0])
    assert np.array_equal(V1["conv"][:, 0], V["conv"][:, 0])
    assert not np.array_equal(K1["ssm"][:, 1], K["ssm"][:, 1])


def test_admit_many_rows_keep_their_own_lengths(params):
    """Batched admission: each row's state ends at its own prompt's end."""
    eng = make_engine(params)
    a, b = tokens(9, seed=4), tokens(14, seed=5)
    eng.admit_many([0, 2], [a, b], [GREEDY, GREEDY])
    one = make_engine(params)
    one.admit(1, b, GREEDY)
    for got, want in zip(state_of(eng, 2), state_of(one, 1)):
        assert np.allclose(got, want, atol=1e-5)
    assert not np.allclose(state_of(eng, 0)[0], state_of(eng, 2)[0],
                           atol=1e-3)


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
        for b, a in zip(before[s], after[s]):
            assert np.array_equal(b, a), s
    assert not np.array_equal(before[0][0], after[0][0])
    assert not np.array_equal(before[0][1], after[0][1])
    # and the parked slot goes on as if nothing had happened in between
    t = eng.extend(1, tokens(30, seed=6), 16, GREEDY)
    fresh = make_engine(params, cache=getattr(jnp, cache))
    t_fresh = fresh.admit(1, tokens(30, seed=6), GREEDY)
    # the tail read the first piece's keys and values back from the cache:
    # through int8 they are not what a one-piece prefill attends to
    exact = cache == "float32"
    assert t == t_fresh or not exact
    for got, want in zip(state_of(eng, 1), state_of(fresh, 1)):
        assert np.allclose(got, want, atol=1e-5 if exact else 2e-2)


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
    assert shared_engine.recurrent
    with pytest.raises(ValueError, match="contiguous cache"):
        make_engine(params, paged=True, page_size=16)


# -- serving defaults, accounting, metrics ------------------------------

@pytest.mark.parametrize("preset, slots", [
    ("olmo-hybrid-7b", 32), ("granite-4.0-h-small", 32),
    ("lfm2-8b-a1b", 32), ("k-exaone-236b-a23b", 64)])
def test_zero_config_resolution_on_the_chip(monkeypatch, preset, slots):
    """A stack with ``layer_kinds`` serves bfloat16 weights from an int8
    contiguous cache in chunks of 32; a stack without experts wants 32
    slots, and the three routed stacks keep what they had."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = cfglib.PRESETS[preset]
    assert englib.resolve_engine_dtype(cfg, "tpu") == "bfloat16"
    ecfg = englib.resolve_serving_defaults(
        EngineConfig(max_slots=0, max_seq_len=4096, decode_chunk=0,
                     paged=None, page_size=0, n_pages=None,
                     cache_dtype=jnp.int8), cfg, None)
    assert (ecfg.paged, ecfg.max_slots, ecfg.decode_chunk) == (False, slots,
                                                               32)
    assert englib._recurrent_slots(cfg) == slots
    conf = work.load_conf(os.path.join(REPO, "benchmark", "configs",
                                       preset + ".json"))
    want = conf["expected_resolution"]
    assert (want["weights"], want["kv"], want["paged"], want["max_slots"],
            want["decode_chunk"]) == ("bfloat16", "int8", False, slots, 32)
    assert conf["saturating_clients"] == slots


def test_dense_presets_resolve_as_they_did(monkeypatch):
    """No ``layer_kinds``, no experts: int8 under 4e9 parameters, int4 from
    there on, as before; the rule for hybrid stacks moves neither."""
    for name, want in (("starcoder2", "int8"), ("phi", "int8"),
                       ("mistral", "int4")):
        cfg = cfglib.PRESETS[name]
        assert not cfg.layer_kinds
        assert englib.resolve_engine_dtype(cfg, "tpu") == want
    # the cut's parameter count alone would have said int4 or int8
    assert PRESET.n_params > 3e9
    assert englib.resolve_engine_dtype(PRESET, "cpu") == "float32"


def test_a_heavy_state_halves_the_slots():
    """The halving under 2 GiB of carried state stays: a delta stack whose
    slot carries 4x as much gets 16 slots."""
    heavy = dataclasses.replace(PRESET, delta_value_dim=4 * 192)
    assert 32 * heavy.ssm_state_bytes > 2 << 30
    assert englib._recurrent_slots(heavy) == 16


def test_accounting_prices_the_new_layers():
    cfg = PRESET
    d, periods = 3840, cfg.n_layers // 4
    delta = (2 * d * (11520 + 5760 + 60) + 2 * 5760 * d + 2 * 4 * 11520
             + 7 * 30 * 96 * 192)
    attn = 2 * 4 * d * 3840
    mlp = 6 * d * 11008
    assert accounting.per_token_flops(cfg) == pytest.approx(
        periods * (3 * delta + attn + 4 * mlp) + 2 * d * 100352)
    # the full layers' span alone
    assert accounting.attn_span_flops(cfg, 0, 1) == periods * 4.0 * 3840


def test_state_gauge_and_ps_details(params, monkeypatch):
    monkeypatch.setenv("TPU_WARM_BUCKETS", "0")   # nothing is served here
    from ollama_operator_tpu.runtime.service import LoadedModel
    from ollama_operator_tpu.tokenizer.tokenizer import Tokenizer
    tok = Tokenizer("llama", [f"t{i}" for i in range(CFG.vocab_size)],
                    bos_id=1, eos_id=2)
    lm = LoadedModel("olmo", CFG, params, tok, ecfg=EngineConfig(
        max_slots=2, max_seq_len=64, cache_dtype=jnp.float32,
        min_prefill_bucket=16))
    try:
        # 6 delta layers x (4 x 8 x 16 state + 3 x 128 inputs) float32
        want = 2 * CFG.ssm_state_bytes
        assert want == 2 * 6 * (4 * 8 * 16 + 3 * 128) * 4
        assert lm.engine.state_bytes == want
        assert lm.engine.cache_bytes["window"] == 0
        assert lm.engine.kv_bytes > want
        assert f'tpu_model_cache_bytes{{kind="state"}} {want}' in \
            METRICS.render().replace(".0", "")
    finally:
        lm.unload()
    assert not re.search(r"^tpu_model_cache_bytes\S* \d",
                         METRICS.render(), re.M)


# -- the benchmark's readers and arithmetic ------------------------------

NEW_READERS = ("decode_delta_ms_per_step", "delta_state_roofline",
               "state_mb_per_slot")


def reader_ctx(conf, before=None, after=None, **resolved):
    return types.SimpleNamespace(
        conf=conf, notes={}, resolved={"decode_chunk": 2,
                                       "weights": "bfloat16", **resolved},
        peaks={"hbm_bytes_per_s": 819e9}, trace_before=before or {},
        trace_after=after or {}, after=after or {})


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_none_without_a_trace(name, tmp_path, monkeypatch):
    """The driver runs the new readers on the parent's program too, which has
    neither the scopes nor such a state: nothing to read is None, no error."""
    from benchmark import run, trace_spans
    monkeypatch.setattr(trace_spans.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    assert run.layer_reader(name).read(
        reader_ctx(work.load_conf(CONF_PATH))) is None


def fake_trace(tmp_path, monkeypatch, with_delta):
    """Two complete runs of a decode module of two steps each, with
    operations under ``delta.*`` scopes or without."""
    from benchmark import delta_spans, trace_spans
    meta = {1: ("jit__decode_n(7)", ""),
            2: ("%fusion.1 = f32[] fusion()",
                "jit(_decode_n)/delta.update/mul"),
            3: ("%fusion.2 = f32[] fusion()",
                "jit(_decode_n)/delta.in_proj/dot"),
            4: ("%fusion.3 = f32[] fusion()", "jit(_decode_n)/mlp/dot")}
    ops = []
    for t0 in (0, 2000):
        ops += [(t0 + 100, t0 + 400, 2 if with_delta else 4),
                (t0 + 400, t0 + 600, 3 if with_delta else 4),
                (t0 + 600, t0 + 900, 4)]
    pl = [{"name": "/device:TPU:0", "meta": meta, "lines": [
        {"name": "XLA Modules", "events": [(0, 1000, 1), (2000, 3000, 1)]},
        {"name": "XLA Ops", "events": ops}]}]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    delta_spans._CACHE.clear()
    monkeypatch.setattr(trace_spans, "find_trace", lambda w=None: str(path))
    monkeypatch.setattr(trace_spans, "reduce",
                        lambda w=None: trace_spans.reduce_planes(pl))
    monkeypatch.setattr(trace_spans, "read_planes", lambda p: pl)


@pytest.mark.parametrize("with_delta", [True, False])
def test_delta_spans_reads_its_scopes_from_a_trace(tmp_path, monkeypatch,
                                                   with_delta):
    """Self time under each ``delta.*`` scope over the steps of the decode
    module's complete runs; a trace without them reads None."""
    from benchmark import delta_spans
    fake_trace(tmp_path, monkeypatch, with_delta)
    got = delta_spans.step_seconds(2)
    ctx = reader_ctx({})
    if with_delta:
        assert got == pytest.approx({"delta.update": 150e-12,
                                     "delta.in_proj": 100e-12})
        assert delta_spans.step_ms(ctx) == pytest.approx(250e-9)
        assert delta_spans.step_ms(ctx, ("delta.update",)) == pytest.approx(
            150e-9)
        assert ctx.notes["decode_delta_parts_ms"]["delta.in_proj"] == \
            pytest.approx(100e-9)
    else:
        assert got is None and delta_spans.step_ms(ctx) is None


def test_the_rooflines_share_is_bytes_over_the_updates_time(tmp_path,
                                                            monkeypatch):
    """``delta_state_roofline``: the work file's bytes of a step at the batch
    the counters give, over the HBM rate, over the time under
    ``delta.update``."""
    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    fake_trace(tmp_path, monkeypatch, True)
    reg = Metrics()
    before = prom.parse(reg.render())
    # 5 dispatches of 2 steps that advanced 8 sequences each
    for _ in range(5):
        reg.observe("tpu_model_dispatch_seconds", 0.01, '{kind="decode"}')
    reg.inc("tpu_model_useful_tokens_total", 5 * 2 * 8.0, '{kind="decode"}')
    conf = work.load_conf(CONF_PATH)
    ctx = reader_ctx(conf, before, prom.parse(reg.render()))
    got = run.layer_reader("delta_state_roofline").read(ctx)
    w = work.load_module(os.path.join(conf["_dir"], conf["work"]))
    least_s = w.delta_state_bytes_step(conf, 8.0) / 819e9
    assert got == pytest.approx(100.0 * least_s / 150e-12)
    assert ctx.notes["delta_state_roofline"]["batch"] == pytest.approx(8.0)


@pytest.mark.parametrize("by, want", [
    (None, None),                                   # no such gauge
    ({"full": 4.0e9, "window": 0.0, "state": 0.0}, None),   # a dense stack
    ({"full": 4.0e9, "window": 0.0, "state": 32 * 28.2e6}, 28.2)])
def test_state_mb_per_slot_reads_the_gauge(by, want):
    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    reg.inc("tpu_model_generated_tokens_total", 5.0)
    for kind, v in (by or {}).items():
        reg.gauge_fn("tpu_model_cache_bytes", lambda v=v: v,
                     f'{{kind="{kind}"}}')
    got = run.layer_reader("state_mb_per_slot").read(
        reader_ctx({}, after=prom.parse(reg.render()), max_slots=32))
    assert got == (None if want is None else pytest.approx(want))


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    """One configuration, one cell under the mix that stands, the three new
    metrics on it alone."""
    from benchmark import run
    cell = run.find_cell(CELL)
    assert (cell.chips, cell.mix_name) == (1, "decode-saturated")
    assert cell.conf["preset"] == "olmo-hybrid-7b"
    assert cell.conf[cell.mix["clients"]] == 32
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) | {"decode_step_roofline",
                               "device_idle_share"} <= names
    assert not {"decode_ssm_ms_per_step", "ssm_state_roofline",
                "decode_moe_ms_per_step", "decode_conv_ms_per_step"} & names
    bench = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert (m["workloads"], m["moves"]) == ([CELL], "out_tok_s")
    for other in ("granite-4.0-h-small.decode-saturated",
                  "phi-2.decode-saturated"):
        cell = run.find_cell(other)
        assert not set(NEW_READERS) & {m["name"] for m in cell.per_layer}


def test_the_configurations_work_arithmetic():
    """The sizes the issue reckons with, from the configuration's own file,
    against the program's own counts."""
    conf = work.load_conf(CONF_PATH)
    w = work.load_module(os.path.join(conf["_dir"], conf["work"]))
    periods = PRESET.n_layers // 4
    assert (w.n_linear(conf), w.n_full(conf)) == (3 * periods, periods)
    assert w.linear_params(conf) == 3840 * 17340 + 5760 * 3840
    assert w.attention_params(conf) == 4 * 3840 * 3840
    assert w.mlp_params(conf) == 3 * 3840 * 11008
    assert w.n_params(conf) == pytest.approx(PRESET.n_params, rel=1e-12)
    # one sequence: the program's own count of what a slot carries
    assert w.n_linear(conf) * w.state_bytes(conf) == PRESET.ssm_state_bytes
    assert w.delta_state_bytes_step(conf, 32) == \
        32 * 2 * PRESET.ssm_state_bytes
    # a step reads every matrix but the embedding once, and the state both
    # ways
    total = work.weight_bytes_step(conf, 32, "bfloat16") \
        - w.delta_state_bytes_step(conf, 32)
    assert total == pytest.approx(
        2 * (PRESET.n_params - 3840 * 100352), rel=1e-12)
    assert work.kv_bytes_per_token(conf, "int8") == \
        2 * periods * 30 * (128 + 4)
    assert work.attn_flops_per_pair(conf) == 4 * periods * 30 * 128
    assert work.matmul_flops_per_token(conf) == pytest.approx(
        accounting.per_token_flops(PRESET)
        - 3 * periods * 2 * 4 * 11520, rel=1e-9)
