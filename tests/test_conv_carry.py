"""What a causal convolution carries from one call to the next: the K-1 inputs
before a row's last real position. ``decoder._causal_conv`` keeps them by a
gather a row where a call brings several positions (a prefill piece: rows
end at places of their own) and by a select over whole arrays where it
brings one (a decode step: a row takes the position or sits out). The gather
form is kept here as the reference: both only move float32 values, so the
two agree to the bit. CPU, toy widths; the three mixers that share the
function (Mamba-2's, the short convolution's, the delta rule's) through
their toy presets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder

MIXERS = {"ssm": "tiny-hybrid", "conv": "tiny-lfm2",
          "delta": "tiny-olmo-hybrid"}


def by_gather(conv, row, new, w, n_valid, bias=None, act=None):
    """``_causal_conv`` as every call ran it before the one-position case
    was told apart: the kept inputs by a dynamic slice a row."""
    K, T, f32 = w.shape[0], new.shape[1], jnp.float32
    prev = lax.dynamic_index_in_dim(conv, row, 0, keepdims=False)
    cat = jnp.concatenate([prev, new.astype(f32)], axis=1)
    w = w.astype(f32)
    out = None if bias is None else bias.astype(f32)
    for j in range(K):
        tap = w[j] * cat[:, j:j + T]
        out = tap if out is None else out + tap
    if act is not None:
        out = act(out)
    prev = jax.vmap(lambda c, n: lax.dynamic_slice_in_dim(c, n, K - 1, 0)
                    )(cat, n_valid)
    return out, lax.dynamic_update_index_in_dim(conv, prev, row, 0)


def live_rows(mix, B):
    """n_valid [B] of a decode step: 1 where a slot takes the position."""
    if mix == "all":
        return np.ones(B, np.int32)
    if mix == "none":
        return np.zeros(B, np.int32)
    return (np.arange(B) % 3 != 1).astype(np.int32)     # B = 1: the one sits


def conv_inputs(K, B, T, seed, C=24, Lr=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (Lr, B, K - 1, C)),
            jax.random.normal(ks[1], (B, T, C)).astype(jnp.bfloat16),
            jax.random.normal(ks[2], (K, C)), jax.random.normal(ks[3], (C,)))


@pytest.mark.parametrize("dressed", [False, True], ids=["bare", "bias+silu"])
@pytest.mark.parametrize("mix", ["all", "none", "mixed"])
@pytest.mark.parametrize("B", [1, 4, 32])
@pytest.mark.parametrize("K", [3, 4])
def test_one_position_is_the_gather_to_the_bit(K, B, mix, dressed):
    """T = 1: the outputs and the whole leaf equal the gather form's, bit
    for bit; a row that sits out keeps its bits, a row that decodes holds
    its old inputs shifted by one and the new one; the other layers' rows
    are untouched."""
    conv, new, w, bias = conv_inputs(K, B, 1, seed=K * 100 + B)
    n_valid = jnp.asarray(live_rows(mix, B))
    kw = dict(bias=bias, act=jax.nn.silu) if dressed else {}
    row = jnp.int32(1)
    f = jax.jit(lambda *a: decoder._causal_conv(*a, **kw))
    g = jax.jit(lambda *a: by_gather(*a, **kw))
    out, leaf = f(conv, row, new, w, n_valid)
    want_out, want_leaf = g(conv, row, new, w, n_valid)
    assert out.dtype == jnp.float32 and out.shape == (B, 1, conv.shape[-1])
    assert np.array_equal(out, want_out)
    assert np.array_equal(leaf, want_leaf)
    leaf, conv = np.asarray(leaf), np.asarray(conv)
    assert np.array_equal(leaf[[0, 2]], conv[[0, 2]])
    live = np.asarray(n_valid) > 0
    assert np.array_equal(leaf[1][~live], conv[1][~live])
    assert np.array_equal(leaf[1][live][:, :-1], conv[1][live][:, 1:])
    assert np.array_equal(leaf[1][live][:, -1],
                          np.asarray(new.astype(jnp.float32))[live][:, 0])


@pytest.mark.parametrize("n_valid", [[5, 0, 2, 7], [1, 1, 0, 1]])
@pytest.mark.parametrize("K", [3, 4])
def test_several_positions_still_end_each_row_at_its_own_place(K, n_valid):
    """T > 1 keeps the gather: a row's kept inputs are those before ITS
    last real position, so the same as feeding it that many positions."""
    B, T = 4, 7
    conv, new, w, _ = conv_inputs(K, B, T, seed=K)
    out, leaf = decoder._causal_conv(conv, jnp.int32(0), new, w,
                                     jnp.asarray(n_valid, jnp.int32))
    want_out, want_leaf = by_gather(conv, jnp.int32(0), new, w,
                                    jnp.asarray(n_valid, jnp.int32))
    assert np.array_equal(out, want_out) and np.array_equal(leaf, want_leaf)
    for b, n in enumerate(n_valid):
        cat = np.concatenate([conv[0, b], new[b].astype(jnp.float32)])
        assert np.array_equal(leaf[0, b], cat[n:n + K - 1])


@pytest.mark.parametrize("K", [3, 4])
def test_steps_of_one_position_are_one_longer_call(K):
    """Seven positions fed one a call, a row sitting every third call out,
    leave the inputs and give the outputs of one call over the positions
    the row took."""
    B, T = 4, 7
    conv, new, w, bias = conv_inputs(K, B, T, seed=7 + K)
    row = jnp.int32(2)
    takes = np.array([[(t + b) % 3 != 0 for t in range(T)] for b in range(B)])
    leaf, outs = conv, []
    for t in range(T):
        o, leaf = decoder._causal_conv(
            leaf, row, new[:, t:t + 1], w, jnp.asarray(takes[:, t], jnp.int32),
            bias, jax.nn.silu)
        outs.append(np.asarray(o[:, 0]))
    for b in range(B):
        took = np.flatnonzero(takes[b])
        o, one = decoder._causal_conv(
            conv[:, b:b + 1], row, new[b:b + 1, took], w,
            jnp.array([len(took)], jnp.int32), bias, jax.nn.silu)
        assert np.array_equal(leaf[2, b], one[2, 0]), b
        got = np.stack([outs[t][b] for t in took])
        assert np.allclose(got, o[0], atol=1e-6), b


def _params(cfg):
    return decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def _empty(cfg, B, S=64):
    kc = jnp.zeros((cfg.n_attn_layers, B, cfg.n_kv_heads, S, cfg.head_dim))
    return decoder.join_state(kc, kc, decoder.empty_state(cfg, B))


@pytest.mark.parametrize("pieces", [(24,), (16, 8), (7, 12, 5)])
@pytest.mark.parametrize("mixer", sorted(MIXERS))
def test_a_decode_step_after_pieces_is_one_longer_prefill(mixer, pieces):
    """A prompt through prefill pieces (the gather) and then three decode
    steps (the select): the carried inputs, the recurrent state and the last
    logits are those of ONE prefill over all the positions."""
    cfg = cfglib.PRESETS[MIXERS[mixer]]
    params = _params(cfg)
    toks = np.random.default_rng(3).integers(3, cfg.vocab_size, (27,)
                                             ).astype(np.int32)
    want_l, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, cfg, t))(params, toks[None])
    K, V = _empty(cfg, 1)
    step = jax.jit(lambda p, t, K, V, n: decoder.forward_with_cache(
        p, cfg, t, K, V, n))
    at = 0
    for n in pieces + (1, 1, 1):
        lg, K, V = step(params, toks[None, at:at + n], K, V,
                        jnp.array([at], jnp.int32))
        at += n
    assert at == 27
    # the inputs below the convolution went through another program's
    # matmuls (another T): equal up to the order of their sums
    assert np.allclose(V["conv"], vs["conv"], atol=1e-6)
    if "ssm" in ks:
        assert np.allclose(K["ssm"], ks["ssm"], atol=1e-5)
    assert np.allclose(lg[0, -1], want_l[0, -1], atol=1e-5)


@pytest.mark.parametrize("mixer", sorted(MIXERS))
def test_a_slot_that_sits_a_step_out_keeps_its_inputs(mixer):
    """Two slots with a prefix each; slot 1 sits the decode step out
    (``n_valid`` 0): every bit of what it carries stays, slot 0 moves."""
    cfg = cfglib.PRESETS[MIXERS[mixer]]
    params = _params(cfg)
    toks = np.random.default_rng(4).integers(3, cfg.vocab_size, (2, 9)
                                             ).astype(np.int32)
    K, V = _empty(cfg, 2)
    _, K, V = decoder.forward_with_cache(params, cfg, toks[:, :8], K, V,
                                         jnp.zeros(2, jnp.int32))
    _, K1, V1 = decoder.forward_with_cache(
        params, cfg, toks[:, 8:], K, V, jnp.array([8, 8], jnp.int32),
        n_valid=jnp.array([1, 0], jnp.int32))
    assert np.array_equal(V1["conv"][:, 1], V["conv"][:, 1])
    assert np.array_equal(V1["conv"][:, 0, :-1], V["conv"][:, 0, 1:])
    assert not np.array_equal(V1["conv"][:, 0], V["conv"][:, 0])
    if "ssm" in K:
        assert np.array_equal(K1["ssm"][:, 1], K["ssm"][:, 1])


@pytest.mark.parametrize("program", ["decode", "admit"])
@pytest.mark.parametrize("mixer", sorted(MIXERS))
def test_the_decode_program_gathers_nothing_under_the_convolution(mixer,
                                                                   program):
    """A count, not a time: under ``<mixer>.conv`` the lowered decode
    program holds no gather and no loop (what the chip's compiler turns into
    a ``while`` of one-row ``dynamic-update-slice``s over the slots), only
    the one write of the layer's row; a prefill piece still gathers."""
    import re
    cfg = cfglib.PRESETS[MIXERS[mixer]]
    params = _params(cfg)
    B, T = (4, 1) if program == "decode" else (2, 16)
    K, V = _empty(cfg, B, 32)
    toks = np.zeros((B, T), np.int32)
    text = jax.jit(lambda p, t, K, V, n, v: decoder.forward_with_cache(
        p, cfg, t, K, V, n, n_valid=v)).lower(
        params, toks, K, V, jnp.zeros(B, jnp.int32),
        jnp.ones(B, jnp.int32)).as_text(debug_info=True)
    scope = re.escape(f"{mixer}.conv")
    locs = set(re.findall(r'(#loc\d+) = loc\("[^"]*/' + scope + r'/[^"]*"',
                          text))
    assert locs, "the scope is in the program"

    def under(op):
        return [ln for ln in text.split("\n")
                if re.search(r"\bstablehlo\." + op + r"\b", ln)
                and re.search(r"loc\((#loc\d+)\)", ln)
                and re.search(r"loc\((#loc\d+)\)", ln).group(1) in locs]
    # one write of the layer's row for each place the scan traces the mixer
    # (lfm2's leading dense layers and its routed ones: two)
    writes = len(under("dynamic_update_slice"))
    assert 1 <= writes <= 2
    assert not under("while") and not under("scatter")
    assert len(under("gather")) == (writes if program == "admit" else 0)
