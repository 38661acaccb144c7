"""Sampler semantics: masks, penalties, greedy/seeded behaviour."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.ops import sampling


def mk_sp(B, **kw):
    return sampling.SamplingParams.make(B, **kw)


def test_greedy_when_temperature_zero():
    logits = jnp.array([[0.1, 2.0, -1.0, 0.5]])
    sp = mk_sp(1, temperature=0.0, repeat_penalty=1.0)
    tok = sampling.sample(logits, jnp.zeros((1, 4), jnp.int32), sp,
                          jax.random.key(0))
    assert int(tok[0]) == 1


def test_top_k_restricts_support():
    logits = jnp.array([[0.0, 5.0, 4.0, -2.0, 1.0]])
    sp = mk_sp(1, temperature=1.0, top_k=2, top_p=1.0, repeat_penalty=1.0)
    counts = jnp.zeros((1, 5), jnp.int32)
    seen = set()
    for i in range(50):
        tok = sampling.sample(logits, counts, sp, jax.random.key(i))
        seen.add(int(tok[0]))
    assert seen <= {1, 2}


def test_top_p_keeps_head_of_distribution():
    # one dominant token (p≈0.99) → top_p=0.5 must always pick it
    logits = jnp.array([[10.0, 1.0, 0.0, -1.0]])
    sp = mk_sp(1, temperature=1.0, top_k=0, top_p=0.5, repeat_penalty=1.0)
    counts = jnp.zeros((1, 4), jnp.int32)
    for i in range(20):
        tok = sampling.sample(logits, counts, sp, jax.random.key(i))
        assert int(tok[0]) == 0


def test_repeat_penalty_discourages_seen_tokens():
    logits = jnp.array([[2.0, 1.9]])
    counts = jnp.array([[5, 0]], jnp.int32)  # token 0 was generated already
    sp = mk_sp(1, temperature=0.0, repeat_penalty=2.0)
    tok = sampling.sample(logits, counts, sp, jax.random.key(0))
    assert int(tok[0]) == 1  # 2.0/2.0 = 1.0 < 1.9


def test_per_slot_seeds_reproducible():
    logits = jnp.tile(jnp.array([[0.0, 0.1, 0.2, 0.3]]), (2, 1))
    sp = mk_sp(2, temperature=1.0, top_k=0, top_p=1.0, repeat_penalty=1.0)
    counts = jnp.zeros((2, 4), jnp.int32)
    keys = jnp.stack([jax.random.key(7), jax.random.key(7)])
    t1 = sampling.sample(logits, counts, sp, keys)
    t2 = sampling.sample(logits, counts, sp, keys)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert int(t1[0]) == int(t1[1])  # same seed, same logits → same token


def test_frequency_and_presence_penalty():
    logits = jnp.array([[1.0, 0.9]])
    counts = jnp.array([[3, 0]], jnp.int32)
    sp = sampling.SamplingParams.make(1, temperature=0.0, repeat_penalty=1.0,
                                      presence_penalty=0.05,
                                      frequency_penalty=0.05)
    tok = sampling.sample(logits, counts, sp, jax.random.key(0))
    assert int(tok[0]) == 1  # 1.0 - 0.05 - 3*0.05 = 0.8 < 0.9

def test_typical_p_drops_atypical_outliers():
    # wide near-uniform body + one modestly-peaked head: entropy sits at
    # the body's surprise, so the HEAD is the atypical token (its surprise
    # is far below H) — a tight typical_p keeps the body and drops the
    # argmax (locally-typical sampling; llama.cpp llama_sampler_typical)
    logits = jnp.array([[2.0] + [0.0] * 99])
    sp = mk_sp(1, temperature=1.0, top_k=0, top_p=1.0, min_p=0.0,
               typical_p=0.5, repeat_penalty=1.0)
    counts = jnp.zeros((1, 100), jnp.int32)
    seen = {int(sampling.sample(logits, counts, sp, jax.random.key(i))[0])
            for i in range(60)}
    assert 0 not in seen and len(seen) > 1


def test_typical_p_off_is_identity():
    logits = jnp.array([[3.0, 2.0, 1.0, 0.0]])
    counts = jnp.zeros((1, 4), jnp.int32)
    base = mk_sp(1, temperature=1.0, repeat_penalty=1.0)
    typ = mk_sp(1, temperature=1.0, repeat_penalty=1.0, typical_p=1.0)
    for i in range(10):
        t1 = sampling.sample(logits, counts, base, jax.random.key(i))
        t2 = sampling.sample(logits, counts, typ, jax.random.key(i))
        assert int(t1[0]) == int(t2[0])


def test_mirostat_v2_truncates_by_surprise_budget():
    # mu near zero admits only the top candidate (surprise of everything
    # else exceeds the budget) even though the static filters are wide open
    logits = jnp.array([[3.0, 2.5, 2.0, 1.0, 0.0]])
    counts = jnp.zeros((1, 5), jnp.int32)
    sp = mk_sp(1, temperature=1.0, top_k=0, top_p=1.0, repeat_penalty=1.0,
               mirostat=2, mirostat_tau=5.0, mirostat_eta=0.1)
    mu = jnp.array([0.05], jnp.float32)
    for i in range(20):
        tok, _ = sampling.sample(logits, counts, sp, jax.random.key(i), mu)
        assert int(tok[0]) == 0


def test_mirostat_mu_moves_toward_tau():
    # observed surprise far below tau → mu must RISE by eta*(tau - s)
    logits = jnp.array([[10.0, 0.0, 0.0, 0.0]])
    counts = jnp.zeros((1, 4), jnp.int32)
    tau, eta = 5.0, 0.5
    sp = mk_sp(1, temperature=1.0, top_k=0, top_p=1.0, repeat_penalty=1.0,
               mirostat=2, mirostat_tau=tau, mirostat_eta=eta)
    mu = jnp.array([2 * tau], jnp.float32)
    _, mu2 = sampling.sample(logits, counts, sp, jax.random.key(0), mu)
    assert float(mu2[0]) > float(mu[0]) - 1e-6  # s≈0 → mu += eta*tau
    np.testing.assert_allclose(float(mu2[0]), 2 * tau + eta * tau, atol=0.2)


def test_mirostat_off_slots_keep_mu_frozen():
    logits = jnp.tile(jnp.array([[1.0, 0.5, 0.0]]), (2, 1))
    counts = jnp.zeros((2, 3), jnp.int32)
    sp = sampling.SamplingParams.make(2, temperature=1.0,
                                      repeat_penalty=1.0)
    sp = dataclasses.replace(sp, mirostat=jnp.array([0, 2], jnp.int32))
    mu = jnp.array([7.7, 10.0], jnp.float32)
    keys = jnp.stack([jax.random.key(1), jax.random.key(2)])
    _, mu2 = sampling.sample(logits, counts, sp, keys, mu)
    assert float(mu2[0]) == np.float32(7.7)  # mirostat off → untouched
    assert float(mu2[1]) != 10.0         # mirostat on → updated


def test_mirostat_v1_zipf_cut_keeps_head():
    # steep zipf-ish distribution with a tiny mu: the derived k cut must
    # restrict sampling to the head of the distribution
    V = 64
    logits = (-1.5 * jnp.log(jnp.arange(1, V + 1, dtype=jnp.float32)))[None]
    counts = jnp.zeros((1, V), jnp.int32)
    sp = mk_sp(1, temperature=1.0, top_k=0, top_p=1.0, repeat_penalty=1.0,
               mirostat=1, mirostat_tau=2.0, mirostat_eta=0.1)
    mu = jnp.array([1.0], jnp.float32)
    seen = set()
    for i in range(40):
        tok, _ = sampling.sample(logits, counts, sp, jax.random.key(i), mu)
        seen.add(int(tok[0]))
    assert max(seen) < 8  # k ≈ (eps·2^mu / (1-V^-eps))^(1/s) is small


def test_typical_p_zero_keeps_most_typical_token():
    # a zero budget must NOT blank the distribution — min_keep=1 keeps
    # exactly the most-typical candidate (llama.cpp's limit behaviour),
    # deterministically. Here the p≈0.97 head is also the most typical
    # (its surprise is nearest the low entropy).
    logits = jnp.array([[5.0, 1.0, 0.0, -1.0]])
    counts = jnp.zeros((1, 4), jnp.int32)
    sp = mk_sp(1, temperature=1.0, top_k=0, top_p=1.0, repeat_penalty=1.0,
               typical_p=0.0)
    for i in range(15):
        assert int(sampling.sample(logits, counts, sp,
                                   jax.random.key(i))[0]) == 0


def test_typical_p_kept_set_is_temperature_invariant():
    # llama.cpp evaluates typ_p at T=1 (chain: top_k → typ_p → … → temp):
    # the same logits with different temperatures must keep the same set
    logits = jnp.array([[2.0] + [0.0] * 99])
    counts = jnp.zeros((1, 100), jnp.int32)
    for temp in (0.3, 1.0, 2.5):
        sp = mk_sp(1, temperature=temp, top_k=0, top_p=1.0,
                   repeat_penalty=1.0, typical_p=0.5)
        seen = {int(sampling.sample(logits, counts, sp,
                                    jax.random.key(i))[0])
                for i in range(40)}
        assert 0 not in seen   # the atypical head stays dropped at any T


def test_min_p_anchors_to_surviving_max_after_typical_drop():
    # typical_p drops the global argmax; min_p must then anchor to the
    # max SURVIVING probability, culling the low-prob tail (the
    # column-0 anchor would read ~0 and keep everything)
    logits = jnp.array([[2.0] + [0.0] * 30 + [-1.2] * 30])
    counts = jnp.zeros((1, 61), jnp.int32)
    sp = mk_sp(1, temperature=1.0, top_k=0, top_p=1.0, repeat_penalty=1.0,
               typical_p=0.838, min_p=0.4)
    seen = {int(sampling.sample(logits, counts, sp, jax.random.key(i))[0])
            for i in range(80)}
    assert 0 not in seen                      # typical dropped the head
    assert all(tok <= 30 for tok in seen)     # min_p culled the tail


def test_merge_options_clamps_invalid_mirostat():
    from ollama_operator_tpu.runtime.service import merge_options
    so, _, _ = merge_options({}, {"mirostat": 3})
    assert so.mirostat == 0        # llama.cpp: non-1/2 reads as off
    so, _, _ = merge_options({}, {"mirostat": 2, "mirostat_tau": 3.0})
    assert so.mirostat == 2 and so.mirostat_tau == 3.0


# -- the argmax branch: a step with no live sampling slot skips the
# candidate path, and returns what that path would have -------------------

def _batch(B=6, V=3000, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    logits = jax.random.normal(k1, (B, V), jnp.float32) * 4.0
    counts = jax.random.randint(k2, (B, V), 0, 3).astype(jnp.int32) \
        * (jax.random.uniform(k3, (B, V)) < 0.02)
    keys = jax.vmap(jax.random.fold_in)(
        jnp.broadcast_to(jax.random.key(seed + 1), (B,)), jnp.arange(B))
    mu = jnp.linspace(6.0, 12.0, B).astype(jnp.float32)
    return logits, counts.astype(jnp.int32), keys, mu


def _with(sp, **rows):
    """``sp`` with single entries overwritten: name={slot: value}."""
    out = {}
    for name, per_slot in rows.items():
        a = getattr(sp, name)
        for slot, v in per_slot.items():
            a = a.at[slot].set(v)
        out[name] = a
    return dataclasses.replace(sp, **out)


def _same_bits(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("case", [
    "no-penalties", "penalties", "grammar-masked-row", "mirostat-1",
    "mirostat-2", "shared-key"])
def test_all_greedy_batch_equals_candidate_path(case):
    logits, counts, keys, mu = _batch()
    B, V = logits.shape
    sp = mk_sp(B, temperature=0.0, repeat_penalty=1.0)
    if case == "penalties":
        sp = mk_sp(B, temperature=0.0, repeat_penalty=1.3,
                   presence_penalty=0.4, frequency_penalty=0.2)
    elif case == "grammar-masked-row":
        # the engine masks before it calls: all of a row but 5 tokens
        allowed = jnp.zeros((V,), bool).at[jnp.array([3, 70, 71, 900,
                                                      2999])].set(True)
        logits = logits.at[2].set(
            jnp.where(allowed, logits[2], sampling.NEG_INF))
    elif case.startswith("mirostat"):
        sp = _with(sp, mirostat={1: int(case[-1]), 4: int(case[-1])})
    elif case == "shared-key":
        keys = jax.random.key(11)
    got = jax.jit(sampling.sample)(logits, counts, sp, keys, mu)
    want = jax.jit(sampling.sample_candidates)(logits, counts, sp, keys, mu)
    _same_bits(got, want)
    # and the mu-less form
    np.testing.assert_array_equal(
        np.asarray(sampling.sample(logits, counts, sp, keys)),
        np.asarray(want[0]))
    if case == "grammar-masked-row":
        assert int(got[0][2]) in (3, 70, 71, 900, 2999)


@pytest.mark.parametrize("case", [
    "one-sampling-slot", "one-mirostat-slot", "live-mask-given",
    "shared-key"])
def test_mixed_batch_equals_candidate_path(case):
    logits, counts, keys, mu = _batch(seed=3)
    B = logits.shape[0]
    sp = mk_sp(B, temperature=0.0, repeat_penalty=1.1)
    sp = _with(sp, temperature={4: 0.8})
    live = None
    if case == "one-mirostat-slot":
        sp = _with(sp, mirostat={4: 2})
    elif case == "live-mask-given":
        live = jnp.ones((B,), jnp.int32).at[0].set(0)
    elif case == "shared-key":
        keys = jax.random.key(5)
    got = jax.jit(sampling.sample)(logits, counts, sp, keys, mu, live=live)
    want = jax.jit(sampling.sample_candidates)(logits, counts, sp, keys, mu)
    _same_bits(got, want)
    if case == "one-mirostat-slot":
        assert float(got[1][4]) != float(mu[4])     # the branch really ran


def test_non_live_sampling_slot_does_not_bring_the_candidates_back():
    """Vacant slots hold the default options (temperature 0.8): beside
    live greedy slots the step is an argmax step, and the live slots'
    tokens and mu are the candidate path's."""
    logits, counts, keys, mu = _batch(seed=5)
    B = logits.shape[0]
    sp = _with(mk_sp(B), temperature={0: 0.0, 1: 0.0, 3: 0.0})
    live = jnp.array([1, 1, 0, 1, 0, 0], jnp.int32)
    assert not bool(sampling.needs_candidates(sp.temperature, live))
    assert bool(sampling.needs_candidates(sp.temperature))
    assert bool(sampling.needs_candidates(
        sp.temperature, live.at[2].set(1)))      # a live sampling slot
    assert not bool(sampling.needs_candidates(
        sp.temperature, jnp.zeros((B,), jnp.int32)))
    toks, mu2 = jax.jit(sampling.sample)(logits, counts, sp, keys, mu,
                                         live=live)
    want_t, want_mu = sampling.sample_candidates(logits, counts, sp, keys,
                                                 mu)
    on = np.asarray(live) == 1
    np.testing.assert_array_equal(np.asarray(toks)[on],
                                  np.asarray(want_t)[on])
    np.testing.assert_array_equal(np.asarray(mu2)[on],
                                  np.asarray(want_mu)[on])


@pytest.mark.parametrize("seed", range(8))
def test_needs_candidates_numpy_agrees_with_jnp(seed):
    rng = np.random.default_rng(seed)
    for _ in range(25):
        B = int(rng.integers(1, 65))
        temps = np.where(rng.random(B) < 0.7, 0.0,
                         rng.random(B) * 2 - 0.5).astype(np.float32)
        if rng.random() < 0.2:       # not greedy by the path's own test
            temps[rng.integers(B)] = np.nan
        for live in (None, rng.random(B) < rng.random(),
                     (rng.random(B) < 0.5).astype(np.int32)):
            host = sampling.needs_candidates(temps, live)
            dev = sampling.needs_candidates(
                jnp.asarray(temps),
                None if live is None else jnp.asarray(live))
            assert isinstance(host, (bool, np.bool_))
            assert bool(host) == bool(dev)
            want = any(not t <= 0 and (live is None or live[i])
                       for i, t in enumerate(temps))
            assert bool(host) == want
