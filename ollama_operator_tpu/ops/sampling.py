"""Token sampling — fully jittable, batched over slots.

Replaces llama.cpp's sampler chain (delegated by the reference via the
ollama image, /root/reference/pkg/model/pod.go:11) with a vectorised
implementation: every slot in the decode batch samples in one fused XLA
program, with per-slot parameters carried as arrays so heterogeneous
requests share one compiled decode step.

Supported (matching the Ollama API options surface): temperature, top_k,
top_p, min_p, typical_p, repeat_penalty (over a token-count buffer),
presence/frequency penalty, mirostat v1/v2 (per-slot ``mu`` state carried
by the engine), per-slot PRNG seed.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-slot sampling parameters, all shape [B] arrays (jit-friendly)."""

    temperature: jax.Array   # [B] f32; <=0 → greedy
    top_k: jax.Array         # [B] i32; <=0 → off
    top_p: jax.Array         # [B] f32; >=1 → off
    min_p: jax.Array         # [B] f32; <=0 → off
    typical_p: jax.Array     # [B] f32; >=1 → off; <=0 → keep
                             #   only the most-typical token
    repeat_penalty: jax.Array    # [B] f32; 1.0 → off
    presence_penalty: jax.Array  # [B] f32
    frequency_penalty: jax.Array  # [B] f32
    mirostat: jax.Array      # [B] i32; 0 off, 1/2 → replaces the filters
    mirostat_tau: jax.Array  # [B] f32 target surprise (bits/token)
    mirostat_eta: jax.Array  # [B] f32 learning rate for mu

    @staticmethod
    def make(B: int, temperature=0.8, top_k=40, top_p=0.9, min_p=0.0,
             typical_p=1.0, repeat_penalty=1.1, presence_penalty=0.0,
             frequency_penalty=0.0, mirostat=0, mirostat_tau=5.0,
             mirostat_eta=0.1):
        f = lambda v: jnp.full((B,), v, jnp.float32)
        return SamplingParams(
            temperature=f(temperature), top_k=jnp.full((B,), top_k, jnp.int32),
            top_p=f(top_p), min_p=f(min_p), typical_p=f(typical_p),
            repeat_penalty=f(repeat_penalty),
            presence_penalty=f(presence_penalty),
            frequency_penalty=f(frequency_penalty),
            mirostat=jnp.full((B,), mirostat, jnp.int32),
            mirostat_tau=f(mirostat_tau), mirostat_eta=f(mirostat_eta))


jax.tree_util.register_dataclass(
    SamplingParams,
    data_fields=["temperature", "top_k", "top_p", "min_p", "typical_p",
                 "repeat_penalty", "presence_penalty", "frequency_penalty",
                 "mirostat", "mirostat_tau", "mirostat_eta"],
    meta_fields=[])


def apply_penalties(logits, token_counts, sp: SamplingParams):
    """logits [B, V] f32; token_counts [B, V] i32 (counts in the window)."""
    seen = token_counts > 0
    rp = sp.repeat_penalty[:, None]
    penalised = jnp.where(logits > 0, logits / rp, logits * rp)
    logits = jnp.where(seen, penalised, logits)
    logits = logits - sp.presence_penalty[:, None] * seen.astype(jnp.float32)
    logits = logits - sp.frequency_penalty[:, None] * token_counts.astype(
        jnp.float32)
    return logits


N_CANDIDATES = 1024


_LN2 = 0.6931471805599453
_MIROSTAT_M = 100   # v1's zipf-fit window (llama.cpp default)


def needs_candidates(temperature, live=None):
    """Whether a step has to build the candidate set: some slot whose
    token the caller keeps (``live`` [B], nonzero or True; every slot where
    omitted) is not greedy, by the very test the candidate path ends in
    (``temperature <= 0``, so a NaN samples as it always did). The one
    predicate of ``sample``'s branch and, over the host's numpy mirror of
    the same two arrays, of the engine's
    ``tpu_model_decode_steps_total{sampler=...}`` count, so the two cannot
    drift. A vacant slot carries the default options (temperature 0.8),
    hence ``live``."""
    hot = ~(temperature <= 0.0)
    if live is not None:
        hot = hot & (live != 0)
    return hot.any()


def sample(logits, token_counts, sp: SamplingParams, key, mu=None,
           live=None):
    """logits [B, V] f32 → tokens [B] i32, or (tokens, mu') when ``mu``
    ([B] f32, the mirostat surprise-budget state) is given.

    Greedy where temperature <= 0, otherwise penalised + top-k/typical/
    top-p/min-p filtered categorical sampling. Slots with mirostat 1/2
    replace the static filters with the adaptive surprise truncation
    (llama.cpp's sampler chain does the same: penalties → temp →
    mirostat); their ``mu`` entries update per sampled token, everyone
    else's pass through unchanged. Callers that never serve mirostat may
    omit ``mu`` and get the plain token array. ``key`` is either a single
    PRNG key (shared across the batch) or a [B] array of per-slot keys
    (each request carries its own seed, per the Ollama API `seed` option).

    A step none of whose ``live`` slots samples (``needs_candidates``)
    takes the argmax of the penalised logits and skips the candidate path:
    that path ends in ``where(temperature <= 0, argmax, ...)`` and leaves
    a greedy slot's ``mu`` alone, so the result is the same bits. The
    choice is a ``lax.cond`` on the device, not a program key; under
    ``vmap`` it would turn into a select and run both sides, so batch
    through the leading axis instead. What a non-live slot gets back is
    unspecified (its caller masks it).
    """
    logits = apply_penalties(logits, token_counts, sp)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # module-level branches over explicit operands: an eager caller's
    # next call finds them traced
    return jax.lax.cond(needs_candidates(sp.temperature, live),
                        _candidates, _argmax_only,
                        logits, greedy, sp, key, mu)


def sample_candidates(logits, token_counts, sp: SamplingParams, key,
                      mu=None):
    """``sample`` with the candidate path taken whatever the batch holds:
    the reference the tests hold ``sample``'s two branches to."""
    logits = apply_penalties(logits, token_counts, sp)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _candidates(logits, greedy, sp, key, mu)


def _argmax_only(logits, greedy, sp, key, mu):
    return greedy if mu is None else (greedy, mu)


def _candidates(logits, greedy, sp: SamplingParams, key, mu):
    """The sampled path over penalised ``logits`` [B, V] (``greedy`` is
    their argmax, which temperature <= 0 slots keep).

    The filters run in a compressed top-``N_CANDIDATES`` space: ONE
    ``lax.top_k`` replaces the full [B, V] sorts the masks would
    otherwise need (a large share of the decode step at 50k+ vocabs), and
    since candidates come out sorted the top-p cumsum needs no further
    sort (typical_p re-orders by entropy deviation — its argsort runs
    over [B, C], not [B, V]). ``top_k`` is effectively capped at
    N_CANDIDATES, and top-p/typical mass beyond the top-1024 logits is
    treated as zero — both far outside any practical sampling
    configuration (Ollama defaults: top_k=40).
    """
    B, V = logits.shape
    C = min(V, N_CANDIDATES)
    vals, cand = jax.lax.top_k(logits, C)           # [B, C], sorted desc
    t = jnp.maximum(sp.temperature, 1e-6)[:, None]
    scaled = vals / t

    # The static filters (top-k/typical/top-p/min-p) all evaluate at T=1:
    # llama.cpp's chain runs them BEFORE temperature (top_k → typ_p →
    # top_p → min_p → temp), so the kept set must not depend on the
    # temperature — only the final categorical draw does. ``filt`` is the
    # T=1 view carrying the accumulated mask; temperature applies when
    # the mask transfers onto ``scaled`` below.

    # top-k: the k-th largest is simply column k-1 of the sorted values
    k = jnp.clip(sp.top_k, 1, C)
    kth = jnp.take_along_axis(vals, (k - 1)[:, None], axis=-1)
    keep = vals >= kth
    keep = jnp.where((sp.top_k > 0)[:, None], keep, True)
    filt = jnp.where(keep, vals, NEG_INF)

    # locally-typical: keep the candidates whose surprise deviates least
    # from the distribution's entropy, up to typical_p cumulative mass
    # (Meister et al.; llama.cpp llama_sampler_typical). Deviation order
    # is not the sorted-logit order, so this is the one filter that pays
    # its own [B, C] argsort. The first deviation-ordered token is always
    # kept (min_keep=1): typical_p <= 0 degrades to "most typical token
    # only", exactly llama.cpp's limit behaviour, not a blank
    # distribution.
    probs = jax.nn.softmax(filt, axis=-1)
    nlp = -jnp.log(jnp.maximum(probs, 1e-30))       # nats
    ent = jnp.sum(jnp.where(probs > 0, probs * nlp, 0.0), axis=-1,
                  keepdims=True)
    order = jnp.argsort(jnp.abs(nlp - ent), axis=-1)
    p_ord = jnp.take_along_axis(probs, order, axis=-1)
    cum = jnp.cumsum(p_ord, axis=-1)
    keep_ord = (cum - p_ord) < sp.typical_p[:, None]
    keep_ord = keep_ord.at[:, 0].set(True)          # min_keep = 1
    bi = jnp.arange(B)[:, None]
    keep = jnp.zeros((B, C), bool).at[bi, order].set(keep_ord)
    keep = jnp.where((sp.typical_p < 1.0)[:, None], keep, True)
    filt = jnp.where(keep, filt, NEG_INF)

    # top-p over the (sorted) candidate probabilities
    probs = jax.nn.softmax(filt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < sp.top_p[:, None]        # always keeps the first
    keep = jnp.where((sp.top_p < 1.0)[:, None], keep, True)
    filt = jnp.where(keep, filt, NEG_INF)

    # min-p relative to the max SURVIVING candidate probability (not
    # column 0 — typical_p may have dropped the global argmax)
    probs = jax.nn.softmax(filt, axis=-1)
    keep = probs >= (sp.min_p[:, None]
                     * jnp.max(probs, axis=-1, keepdims=True))
    keep = jnp.where((sp.min_p > 0.0)[:, None], keep, True)
    filt = jnp.where(keep, filt, NEG_INF)

    # transfer the T=1 mask onto the temperature-scaled logits for the
    # final draw
    filt = jnp.where(filt > NEG_INF / 2, scaled, NEG_INF)

    if mu is not None:
        # mirostat truncation over the UNfiltered temp-scaled candidates
        # (the adaptive cut replaces the static filters). v2 drops
        # candidates whose surprise (-log2 p) exceeds mu; v1 derives a
        # top-k cut from a zipf-exponent fit over the head of the
        # distribution (llama.cpp llama_sampler_mirostat{,_v2}).
        pm = jax.nn.softmax(scaled, axis=-1)
        surprise = -jnp.log(jnp.maximum(pm, 1e-30)) / _LN2   # bits
        m = min(_MIROSTAT_M, C)
        t_i = jnp.log(jnp.arange(2, m + 1) / jnp.arange(1, m))   # [m-1]
        b_i = jnp.log(jnp.maximum(pm[:, :m - 1], 1e-30)
                      / jnp.maximum(pm[:, 1:m], 1e-30))          # [B, m-1]
        s_hat = jnp.sum(t_i * b_i, axis=-1) / jnp.sum(t_i * t_i)  # [B]
        eps = jnp.maximum(s_hat - 1.0, 1e-5)
        k1 = ((eps * jnp.exp2(jnp.minimum(mu, 60.0)))
              / (1.0 - float(V) ** (-eps))) ** (1.0 / jnp.maximum(s_hat,
                                                                  1e-5))
        k1 = jnp.clip(jnp.nan_to_num(k1, nan=float(C)), 1.0, float(C))
        col = jnp.arange(C)[None, :]
        keep1 = col < k1[:, None]
        keep2 = surprise <= mu[:, None]
        keep_m = jnp.where((sp.mirostat == 2)[:, None], keep2, keep1)
        keep_m = keep_m.at[:, 0].set(True)          # min_keep = 1
        use_m = (sp.mirostat > 0)[:, None]
        filt = jnp.where(use_m, jnp.where(keep_m, scaled, NEG_INF), filt)

    if getattr(key, "ndim", 0) >= 1:  # per-slot keys
        ci = jax.vmap(jax.random.categorical)(key, filt)
    else:
        ci = jax.random.categorical(key, filt, axis=-1)
    sampled = jnp.take_along_axis(cand, ci[:, None], axis=-1)[:, 0]
    sampled = sampled.astype(jnp.int32)
    toks = jnp.where(sp.temperature <= 0.0, greedy, sampled)
    if mu is None:
        return toks

    # observed surprise of the sampled token in the truncated,
    # re-normalised distribution drives the mu update (llama.cpp measures
    # p from the post-truncation softmax the same way)
    pf = jax.nn.softmax(filt, axis=-1)
    p_sel = jnp.take_along_axis(pf, ci[:, None], axis=-1)[:, 0]
    e_obs = -jnp.log(jnp.maximum(p_sel, 1e-30)) / _LN2
    mu2 = mu - sp.mirostat_eta * (e_obs - sp.mirostat_tau)
    live = (sp.mirostat > 0) & (sp.temperature > 0.0)
    return toks, jnp.where(live, mu2, mu)
