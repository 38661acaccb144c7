"""What a request receives through the scheduler is what the engine gives
it alone, one ``decode()`` a token.

The scheduler decodes in chunks of ``decode_chunk`` steps, launched a
chunk ahead of the one it fans out, for every slot at once, across the
attention buckets a stream grows through, with prefixes stitched from the
radix tree and requests preempted and re-admitted under pool pressure.
None of that may show in a stream. The oracle is independent of all of
it: a fresh engine, one request, one single-step program a token.

- ``test_streams_equal_token_by_token_decode``: a greedy and a seeded
  sampling request side by side for 70 tokens (buckets 16 -> 32 -> 64 ->
  128) on the contiguous cache and on pages; with the loop synchronous;
  admitted through a radix prefix hit; preempted and re-admitted.
- ``test_sampler_kinds_share_a_chunk``: a greedy, a penalised greedy and a
  sampling slot in one batch: each stream is its solo stream, whatever
  sampler branch the chunk takes for the others.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib, decoder
from ollama_operator_tpu.runtime.engine import Engine, EngineConfig, SlotOptions
from ollama_operator_tpu.runtime.scheduler import Scheduler

CFG = dataclasses.replace(cfglib.PRESETS["tiny"], kernels="xla")
DENSE = EngineConfig(max_slots=3, max_seq_len=128, cache_dtype=jnp.float32,
                     min_prefill_bucket=16, decode_chunk=4)
PAGED = dataclasses.replace(DENSE, paged=True, page_size=8)
CACHES = {"dense": DENSE, "paged": PAGED}

GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
PENALISED = SlotOptions(temperature=0.0, repeat_penalty=1.8)
SAMPLING = SlotOptions(temperature=0.8, seed=11)

PROMPT = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
# the tiny model's greedy stream from this prompt settles into a loop: the
# penalty ring and the sampler see repeats from the first chunk on
LOOPY = np.array([7, 8, 9, 7, 8, 9, 7, 8], np.int32)


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(CFG, jax.random.key(0), jnp.float32)


@pytest.fixture(scope="module")
def solo(params):
    """(cache, prompt, opts, n) -> the n tokens of that request alone, one
    ``decode()`` a token; an engine a cache for the whole module."""
    engines, memo = {}, {}

    def run(cache, prompt, opts, n):
        key = (cache, tuple(int(t) for t in prompt), repr(opts), n)
        if key not in memo:
            if cache not in engines:
                engines[cache] = Engine(CFG, params, ecfg=CACHES[cache])
            eng = engines[cache]
            out = [eng.admit(0, np.asarray(prompt, np.int32), opts)]
            while len(out) < n:
                out.append(int(eng.decode()[0]))
            eng.release(0)
            memo[key] = out
        return memo[key]
    return run


def _serve(params, ecfg, jobs, max_tokens, **sched_kw):
    """Submit ``jobs`` [(prompt, opts)] together to a fresh scheduler;
    returns (streams, the scheduler after shutdown)."""
    sched = Scheduler(Engine(CFG, params, ecfg=ecfg), **sched_kw)
    try:
        reqs = [sched.submit(p, opts=o, max_tokens=max_tokens)
                for p, o in jobs]
        outs = [list(r.tokens()) for r in reqs]
        for r in reqs:
            assert r.error is None, r.error
    finally:
        sched.shutdown()
    return outs, sched


@pytest.mark.parametrize("case", [
    "across_tail_buckets-dense", "across_tail_buckets-paged",
    "sync_dispatch", "radix_prefix_hit", "preempt_readmit"])
def test_streams_equal_token_by_token_decode(params, solo, case):
    if case.startswith("across_tail_buckets"):
        cache = case.rsplit("-", 1)[1]
        jobs = [(LOOPY, GREEDY), (PROMPT, SAMPLING)]
        outs, _ = _serve(params, CACHES[cache], jobs, 70)
        assert [len(o) for o in outs] == [70, 70]
        assert outs == [solo(cache, p, o, 70) for p, o in jobs]
    elif case == "sync_dispatch":
        # TPU_ASYNC_DISPATCH=0: drain, decode_n, fan out
        jobs = [(LOOPY, GREEDY), (PROMPT, SAMPLING)]
        outs, sched = _serve(params, DENSE, jobs, 40, async_dispatch=False)
        assert not sched.async_dispatch
        assert outs == [solo("dense", p, o, 40) for p, o in jobs]
    elif case == "radix_prefix_hit":
        # the second request's prefix is stitched from the tree, not
        # prefilled: the chunk sees lengths, never how a prefix arrived
        prefix = np.concatenate([LOOPY, LOOPY, np.array([7, 8], np.int32)])
        sched = Scheduler(Engine(CFG, params, ecfg=PAGED))
        try:
            cold = list(sched.submit(prefix, opts=GREEDY,
                                     max_tokens=24).tokens())
            hit = sched.submit(prefix, opts=GREEDY, max_tokens=24)
            warm = list(hit.tokens())
        finally:
            sched.shutdown()
        assert hit.stats.n_reused > 0
        assert cold == warm == solo("paged", prefix, GREEDY, 24)
    else:
        # 3 slots x (8 prompt + 16 generated) = 72 positions over 64 page
        # places: someone is preempted mid-stream and resumes on the same
        # queue from a re-prefill of prompt + generated
        ecfg = dataclasses.replace(PAGED, n_pages=8)
        jobs = [(LOOPY, GREEDY), (LOOPY + 1, GREEDY), (LOOPY + 2, GREEDY)]
        outs, sched = _serve(params, ecfg, jobs, 16)
        assert sched.n_preemptions >= 1
        assert outs == [solo("paged", p, o, 16) for p, o in jobs]


KINDS = {"greedy": (LOOPY, GREEDY), "penalised_greedy": (LOOPY, PENALISED),
         "sampling": (PROMPT[:5], SAMPLING)}


@pytest.fixture(scope="module")
def mixed(params):
    """cache -> the streams of the three kinds served in one batch."""
    memo = {}

    def run(cache):
        if cache not in memo:
            outs, _ = _serve(params, CACHES[cache], list(KINDS.values()), 24)
            memo[cache] = dict(zip(KINDS, outs))
        return memo[cache]
    return run


@pytest.mark.parametrize("cache", ["dense", "paged"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_sampler_kinds_share_a_chunk(solo, mixed, kind, cache):
    """The chunk's sampler is picked for the batch (argmax where every
    slot is greedy and unpenalised, candidates otherwise); a slot's token
    is its own options' all the same: penalties from its own ring, keys
    from its own seed and position."""
    got = mixed(cache)[kind]
    assert len(got) == 24
    assert got == solo(cache, *KINDS[kind], 24)
    if kind == "penalised_greedy":
        # the penalty does something here: not the unpenalised stream
        assert got != solo(cache, LOOPY, GREEDY, 24)
