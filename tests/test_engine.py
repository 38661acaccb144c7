"""Engine: continuous batching with slot KV cache must reproduce the
sequential greedy decode of the bare decoder."""

import jax
import jax.numpy as jnp
import numpy as np

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.runtime.engine import Engine, EngineConfig, SlotOptions

F32 = jnp.float32


def greedy_reference(params, cfg, prompt, n_steps):
    """Sequential greedy decode with the raw decoder (no engine)."""
    tokens = jnp.asarray(prompt, jnp.int32)[None]
    logits, ks, vs = decoder.prefill_chunk(params, cfg, tokens)
    S = 128
    shape = (cfg.n_layers, 1, cfg.n_kv_heads, S, cfg.head_dim)
    k_cache = jnp.zeros(shape, F32).at[:, :, :, :tokens.shape[1]].set(ks)
    v_cache = jnp.zeros(shape, F32).at[:, :, :, :tokens.shape[1]].set(vs)
    lengths = jnp.array([tokens.shape[1]], jnp.int32)
    out = [int(jnp.argmax(logits[0, -1]))]
    tok = jnp.array([[out[0]]], jnp.int32)
    for _ in range(n_steps - 1):
        logits, k_cache, v_cache = decoder.forward_with_cache(
            params, cfg, tok, k_cache, v_cache, lengths)
        lengths = lengths + 1
        nxt = int(jnp.argmax(logits[0, 0]))
        out.append(nxt)
        tok = jnp.array([[nxt]], jnp.int32)
    return out


GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)


def make_engine(cfg, params, slots=4):
    return Engine(cfg, params,
                  ecfg=EngineConfig(max_slots=slots, max_seq_len=128,
                                    cache_dtype=F32, min_prefill_bucket=16))


def test_engine_matches_reference_greedy():
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    eng = make_engine(cfg, params)

    prompt = np.array([5, 9, 2, 11, 7], np.int32)
    ref = greedy_reference(params, cfg, prompt, 6)

    first = eng.admit(0, prompt, GREEDY)
    got = [first]
    for _ in range(5):
        toks = eng.decode()
        got.append(int(toks[0]))
    assert got == ref


def test_continuous_batching_isolation():
    """Admitting a second request mid-decode must not change the first
    request's token stream."""
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)

    p1 = np.array([3, 1, 4, 1, 5], np.int32)
    p2 = np.array([9, 2, 6], np.int32)
    ref1 = greedy_reference(params, cfg, p1, 7)
    ref2 = greedy_reference(params, cfg, p2, 4)

    eng = make_engine(cfg, params)
    got1 = [eng.admit(0, p1, GREEDY)]
    for _ in range(2):
        got1.append(int(eng.decode()[0]))
    # admit second request mid-stream into another slot
    got2 = [eng.admit(2, p2, GREEDY)]
    for _ in range(3):
        toks = eng.decode()
        got1.append(int(toks[0]))
        got2.append(int(toks[2]))
    eng.release(2)
    toks = eng.decode()
    got1.append(int(toks[0]))

    assert got1 == ref1
    assert got2 == ref2


def test_release_and_reuse_slot():
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    eng = make_engine(cfg, params, slots=2)
    p = np.array([4, 8, 15], np.int32)
    ref = greedy_reference(params, cfg, p, 4)

    eng.admit(0, p, GREEDY)
    eng.decode()
    eng.release(0)
    assert eng.free_slots() == [0, 1]

    got = [eng.admit(0, p, GREEDY)]
    for _ in range(3):
        got.append(int(eng.decode()[0]))
    assert got == ref


def test_prompt_too_long_rejected():
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    eng = make_engine(cfg, params, slots=2)
    try:
        eng.admit(0, np.zeros(500, np.int32), GREEDY)
        assert False, "expected ValueError"
    except ValueError:
        pass


def test_decode_n_matches_single_steps():
    """decode_n(k) must produce exactly the tokens of k decode() calls."""
    import jax.numpy as jnp
    from ollama_operator_tpu.models import config as cfglib
    from ollama_operator_tpu.models import decoder as dec
    from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                    SlotOptions)
    cfg = cfglib.PRESETS["tiny"]
    params = dec.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=2, max_seq_len=64, min_prefill_bucket=8,
                        cache_dtype=jnp.float32)
    prompt = np.arange(1, 10, dtype=np.int32)
    opts = SlotOptions(temperature=0.7, seed=123)

    e1 = Engine(cfg, params, ecfg=ecfg)
    e1.admit(0, prompt, opts)
    singles = [int(e1.decode()[0]) for _ in range(6)]

    e2 = Engine(cfg, params, ecfg=ecfg)
    e2.admit(0, prompt, opts)
    chunk = e2.decode_n(6)
    assert chunk.shape == (6, 2)
    assert [int(t[0]) for t in chunk] == singles


def test_decode_across_attn_bucket_boundary():
    """Generations crossing a power-of-two attention bucket must be
    identical to an engine that always attends the full cache."""
    import jax.numpy as jnp
    from ollama_operator_tpu.models import config as cfglib
    from ollama_operator_tpu.models import decoder as dec
    from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                    SlotOptions)
    cfg = cfglib.PRESETS["tiny"]
    params = dec.init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=2, max_seq_len=128, min_prefill_bucket=8,
                        cache_dtype=jnp.float32, decode_chunk=4)
    opts = SlotOptions(temperature=0.0)
    prompt = np.arange(1, 7, dtype=np.int32)   # len 6: bucket 8 → 16 → 32

    e1 = Engine(cfg, params, ecfg=ecfg)
    e1.admit(0, prompt, opts)
    bucketed = [t for _ in range(7) for t in e1.decode_n()[:, 0]]

    e2 = Engine(cfg, params, ecfg=ecfg)
    e2._bucketed_attn = False   # always full-cache attention
    e2.admit(0, prompt, opts)
    full = [t for _ in range(7) for t in e2.decode_n()[:, 0]]

    assert [int(t) for t in bucketed] == [int(t) for t in full]
    # crossed at least two bucket boundaries (6 + 28 tokens > 32 > 16 > 8)
    assert e1._attn_bucket(1) >= 32


def test_repeat_last_n_window_evicts():
    """Penalty counts must cover exactly the last repeat_last_n tokens:
    after decoding past the window, total counts stay at W (prompt tokens
    that fell out are no longer penalised — Ollama repeat_last_n)."""
    import jax.numpy as jnp
    from ollama_operator_tpu.models import config as cfglib
    from ollama_operator_tpu.models import decoder as dec
    from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                    SlotOptions)
    cfg = cfglib.PRESETS["tiny"]
    params = dec.init_params(cfg, jax.random.PRNGKey(9), dtype=jnp.float32)
    W = 8
    ecfg = EngineConfig(max_slots=2, max_seq_len=64, min_prefill_bucket=8,
                        cache_dtype=jnp.float32, decode_chunk=4,
                        repeat_last_n=W)
    eng = Engine(cfg, params, ecfg=ecfg)
    r = np.random.default_rng(17)
    prompt = np.asarray(r.integers(1, cfg.vocab_size, 12), np.int32)
    eng.admit(0, prompt, SlotOptions(temperature=0.8, seed=3))
    # after admit: window = last W prompt tokens + 1 sampled = W (ring
    # wrapped: eviction keeps the total at W)
    counts0 = np.asarray(eng.counts)[0]
    assert counts0.sum() == W
    # the first sampled token must stay in the window for W steps, not be
    # evicted by the first decode (ring position off-by-one regression)
    tok0 = int(np.asarray(eng.last_tokens)[0])
    eng.decode_n(1)
    assert np.asarray(eng.counts)[0][tok0] >= 1
    for _ in range(4):
        eng.decode_n()
    counts = np.asarray(eng.counts)[0]
    assert counts.sum() == W          # stable at window size
    assert (counts >= 0).all()        # eviction never goes negative
    eng.release(0)
    assert np.asarray(eng.counts)[0].sum() == 0


def test_per_request_repeat_last_n():
    """Each request's own repeat_last_n must take effect (round-2 VERDICT
    weak #6: the API option was accepted and silently ignored) without a
    recompile — the static ring holds the engine max, the per-slot window
    is a traced modulus."""
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(9), dtype=F32)
    W = 8
    ecfg = EngineConfig(max_slots=3, max_seq_len=64, min_prefill_bucket=8,
                        cache_dtype=F32, decode_chunk=4, repeat_last_n=W)
    eng = Engine(cfg, params, ecfg=ecfg)
    prompt = np.asarray([5, 5, 5, 5, 5, 5], np.int32)

    # same prompt, three windows: full (counts = W-window over prompt +
    # sample), narrowed to 2, and 0 (penalties disabled entirely)
    eng.admit(0, prompt, SlotOptions(temperature=0.0, repeat_last_n=-1))
    eng.admit(1, prompt, SlotOptions(temperature=0.0, repeat_last_n=2))
    eng.admit(2, prompt, SlotOptions(temperature=0.0, repeat_last_n=0))
    counts = np.asarray(eng.counts)
    t0 = int(np.asarray(eng.last_tokens)[0])
    # full window: 6 prompt tokens + 1 sample, nothing evicted yet
    assert counts[0].sum() == len(prompt) + 1
    assert counts[0][5] == len(prompt) + (1 if t0 == 5 else 0)
    # slot 1: window of 2 = one prompt token evicted by the sample, or
    # {5, tok}; either way total counts == 2 and at most two 5s
    assert counts[1].sum() == 2
    assert counts[1][5] <= 2
    assert counts[2].sum() == 0       # window 0: penalties see nothing
    # one admission program serves every window — no per-request compile
    assert len(eng._admit_execs) == 1

    eng.decode_n()
    counts = np.asarray(eng.counts)
    assert counts[1].sum() == 2       # stays at the request's window
    assert counts[2].sum() == 0
    eng.release(1)
    # a later admit on the same slot returns to the default window
    eng.admit(1, prompt, SlotOptions(temperature=0.0))
    assert np.asarray(eng.counts)[1].sum() >= min(len(prompt), W)
    assert len(eng._admit_execs) == 1


def test_resolve_paged_default():
    """Serving default: paged for GQA and MHA on TPU (the ledger's two
    paged cells), dense for MoE/CPU/incompatible meshes; explicit flags
    resolve in the server before the engine is built."""
    from unittest import mock

    import dataclasses

    from ollama_operator_tpu.parallel import MeshPlan, make_mesh
    from ollama_operator_tpu.runtime.engine import resolve_paged_default
    gqa = cfglib.PRESETS["tiny"]                       # 4 heads, 2 kv
    # this suite runs on the CPU backend: the v5e measurement must not
    # page a 1-core dev/kind pod
    assert resolve_paged_default(gqa, None) is False
    with mock.patch("jax.default_backend", return_value="tpu"):
        assert resolve_paged_default(gqa, None) is True
        mha = dataclasses.replace(gqa, n_kv_heads=gqa.n_heads)
        assert resolve_paged_default(mha, None) is True
        moe = dataclasses.replace(gqa, n_experts=4)
        assert resolve_paged_default(moe, None) is False
        assert resolve_paged_default(
            gqa, make_mesh(MeshPlan(sp=2))) is False
        assert resolve_paged_default(
            gqa, make_mesh(MeshPlan(tp=2))) is True
        assert resolve_paged_default(
            gqa, make_mesh(MeshPlan(dp=2))) is True


def test_resolve_serving_defaults():
    """Tri-state knob resolution incl. the pool-ceiling guarantee: the
    auto-paged default must NOT grow HBM past the old dense-8 footprint."""
    from unittest import mock

    from ollama_operator_tpu.runtime.engine import resolve_serving_defaults
    gqa = cfglib.PRESETS["tiny"]                       # max_seq_len 128
    base = EngineConfig(max_slots=0, max_seq_len=4096, paged=None,
                        page_size=16)
    with mock.patch("jax.default_backend", return_value="tpu"):
        r = resolve_serving_defaults(base, gqa, None)
        # GQA paged on TPU defaults to 64 slots since r5 (ladder: 3902
        # tok/s at 64 vs 2848 at 32) with a dense-24-equivalent pool
        # ceiling (dense-8/16 caps measured pool-dry under 64 mixed
        # slots at design load, r5 window 3)
        assert r.paged is True and r.max_slots == 64
        # ceiling uses the SERVING seq (engine clamps to the model's 128)
        # and preserves dense-24 BYTES: the pool pads head_dim to the
        # 128-lane tile (tiny: hd 16 → 8× padding), so the page count
        # shrinks by hd/hd_pool (round-3 advisor finding)
        assert r.n_pages == 24 * 128 * 16 // 128 // 16
        # a hd=128 model keeps the full token count
        r128 = resolve_serving_defaults(
            base, cfglib.PRESETS["llama3.2:3b"], None)
        assert r128.n_pages == 24 * 4096 // 16
        # explicit slots: user asked for scale — dense-equivalent pool
        r2 = resolve_serving_defaults(
            EngineConfig(max_slots=16, max_seq_len=4096, paged=None,
                         page_size=16), gqa, None)
        assert r2.paged is True and r2.max_slots == 16
        assert r2.n_pages is None
        # explicit dense stays dense with 8 slots
        r3 = resolve_serving_defaults(
            EngineConfig(max_slots=0, max_seq_len=4096, paged=False),
            gqa, None)
        assert r3.paged is False and r3.max_slots == 8
    # CPU backend: auto resolves dense
    r4 = resolve_serving_defaults(base, gqa, None)
    assert r4.paged is False and r4.max_slots == 8


def test_resolve_page_size_and_mha_slots():
    """page_size=0 resolves to 128 when paged on TPU (r5 ladder: +10.5%
    over 64 at B=32, 256 regresses) and 64 elsewhere; MHA models keep 32
    slots (their paged step is ~3x GQA's — 64 is unmeasured there)."""
    import dataclasses as dc
    from unittest import mock

    from ollama_operator_tpu.runtime.engine import resolve_serving_defaults
    gqa = cfglib.PRESETS["tiny"]
    mha = dc.replace(gqa, n_kv_heads=gqa.n_heads)
    auto = EngineConfig(max_slots=0, max_seq_len=4096, paged=None,
                        page_size=0)
    with mock.patch("jax.default_backend", return_value="tpu"):
        r = resolve_serving_defaults(auto, gqa, None)
        assert r.page_size == 128 and r.max_slots == 64
        m = resolve_serving_defaults(auto, mha, None)
        assert m.paged is True and m.max_slots == 32
        assert m.page_size == 64    # ps=128 measured -2% on MHA (phi)
        # explicit page size passes through, incl. via the early return
        pinned = EngineConfig(max_slots=8, max_seq_len=4096, paged=True,
                              page_size=64)
        assert resolve_serving_defaults(pinned, gqa, None).page_size == 64
        early = EngineConfig(max_slots=8, max_seq_len=4096, paged=True,
                             page_size=0)
        assert resolve_serving_defaults(early, gqa, None).page_size == 128
    # CPU: dense anyway, page size resolves to the classic 64
    c = resolve_serving_defaults(auto, gqa, None)
    assert c.page_size == 64 and c.paged is False


def test_resolve_decode_chunk_default():
    """decode_chunk=0 resolves per backend (32 TPU / 8 CPU — BASELINE.md's
    measured serving config vs round-1's chunk-8); an explicit chunk always
    passes through, including when paged/slots are explicit too (the early
    return must still resolve the chunk)."""
    from unittest import mock

    from ollama_operator_tpu.runtime.engine import resolve_serving_defaults
    gqa = cfglib.PRESETS["tiny"]
    auto = EngineConfig(max_slots=0, max_seq_len=4096, paged=None,
                        decode_chunk=0)
    with mock.patch("jax.default_backend", return_value="tpu"):
        assert resolve_serving_defaults(auto, gqa, None).decode_chunk == 32
        # explicit paged+slots takes the early return — chunk still resolves
        explicit = EngineConfig(max_slots=8, max_seq_len=4096, paged=False,
                                decode_chunk=0)
        assert resolve_serving_defaults(explicit, gqa,
                                        None).decode_chunk == 32
        pinned = EngineConfig(max_slots=8, max_seq_len=4096, paged=False,
                              decode_chunk=16)
        assert resolve_serving_defaults(pinned, gqa, None).decode_chunk == 16
    # CPU backend: streaming-latency default
    assert resolve_serving_defaults(auto, gqa, None).decode_chunk == 8


def test_resolve_engine_dtype():
    """Zero-config weight dtype per model size (VERDICT r4 #3): a bare
    Model CR must serve the measured config — int8 ≤4B, int4 7B+, bf16
    MoE on TPU; f32 on CPU. Explicit spec/env wins upstream (ModelManager
    only consults this when engine_dtype is None)."""
    import dataclasses

    from ollama_operator_tpu.runtime.engine import (resolve_engine_dtype,
                                                    resolve_kv_dtype_default)
    tiny = cfglib.PRESETS["tiny"]
    assert resolve_engine_dtype(tiny, "cpu") == "float32"
    assert resolve_engine_dtype(tiny, "tpu") == "int8"
    small = cfglib.PRESETS["llama3.2:3b"]
    assert resolve_engine_dtype(small, "tpu") == "int8"
    big = cfglib.PRESETS["mistral"]          # 7B class
    assert big.n_params >= 4e9
    assert resolve_engine_dtype(big, "tpu") == "int4"
    moe = dataclasses.replace(tiny, n_experts=4)
    assert resolve_engine_dtype(moe, "tpu") == "bfloat16"
    assert resolve_kv_dtype_default("tpu") == "int8"
    assert resolve_kv_dtype_default("cpu") == "float32"


def test_deleted_kernel_switches_change_nothing(monkeypatch):
    """The six variables that chose a kernel at trace time are gone (PR
    31): set to what used to turn every other path on, a paged engine
    traces the same kernels and decodes the same tokens as with none."""
    import dataclasses

    import numpy as np

    from ollama_operator_tpu.runtime.engine import Engine, SlotOptions
    cfg = dataclasses.replace(cfglib.PRESETS["tiny"], kernels="interpret")
    params = decoder.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    prompt = np.array([5, 6, 7, 8, 9, 2], np.int32)
    g = SlotOptions(temperature=0.0, repeat_penalty=1.0)
    gone = {"TPU_PAGED_V3": "0", "TPU_PAGED_V4": "1", "TPU_PAGED_DEPTH": "4",
            "TPU_PAGED_FUSED": "0", "TPU_MHA_KERNEL": "1",
            "TPU_FUSED_QKV": "1"}

    def run():
        eng = Engine(cfg, params,
                     ecfg=EngineConfig(max_slots=2, max_seq_len=64,
                                       cache_dtype=jnp.int8, paged=True,
                                       page_size=8, min_prefill_bucket=16))
        toks = [eng.admit(0, prompt, g)]
        toks += [int(t) for t in eng.decode_n(6)[:, 0]]
        assert set(eng.params["layers"]) >= {"wq", "wk", "wv"}
        return toks, eng.kernels_by_kind()

    for name in gone:
        monkeypatch.delenv(name, raising=False)
    ref, ref_kinds = run()
    assert "paged_decode=paged_v3" in ref_kinds["decode"]
    for name, value in gone.items():
        monkeypatch.setenv(name, value)
    got, kinds = run()
    assert (got, kinds) == (ref, ref_kinds)


def test_mirostat_mu_threads_through_decode_chunks():
    """A mirostat slot's surprise budget must (a) re-seed to 2*tau at
    admission, (b) keep evolving across decode_n chunk boundaries, and
    (c) stay frozen for non-mirostat slots sharing the batch."""
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    eng = make_engine(cfg, params)

    tau = 5.0
    miro = SlotOptions(temperature=0.7, repeat_penalty=1.0, mirostat=2,
                       mirostat_tau=tau, mirostat_eta=0.3, seed=3)
    eng.admit(0, np.array([5, 9, 2], np.int32), miro)
    eng.admit(1, np.array([4, 1, 8], np.int32), GREEDY)
    mu_after_admit = np.asarray(eng._fetch(eng.mu))
    # the admission sample already applied one update off the 2*tau seed
    assert mu_after_admit[0] != 0.0
    assert abs(mu_after_admit[0] - 2 * tau) < tau  # one eta-sized step
    # non-mirostat slots carry the inert 2*tau seed (never read)
    assert mu_after_admit[1] == 2 * 5.0

    eng.decode_n(4)
    mu_mid = np.asarray(eng._fetch(eng.mu))
    assert mu_mid[0] != mu_after_admit[0]          # evolved inside chunk
    assert mu_mid[1] == 2 * 5.0                    # frozen: mirostat off

    eng.decode_n(4)
    assert np.asarray(eng._fetch(eng.mu))[0] != mu_mid[0]

    eng.release(0)
    assert np.asarray(eng._fetch(eng.mu))[0] == 0.0


def test_mirostat_generation_stays_in_vocab():
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(1), dtype=F32)
    eng = make_engine(cfg, params)
    opts = SlotOptions(temperature=0.9, repeat_penalty=1.1, mirostat=1,
                       seed=11)
    first = eng.admit(2, np.array([3, 7, 1, 2], np.int32), opts)
    toks = [first]
    for _ in range(3):
        toks.extend(int(t) for t in eng.decode_n(2)[:, 2])
    assert all(0 <= t < cfg.vocab_size for t in toks)


# -- the sampler's branch, seen from the engine (ops/sampling.sample) -------

def _steps(sampler):
    from ollama_operator_tpu.server.metrics import GLOBAL as METRICS
    return METRICS.get("tpu_model_decode_steps_total",
                       '{sampler="%s"}' % sampler)


def _counted(fn):
    """(what ``fn`` returns, argmax steps it counted, candidates steps)."""
    a0, c0 = _steps("argmax"), _steps("candidates")
    out = fn()
    return out, _steps("argmax") - a0, _steps("candidates") - c0


def test_greedy_chunk_beside_vacant_slots_is_an_argmax_chunk():
    """Two greedy requests in a four-slot engine: the vacant slots carry
    the default options (temperature 0.8) on the device, and still every
    step of the chunk counts, and computes, as an argmax step: decode_n
    yields what single steps yield and what the bare decoder yields."""
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    p0 = np.array([5, 9, 2, 11, 7], np.int32)
    p2 = np.array([9, 2, 6], np.int32)

    e1 = make_engine(cfg, params)
    e1.admit(0, p0, GREEDY), e1.admit(2, p2, GREEDY)
    assert float(np.asarray(e1.sp.temperature)[1]) == \
        np.float32(SlotOptions().temperature) > 0      # a vacant slot
    singles, a, c = _counted(
        lambda: np.stack([e1.decode() for _ in range(6)]))
    assert (a, c) == (6, 0)

    e2 = make_engine(cfg, params)
    firsts = [e2.admit(0, p0, GREEDY), e2.admit(2, p2, GREEDY)]
    handle, a, c = _counted(lambda: e2.decode_n_launch(6))
    assert (a, c) == (6, 0) and handle.sampler == "argmax"
    chunk = handle.wait()
    np.testing.assert_array_equal(chunk[:, [0, 2]], singles[:, [0, 2]])
    for slot, first, prompt in ((0, firsts[0], p0), (2, firsts[1], p2)):
        assert [first] + [int(t) for t in chunk[:, slot]] == \
            greedy_reference(params, cfg, prompt, 7)


def test_sampling_slot_takes_the_candidates_and_keeps_its_stream(
        monkeypatch):
    """A seeded temperature-0.8 request beside a greedy one: the chunks
    count under candidates while it lives, both slots' tokens are those
    of an engine whose every step is forced down the candidate path (the
    sampler as it was), and once it is released the greedy slot's chunks
    are argmax chunks again, its stale device options notwithstanding."""
    from ollama_operator_tpu.ops import sampling
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(2), dtype=F32)
    warm = SlotOptions(temperature=0.8, seed=42)
    prompts = (np.array([3, 1, 4, 1, 5], np.int32),
               np.array([2, 7, 1, 8], np.int32))

    def run():
        eng = make_engine(cfg, params)
        out = [eng.admit(1, prompts[0], warm),
               eng.admit(3, prompts[1], GREEDY)]
        chunk, a, c = _counted(lambda: eng.decode_n(5))
        out += [int(t) for t in chunk[:, [1, 3]].ravel()]
        mixed = (a, c)
        eng.release(1)
        chunk, a, c = _counted(lambda: eng.decode_n(4))
        return out + [int(t) for t in chunk[:, 3]], mixed, (a, c)

    got, mixed, after = run()
    assert mixed == (0, 5) and after == (4, 0)
    monkeypatch.setattr(
        sampling, "sample",
        lambda logits, counts, sp, key, mu=None, live=None:
        sampling.sample_candidates(logits, counts, sp, key, mu))
    assert run()[0] == got


def test_host_masked_sampling_slot_counts_its_one_step():
    """A host-masked constrained slot has a budget of one step a chunk:
    where it is the only sampling slot the chunk's first step needs the
    candidates and the rest, in which it is frozen, do not."""
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(3), dtype=F32)
    eng = make_engine(cfg, params)
    eng.admit(0, np.array([5, 9, 2], np.int32), GREEDY)
    eng.admit(1, np.array([4, 1, 8], np.int32),
              SlotOptions(temperature=0.8, seed=7))
    eng.set_mask(1, np.full((eng.mask_words,), 0xFFFFFFFF, np.uint32))
    handle, a, c = _counted(lambda: eng.decode_n_launch(4))
    assert (a, c) == (3, 1) and handle.sampler == "candidates"
    handle.wait()
