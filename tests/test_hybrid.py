"""A hybrid stack (granitemoehybrid): Mamba-2 layers and one attention layer
in one scan, a recurrent state beside the keys and values of every slot, and
one chip's share of a routed expert layer. CPU, the toy of the same shape
(``tiny-hybrid``), seeded weights; the plain reference is the benchmark's
(``benchmark/configs/granite-4.0-h-small.reference.py``), read at the toy's
sizes through the configuration file's own ``holds``."""

import dataclasses
import os
import re

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
from ollama_operator_tpu.runtime.scheduler import Scheduler
from ollama_operator_tpu.runtime.trace import FLIGHT
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

from benchmark import server_child, work

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONF_PATH = os.path.join(REPO, "benchmark", "configs",
                         "granite-4.0-h-small.json")
CFG = cfglib.PRESETS["tiny-hybrid"]
GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)
SEEDED = SlotOptions(temperature=0.9, seed=1234, repeat_penalty=1.0)


def conf_of(cfg):
    """The configuration file's dict at ``cfg``'s sizes: each key the file
    holds the preset to, read back from the config."""
    conf = work.load_conf(CONF_PATH)
    for ours, theirs in conf["holds"]:
        conf[theirs] = getattr(cfg, ours)
    conf["layer_types"] = ["attention" if c == "A" else "mamba"
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
    """(ssm, conv) of one slot as host arrays."""
    _, _, (ssm, conv, _) = decoder.split_state(eng.k_cache, eng.v_cache)
    return np.asarray(ssm[:, slot]), np.asarray(conv[:, slot])


def manual(sched):
    """Stop the loop thread so a test drives _step() itself."""
    sched._stop.set()
    sched._wake.set()
    sched._thread.join(timeout=5)
    return sched


def drain(req):
    """Every token queued for ``req`` so far, without blocking."""
    out = []
    while not req.out.empty():
        kind, payload = req.out.get_nowait()
        if kind == "tokens":
            out += list(payload)
        elif kind == "done":
            req.done_reason = payload
        else:
            raise AssertionError((kind, payload))
    return out


def run_to_end(sched, reqs, got, steps=400):
    for _ in range(steps):
        sched._step()
        for r in reqs:
            got[r] += drain(r)
        if all(r.done_reason is not None for r in reqs):
            return
    raise AssertionError("the requests did not finish")


# -- the model against the reference -----------------------------------

def test_preset_is_the_published_shape():
    """The served preset against the configuration's file, key by key (the
    benchmark's own check), and the cut's floors."""
    conf = server_child.load_conf(CONF_PATH, False)
    cfg = server_child.model_config(conf, False)
    assert cfg.layer_kinds == "mmmmmAmmmm" and cfg.n_ssm_layers == 9
    assert cfg.ssm_inner == 8192 and cfg.ssm_conv_dim == 8448
    assert cfg.experts_held == 36 and cfg.n_experts == 72
    assert not cfg.rope and not cfg.shared_gate
    assert conf["published"]["num_local_experts"] == 72
    assert conf["vocab_size"] * 8 >= conf["published"]["vocab_size"]
    # 9 x (128 x 64 x 128 + 3 x 8448) float32
    assert cfg.ssm_state_bytes == 4 * 9 * (8192 * 128 + 3 * 8448)
    assert 9.4e9 < 2 * cfg.n_params < 9.6e9


@pytest.mark.parametrize("cache", ["float32", "int8"])
def test_prefill_then_decode_against_the_reference(ref, params, cache):
    """Prefill 24 positions, then 16 decode steps through the cache, each
    position's logits against the reference's full forward pass."""
    toks = tokens(40)
    want = np.asarray(ref.forward(params, conf_of(CFG), jnp.asarray(toks)))
    scale = np.abs(want).max()
    logits, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None, :24])
    assert np.abs(np.asarray(logits[0]) - want[:24]).max() < 2e-4 * scale
    S = 64
    if cache == "int8":
        from ollama_operator_tpu.ops import quant_cache as QC
        kc, vc = QC.empty_cache(1, 1, CFG.n_kv_heads, S, CFG.head_dim), \
            QC.empty_cache(1, 1, CFG.n_kv_heads, S, CFG.head_dim)
        for c, new in ((kc, ks["kv"]), (vc, vs["kv"])):
            q, s = QC.quantize_kv(new)
            c["q"] = c["q"].at[:, :, :, :24].set(q)
            c["s"] = c["s"].at[:, :, :, :24].set(s)
        tol = 3e-2
    else:
        kc = jnp.zeros((1, 1, CFG.n_kv_heads, S, CFG.head_dim))
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


def test_the_benchmarks_probe_passes_on_the_toy(params):
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
    prefill's [L, T, k] and the decode step's [L, B, k], in layer order: the
    expert half is traced once, whatever the layer's mixer."""
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
        (CFG.n_layers, 12, CFG.n_experts_used),
        (CFG.n_layers, eng.n_slots, CFG.n_experts_used)]
    assert calls[0].max() < CFG.n_experts     # sets over ALL the router's


# -- the state: pieces, padding, inactive slots --------------------------

@pytest.mark.parametrize("pieces", [(40,), (16, 24), (16, 16, 8), (24, 16),
                                    (7, 33)])
def test_prefill_in_pieces_equals_one_piece(params, pieces):
    """One prefill, and the same prompt through prefill + extends of the
    cache (Mamba blocks of 16: the pieces cut them at other places): state
    and last logits agree."""
    toks = tokens(40, seed=1)
    want_l, ks, vs = jax.jit(
        lambda p, t: decoder.prefill_chunk(p, CFG, t))(params, toks[None])
    kc = jnp.zeros((1, 1, CFG.n_kv_heads, 64, CFG.head_dim))
    K, V = decoder.join_state(kc, kc, decoder.empty_state(CFG, 1))
    at = 0
    for n in pieces:
        lg, K, V = decoder.forward_with_cache(
            params, CFG, toks[None, at:at + n], K, V,
            jnp.array([at], jnp.int32))
        at += n
    assert np.allclose(lg[0, -1], want_l[0, -1], atol=2e-6)
    assert np.allclose(K["ssm"], ks["ssm"], atol=1e-6)
    assert np.allclose(V["conv"], vs["conv"], atol=1e-6)


@pytest.mark.parametrize("n_valid", [1, 2, 5, 16, 23, 31])
def test_padded_positions_never_alter_the_state(params, n_valid):
    """A prefill bucket pads the prompt: the state and the last real
    position's logits are those of the unpadded prompt, to the bit."""
    toks = tokens(32, seed=2)
    f = jax.jit(lambda p, t, n: decoder.prefill_chunk(p, CFG, t, n_valid=n))
    lg, ks, vs = f(params, toks[None], jnp.int32(n_valid))
    lg0, ks0, vs0 = jax.jit(lambda p, t: decoder.prefill_chunk(p, CFG, t))(
        params, toks[None, :n_valid])
    # the same values; not the same program (another T), so not the same
    # order of sums inside a block
    assert np.allclose(ks["ssm"], ks0["ssm"], atol=1e-6)
    assert np.allclose(vs["conv"], vs0["conv"], atol=1e-6)
    assert np.allclose(lg[0, n_valid - 1], lg0[0, -1], atol=2e-6)
    # and within ONE program the padding's content is nothing to the state
    other = toks.copy()
    other[n_valid:] = (other[n_valid:] + 7) % CFG.vocab_size
    _, ks1, vs1 = f(params, other[None], jnp.int32(n_valid))
    assert np.array_equal(ks["ssm"], ks1["ssm"])
    assert np.array_equal(vs["conv"], vs1["conv"])


def test_admit_many_rows_keep_their_own_lengths(params):
    """Batched admission: each row's state ends at its own prompt's end."""
    eng = make_engine(params)
    a, b = tokens(9, seed=4), tokens(14, seed=5)
    eng.admit_many([0, 2], [a, b], [GREEDY, GREEDY])
    one = make_engine(params)
    one.admit(1, b, GREEDY)
    for x, y in zip(state_of(eng, 2), state_of(one, 1)):
        assert np.allclose(x, y, atol=1e-6)


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
        assert np.array_equal(before[s][0], after[s][0]), s
        assert np.array_equal(before[s][1], after[s][1]), s
    assert not np.array_equal(before[0][0], after[0][0])
    # and the parked slot goes on as if nothing had happened in between
    t = eng.extend(1, tokens(30, seed=6), 16, GREEDY)
    fresh = make_engine(params, cache=getattr(jnp, cache))
    t_fresh = fresh.admit(1, tokens(30, seed=6), GREEDY)
    # the tail read the first piece's keys and values back from the cache:
    # through int8 they are not what a one-piece prefill attends to
    exact = cache == "float32"
    assert t == t_fresh or not exact
    for x, y in zip(state_of(eng, 1), state_of(fresh, 1)):
        assert np.allclose(x, y, atol=1e-6 if exact else 1e-3)


def test_extend_refuses_to_cut_a_state_back(params):
    eng = make_engine(params)
    eng.admit(0, tokens(20), GREEDY)
    eng.release(0, park=True)
    with pytest.raises(ValueError, match="cannot be cut back"):
        eng.extend(0, tokens(30), 12, GREEDY)


# -- the scheduler: what assumed a cache can be cut back to a prefix -----

@pytest.fixture(scope="module")
def shared_engine(params):
    """One two-slot engine for the scheduler tests: its programs compile
    once; every test leaves its slots released."""
    return make_engine(params, slots=2)


def make_stack(eng, **kw):
    for s in range(eng.n_slots):
        eng.release(s)
    return eng, Scheduler(eng, **kw)


def uninterrupted(eng, prompt, opts, n):
    eng, sched = make_stack(eng)
    try:
        return list(sched.submit(prompt, opts, max_tokens=n).tokens())
    finally:
        sched.shutdown()


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


def test_parked_prefix_is_reused_only_whole(shared_engine):
    """A parked sequence is a prefix worth reusing only as a whole, and only
    where the slot's state stands at its end."""
    eng, sched = make_stack(shared_engine)
    manual(sched)
    try:
        base = [int(t) for t in tokens(24, seed=12)]
        req = type("R", (), {})()
        req.embeds = None
        sched.min_prefix_reuse = 4
        # the slot's state stands at 24 positions, as the parked list says
        eng.admit(0, np.asarray(base, np.int32), GREEDY)
        eng.release(0, park=True)
        sched._parked[0] = base
        req.admit_ids = base + [5, 6, 7]
        assert sched._best_prefix(req) == (0, 24)
        # shares 20 of the 24: a cache could be cut back, a state cannot
        req.admit_ids = base[:20] + [9, 9, 9, 9, 9, 9]
        assert sched._best_prefix(req) == (None, 0)
        # the whole parked list, but the engine ran on past it (a stream
        # that ended mid-chunk): the state is not where the list ends
        eng._host_lengths[0] = 27
        req.admit_ids = base + [5, 6, 7]
        assert sched._best_prefix(req) == (None, 0)
    finally:
        sched.shutdown()


def test_a_conversation_continues_correctly_after_parking(shared_engine):
    """End to end: a finished stream parks its slot; its continuation is
    served as a cold prefill would serve it, reused or not."""
    first = tokens(12, seed=13)
    eng, sched = make_stack(shared_engine)
    try:
        out = list(sched.submit(first, GREEDY, max_tokens=7).tokens())
        cont = np.concatenate([first, np.asarray(out, np.int32),
                               tokens(5, seed=14)])
        got = list(sched.submit(cont, GREEDY, max_tokens=6).tokens())
    finally:
        sched.shutdown()
    assert got == uninterrupted(shared_engine, cont, GREEDY, 6)


# -- the chip's share of the expert layer -------------------------------

@pytest.mark.parametrize("who", ["program", "reference"])
def test_the_two_shares_add_up_to_the_uncut_layer(ref, who):
    """Experts 0-3 and 4-7 of the toy's 8, each share with the shared expert
    added whole: their sum, the shared expert counted once, is the uncut
    layer of the reference."""
    full = dataclasses.replace(CFG, n_experts_held=CFG.n_experts)
    p = decoder.init_params(full, jax.random.PRNGKey(2), dtype=jnp.float32)
    lp_all, i = p["layers"], 3
    h = jax.random.normal(jax.random.PRNGKey(3), (11, CFG.dim), jnp.float32)
    want, _, _ = ref.expert_layer(lp_all, conf_of(full), h, i)

    def share(first, held):
        cfg = dataclasses.replace(CFG, n_experts_held=held,
                                  expert_first=first)
        cut = {k: (v[:, first:first + held]
                   if k in ("we_gate", "we_up", "we_down") else v)
               for k, v in lp_all.items()}
        if who == "reference":
            return ref.expert_layer(cut, conf_of(cfg), h, i)[0]
        lp = {k: v[i] for k, v in cut.items()
              if v.shape[0] == CFG.n_layers}
        u = decoder._norm(cfg, h[None], lp["mlp_norm_w"])
        return decoder._moe_mlp(cfg, lp, u)[0]

    u = np.asarray(decoder._norm(CFG, h, lp_all["mlp_norm_w"][i]))
    shared = (jax.nn.silu(u @ lp_all["we_sh_gate"][i])
              * (u @ lp_all["we_sh_up"][i])) @ lp_all["we_sh_down"][i]
    got = share(0, 4) + share(4, 4) - shared
    assert np.abs(np.asarray(got - want)).max() < 1e-5 * np.abs(want).max()
    # and a share alone is not the layer
    assert np.abs(np.asarray(share(0, 4) - want)).max() > 1e-3 * np.abs(
        want).max()


# -- serving defaults, accounting, metrics ------------------------------

def test_zero_config_resolution_on_the_chip(monkeypatch):
    """bfloat16 weights, int8 contiguous cache, 32 slots: from the model."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = cfglib.PRESETS["granite-4.0-h-small"]
    assert englib.resolve_engine_dtype(cfg, "tpu") == "bfloat16"
    ecfg = englib.resolve_serving_defaults(
        EngineConfig(max_slots=0, max_seq_len=4096, decode_chunk=0,
                     paged=None, page_size=0, n_pages=None,
                     cache_dtype=jnp.int8), cfg, None)
    assert (ecfg.paged, ecfg.max_slots, ecfg.decode_chunk) == (False, 32, 32)
    assert 32 * cfg.ssm_state_bytes < 2 << 30
    # dense contiguous models keep their 8
    mha = dataclasses.replace(cfglib.PRESETS["mixtral"])
    assert englib.resolve_serving_defaults(
        EngineConfig(max_slots=0, paged=None, decode_chunk=0, page_size=0),
        mha, None).max_slots == 8


@pytest.mark.parametrize("kw, what", [
    (dict(paged=True, page_size=16), "contiguous cache"),
])
def test_modes_without_a_place_for_the_state_are_refused(params, kw, what):
    with pytest.raises(ValueError, match=what):
        make_engine(params, **kw)


def test_accounting_prices_the_new_layers():
    cfg = cfglib.PRESETS["granite-4.0-h-small"]
    d, di, n = 4096, 8192, 128
    ssm = 2 * d * (di + 8448 + 128) + 2 * di * d + 2 * 4 * 8448 + 6 * di * n
    attn = 2 * (2 * d * 4096 + 2 * d * 1024)
    moe = 5 * 6 * d * 768 + 2 * d * 72 + 6 * d * 1536   # 10 x 36/72 kept here
    assert accounting.per_token_flops(cfg) == pytest.approx(
        9 * ssm + attn + 10 * moe + 2 * d * 50176)
    # one attention layer's span, not ten
    assert accounting.attn_span_flops(cfg, 0, 1) == 4.0 * 4096
    assert accounting.decode_flops(cfg, 100) > accounting.per_token_flops(cfg)


def test_state_gauge_and_ps_details(params, monkeypatch):
    monkeypatch.setenv("TPU_WARM_BUCKETS", "0")   # nothing is served here
    from ollama_operator_tpu.runtime.service import LoadedModel
    from ollama_operator_tpu.tokenizer.tokenizer import Tokenizer
    tok = Tokenizer("llama", [f"t{i}" for i in range(CFG.vocab_size)],
                    bos_id=1, eos_id=2)
    lm = LoadedModel("hybrid", CFG, params, tok, ecfg=EngineConfig(
        max_slots=2, max_seq_len=64, cache_dtype=jnp.float32,
        min_prefill_bucket=16))
    try:
        want = 2 * CFG.ssm_state_bytes
        assert lm.engine.state_bytes == want
        assert lm.engine.kv_bytes > want
        text = METRICS.render()
        assert f'tpu_model_cache_bytes{{kind="state"}} {want}' in \
            text.replace(".0", "")
    finally:
        lm.unload()
    # the gauge went with the model: no sample line is left
    assert not re.search(r"^tpu_model_cache_bytes\S* \d",
                         METRICS.render(), re.M)


# -- the benchmark's readers of the new scopes ---------------------------

HYBRID_READERS = ("decode_ssm_ms_per_step", "decode_moe_ms_per_step",
                  "ssm_state_roofline", "moe_experts_roofline")


def reader_ctx(conf):
    import types
    return types.SimpleNamespace(
        conf=conf, notes={}, resolved={"decode_chunk": 2,
                                       "weights": "bfloat16"},
        peaks={"hbm_bytes_per_s": 819e9}, trace_before={}, trace_after={})


@pytest.mark.parametrize("name", HYBRID_READERS)
def test_readers_return_none_without_a_trace(name, tmp_path, monkeypatch):
    """The driver runs the new readers on the parent's program too, which has
    none of the scopes: nothing to read is None, never an error."""
    from benchmark import run, trace_spans
    monkeypatch.setattr(trace_spans.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    assert run.layer_reader(name).read(
        reader_ctx(work.load_conf(CONF_PATH))) is None


def test_ssm_spans_reads_its_scopes_from_a_trace(tmp_path, monkeypatch):
    """Two complete runs of a decode module of two steps each: self time under
    each ``ssm.*`` scope over the steps; a trace without them reads None."""
    from benchmark import ssm_spans, trace_spans
    meta = {1: ("jit__decode_n(7)", ""),
            2: ("%fusion.1 = f32[] fusion()", "jit(_decode_n)/ssm.scan/mul"),
            3: ("%fusion.2 = f32[] fusion()", "jit(_decode_n)/ssm.in_proj/dot"),
            4: ("%fusion.3 = f32[] fusion()",
                "jit(_decode_n)/mlp/moe.experts/dot")}

    def planes(with_ssm):
        ops = []
        for t0 in (0, 2000):
            ops += [(t0 + 100, t0 + 400, 2 if with_ssm else 4),
                    (t0 + 400, t0 + 600, 3 if with_ssm else 4),
                    (t0 + 600, t0 + 900, 4)]
        return [{"name": "/device:TPU:0", "meta": meta, "lines": [
            {"name": "XLA Modules", "events": [(0, 1000, 1), (2000, 3000, 1)]},
            {"name": "XLA Ops", "events": ops}]}]

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    for with_ssm in (True, False):
        ssm_spans._CACHE.clear()
        pl = planes(with_ssm)
        monkeypatch.setattr(trace_spans, "find_trace", lambda w=None: str(path))
        monkeypatch.setattr(trace_spans, "reduce",
                            lambda w=None, pl=pl: trace_spans.reduce_planes(pl))
        monkeypatch.setattr(trace_spans, "read_planes", lambda p, pl=pl: pl)
        got = ssm_spans.step_seconds(2)
        if with_ssm:
            assert got == pytest.approx({"ssm.scan": 150e-12,
                                         "ssm.in_proj": 100e-12})
        else:
            assert got is None


def test_the_configurations_work_arithmetic():
    """The sizes the issue reckons with, from the configuration's own file."""
    conf = work.load_conf(CONF_PATH)
    w = work.load_module(os.path.join(conf["_dir"], conf["work"]))
    assert w.mamba_params(conf) == 4096 * 16768 + 8192 * 4096
    assert w.attention_params(conf) == 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert w.expert_params(conf) == 3 * 4096 * 768
    # one sequence, one Mamba layer: [128, 64, 128] + 3 x 8448, float32
    assert w.state_bytes(conf) == 4 * (8192 * 128 + 3 * 8448)
    assert w.ssm_state_bytes_step(conf, 32) == 32 * 9 * 2 * w.state_bytes(conf)
    total = work.weight_bytes_step(conf, 1e9, "bfloat16") \
        - w.ssm_state_bytes_step(conf, 1e9)
    assert total == pytest.approx(2 * cfglib.PRESETS[
        "granite-4.0-h-small"].n_params, rel=2e-3)     # vectors aside
    assert work.kv_bytes_per_token(conf, "int8") == 2 * 8 * (128 + 4)
    assert work.attn_flops_per_pair(conf) == 4 * 32 * 128
