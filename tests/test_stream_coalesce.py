"""Chunked token fan-out and stream coalescing (ISSUE 1).

Covers the scheduler→service→HTTP streaming path introduced for the
serving-gap work: per-dispatch queue items, batched incremental
detokenisation, chunk-granular stop matching, and frame coalescing."""

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.runtime.engine import Engine, EngineConfig, SlotOptions
from ollama_operator_tpu.runtime.scheduler import RequestStats, Scheduler
from ollama_operator_tpu.runtime.service import StopMatcher
from ollama_operator_tpu.runtime import service as svc
from ollama_operator_tpu.runtime.trace import NULL_TRACE
from ollama_operator_tpu.server.app import (_StreamCoalescer,
                                            resolve_stream_flush,
                                            STREAM_FLUSH_TOKENS)
from ollama_operator_tpu.tokenizer import StreamDecoder

from test_tokenizer import spm_tok

GREEDY = SlotOptions(temperature=0.0, repeat_penalty=1.0)


def make_stack(slots=1, decode_chunk=8):
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    eng = Engine(cfg, params,
                 ecfg=EngineConfig(max_slots=slots, max_seq_len=64,
                                   decode_chunk=decode_chunk,
                                   cache_dtype=jnp.float32,
                                   min_prefill_bucket=16))
    return Scheduler(eng)


def byte_tok():
    byte_toks = [f"<0x{b:02X}>" for b in range(256)]
    return spm_tok(extra_tokens=byte_toks, extra_scores=[0.0] * 256)


def bids(t, text):
    return [t.vocab[f"<0x{b:02X}>"] for b in text.encode("utf-8")]


# --- scheduler: one queue item per decode dispatch ------------------------


def test_queue_items_bounded_by_decode_chunks():
    """ISSUE 1 acceptance: a request of N generated tokens crosses the
    scheduler→service queue in at most ceil(N / decode_chunk) items, not
    N items (per-token fan-out was ~35% of the old HTTP gap)."""
    sched = make_stack(slots=1, decode_chunk=8)
    try:
        r = sched.submit(np.array([1, 2], np.int32), GREEDY, max_tokens=17)
        chunks = list(r.chunks())
        total = sum(len(c) for c in chunks)
        assert total == 17
        assert len(chunks) <= math.ceil(17 / 8)
        # byte-for-byte identical token stream to the per-token view
        r2 = sched.submit(np.array([1, 2], np.int32), GREEDY, max_tokens=17)
        assert [t for c in chunks for t in c] == list(r2.tokens())
    finally:
        sched.shutdown()


# --- detokeniser: batched feed is equivalent to per-token feed ------------


def test_feed_many_matches_sequential_feed():
    t = byte_tok()
    ids = bids(t, "abéc世d")      # multi-byte chars inside
    for cut in range(1, len(ids)):
        sd1, sd2 = StreamDecoder(t), StreamDecoder(t)
        seq = "".join(sd1.feed(i) for i in ids)
        batched = sd2.feed_many(ids[:cut]) + sd2.feed_many(ids[cut:])
        assert seq + sd1.flush() == batched + sd2.flush() == "abéc世d"


def test_feed_many_holds_partial_utf8_at_chunk_boundary():
    t = byte_tok()
    ids = bids(t, "aé")               # é = 0xC3 0xA9
    sd = StreamDecoder(t)
    assert sd.feed_many(ids[:2]) == "a"    # 0xC3 held back
    assert sd.feed_many(ids[2:]) == "é"


# --- stop matching at chunk granularity -----------------------------------


def test_stop_matcher_split_across_chunks():
    sm = StopMatcher(["STOP"])
    assert sm.feed("hello ST") == "hello "   # partial match held back
    assert sm.feed("OP world") == ""
    assert sm.hit
    assert sm.flush() == ""


def test_stream_truncates_stop_split_across_chunks():
    """A stop string whose halves land in two different coalesced decode
    chunks must still truncate the stream and report done_reason="stop"."""
    t = byte_tok()
    chunks = [bids(t, "abcX"), bids(t, "Yz after stop")]

    class FakeReq:
        def __init__(self):
            self.cancelled = False
            self.stats = RequestStats(n_prompt=2)
            self.stats.n_generated = sum(len(c) for c in chunks)
            # what a Request carries beside its stream: an id, and the
            # trace it has at TPU_TRACE=0
            self.id = 1
            self.trace = NULL_TRACE

        def chunks(self):
            for c in chunks:
                yield c

        def cancel(self):
            self.cancelled = True

    class FakeSelf:
        tokenizer = t

    req = FakeReq()
    out = list(svc.LoadedModel._stream(
        FakeSelf(), req, ["XY"], [1, 2], 100, time.monotonic(), None))
    pieces = [p for p, res in out if res is None]
    final = out[-1][1]
    assert "".join(pieces) == "abc"          # truncated before the stop
    assert final.text == "abc"
    assert final.done_reason == "stop"
    assert req.cancelled                     # slot freed on stop hit
    # _Piece carries per-chunk token counts for the HTTP coalescer
    assert sum(getattr(p, "n_tokens", 1) for p in pieces) == len(chunks[0])


# --- HTTP frame coalescing ------------------------------------------------


def test_resolve_stream_flush_precedence(monkeypatch):
    assert resolve_stream_flush(None) == (STREAM_FLUSH_TOKENS, 0.025)
    monkeypatch.setenv("TPU_STREAM_FLUSH_TOKENS", "4")
    monkeypatch.setenv("TPU_STREAM_FLUSH_MS", "100")
    assert resolve_stream_flush({}) == (4, 0.1)
    # request options win over env; floors apply
    assert resolve_stream_flush(
        {"stream_flush_tokens": 0, "stream_flush_ms": -5}) == (1, 0.0)
    assert resolve_stream_flush(
        {"stream_flush_tokens": "bogus"}) == (STREAM_FLUSH_TOKENS, 0.1)


def test_coalescer_first_piece_immediate_then_batches():
    frames = []
    co = _StreamCoalescer(frames.append, lambda s: s, max_tokens=4,
                          max_s=3600.0)
    co.add("a")                  # TTFT piece: flushes immediately
    assert frames == ["a"]
    co.add("b")
    co.add("c")
    assert frames == ["a"]       # below the token threshold, buffered
    co.add("defg")               # still 1 token by default attr... counts 1
    co.add("h")                  # 4th buffered token → flush
    assert frames == ["a", "bcdefgh"]
    co.add("tail")
    co.flush()                   # explicit end-of-stream drain
    assert frames == ["a", "bcdefgh", "tail"]
    assert co.frames == 3


def test_coalescer_respects_piece_token_counts():
    frames = []
    co = _StreamCoalescer(frames.append, lambda s: s, max_tokens=8,
                          max_s=3600.0)

    class P(str):
        n_tokens = 0
    first = P("x")
    first.n_tokens = 1
    co.add(first)                # flush (first frame)
    big = P("eight-token chunk")
    big.n_tokens = 8
    co.add(big)                  # 8 tokens at once → immediate flush
    assert frames == ["x", "eight-token chunk"]
