"""Checks of the yardstick itself. Run by hand: ``python -m pytest
benchmark/tests -q`` from the root of the repo (tier-1 collects ``tests/``
only)."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark import prom, reduce_trace, stats, traffic_gen, work  # noqa: E402
from benchmark.stats import Record  # noqa: E402


def conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# -- percentiles and the open-loop due-time arithmetic -----------------------

def test_percentile_interpolates_between_order_statistics():
    assert stats.percentile([1, 2, 3, 4, 5], 0.5) == 3
    assert stats.percentile([10, 20], 0.95) == pytest.approx(19.5)
    assert stats.percentile(list(range(101)), 0.95) == pytest.approx(95.0)


@pytest.mark.parametrize("n,q,ok", [(199, 0.95, False), (200, 0.95, True),
                                    (99, 0.9, False), (100, 0.9, True),
                                    (19, 0.5, False), (20, 0.5, True)])
def test_a_percentile_needs_ten_samples_beyond_it(n, q, ok):
    assert stats.supported(n, q) is ok


def rec(i, due, sent, frames, done, n=4, measured=True):
    return Record(index=i, measured=measured, t_due=due, t_sent=sent,
                  prompt_tokens=10, output_tokens=n, frames=frames,
                  t_done=done, eval_count=n, status=200)


def test_ttft_counts_from_when_the_request_was_due_not_sent():
    # due at 100.0, sent 0.3 s late, first text frame at 100.5
    r = [rec(0, 100.0, 100.3, [100.5, 100.6, 101.0], 101.1)]
    m = stats.reduce_records(r, 100.0, 10.0, ["ttft_p50_ms", "ttft_p90_ms",
                                              "stream_gap_p50_ms"])
    assert m["ttft_p50_ms"]["value"] == pytest.approx(500.0)
    assert m["stream_gap_p50_ms"]["value"] == pytest.approx(250.0)
    assert m["ttft_p90_ms"]["supported"] is False


def test_out_tok_s_counts_what_finished_inside_the_window():
    r = [rec(0, 99.0, 99.0, [99.5], 100.5, n=30, measured=False),  # ramp, in
         rec(1, 101.0, 101.0, [101.5], 109.9, n=50),               # in
         rec(2, 105.0, 105.0, [105.5], 110.2, n=70)]               # ends late
    m = stats.reduce_records(r, 100.0, 10.0, ["out_tok_s"])
    assert m["out_tok_s"]["value"] == pytest.approx(8.0)
    assert m["out_tok_s"]["n"] == 2


def test_a_request_short_of_its_tokens_is_not_ok():
    r = rec(0, 0.0, 0.0, [0.5], 1.0, n=8)
    r.eval_count = 7
    assert not r.ok


# -- traffic: the seed decides the order, not the work -----------------------

# no cell uses the open-loop mix yet (PERF.md, section 7)
OPEN_MIX = traffic_gen.load_mix("chat-open")


@pytest.mark.parametrize("mix,rate", [
    (OPEN_MIX, 3.0), (traffic_gen.load_mix("decode-saturated"), None)])
def test_same_seed_same_traffic_other_seed_same_work(mix, rate):
    big = 2 ** 31 + 12345
    a = traffic_gen.make_requests(mix, big, max_seq_len=2048, seconds=40,
                                  rate_rps=rate)
    b = traffic_gen.make_requests(mix, big, max_seq_len=2048, seconds=40,
                                  rate_rps=rate)
    c = traffic_gen.make_requests(mix, 5, max_seq_len=2048, seconds=40,
                                  rate_rps=rate)
    assert a == b
    assert a != c

    def window(reqs):
        return [r for r in reqs if r.due_s is None or r.due_s >= 0]
    assert (sorted(r.output_tokens for r in window(a))
            == sorted(r.output_tokens for r in window(c)))
    assert len(window(a)) == len(window(c))
    assert traffic_gen.prompt_text(50, 4, big, 3) == \
        traffic_gen.prompt_text(50, 4, big, 3)
    assert len(traffic_gen.prompt_text(50, 4, big, 3)) == 46
    assert all(r.prompt_tokens + r.output_tokens <= 2048 - 16 for r in a)


def test_open_loop_offers_the_cells_rate_inside_the_window():
    mix = OPEN_MIX
    reqs = traffic_gen.make_requests(mix, 9, max_seq_len=4096, seconds=40,
                                     rate_rps=2.5)
    due = [r.due_s for r in reqs]
    assert due == sorted(due)
    assert sum(1 for d in due if d >= 0) == 100
    assert sum(1 for d in due if d < 0) == round(2.5 * mix["ramp_seconds"])
    assert max(due) < 40 and min(due) >= -mix["ramp_seconds"]


def test_lognormal_quantiles_have_the_stated_median_and_clip():
    q = traffic_gen.quantiles({"dist": "lognormal", "median": 256,
                               "sigma": 1.0, "lo": 32, "hi": 2048}, 1001)
    assert q[500] == 256 and q.min() >= 32 and q.max() <= 2048


# -- work.py against numbers worked by hand ----------------------------------

def test_starcoder2_3b_work_by_hand():
    c = conf("starcoder2-3b")
    # a layer: q and o 3072x3072, k and v 3072x256, two 3072x12288
    layer = 2 * 3072 * 3072 + 2 * 3072 * 256 + 2 * 3072 * 12288
    assert layer == 95_944_704 == work.layer_matmul_params(c)
    head = 3072 * 49152
    total = 30 * layer + head
    assert total == 3_029_336_064 == work.matmul_params(c)
    # int8: a byte a weight and one float32 scale per 32 of them; the tied
    # head is read as the bfloat16 embedding
    assert work.weight_bytes(c, "int8") == 30 * layer * 1.125 + head * 2
    # int8 KV: 2 x 30 layers x 2 heads x (128 codes + one 4-byte scale)
    assert work.kv_bytes_per_token(c, "int8") == 2 * 30 * 2 * 132 == 15_840
    step = work.decode_step(c, 64, 10_240, "int8", "int8")
    assert step["flops"] == 2 * total * 64 + 4 * 30 * 24 * 128 * 10_240
    assert step["bytes"] == (30 * layer * 1.125 + head * 2
                             + (10_240 + 64) * 15_840 + 64 * 49152 * 4)


def test_phi_2_work_by_hand():
    c = conf("phi-2")
    layer = 4 * 2560 * 2560 + 2 * 2560 * 10240
    assert layer == 78_643_200 == work.layer_matmul_params(c)
    total = 32 * layer + 2560 * 51200
    assert total == 2_647_654_400 == work.matmul_params(c)
    assert work.weight_bytes(c, "int8") == total * 1.125
    # MHA: 2 x 32 layers x 32 heads x (80 codes + one 4-byte scale)
    assert work.kv_bytes_per_token(c, "int8") == 2 * 32 * 32 * 84 == 172_032


def test_a_configuration_with_no_work_file_gives_the_dense_formulas():
    """The parent's arithmetic, written out: nothing moves for a file that
    names no ``work``."""
    for name in ("starcoder2-3b", "phi-2"):
        c = conf(name)
        assert "work" not in c and work.own(c, "kv_bytes_per_token") is None
        L, H, hd = (c["num_hidden_layers"], c["num_attention_heads"],
                    c["head_dim"])
        total = work.matmul_params(c)
        assert work.matmul_flops_per_token(c) == 2.0 * total
        assert work.attn_flops_per_pair(c) == 4 * L * H * hd
        assert work.weight_bytes_step(c, 37.5, "int8") == work.weight_bytes(
            c, "int8")
        kvb = 2 * L * c["num_key_value_heads"] * (hd * 1.0 + 4.0)
        step = work.decode_step(c, 50.2, 8_800.5, "int8", "int8")
        assert step["flops"] == 2.0 * total * 50.2 + 4 * L * H * hd * 8_800.5
        assert step["bytes"] == (work.weight_bytes(c, "int8")
                                 + (8_800.5 + 50.2) * kvb
                                 + 50.2 * c["vocab_size"] * 4.0)
        pre = work.prefill(c, 777, "int8", "int8")
        assert pre["flops"] == (2.0 * total * 777
                                + 4 * L * H * hd * 777 * 778 / 2.0)
        assert pre["bytes"] == (work.weight_bytes(c, "int8") + 777 * kvb
                                + c["vocab_size"] * 4.0)


def test_routed_work_by_hand():
    """One chip's share of a routed layer: 128 experts of which 32 are held,
    4 a token, one shared expert, grouped-query attention (32 heads over 8
    key/value heads of 128). The fixture's work file, fed these keys."""
    c = work.load_conf(os.path.join(HERE, "fixtures", "routed.json"))
    c.update(hidden_size=4096, num_hidden_layers=6, num_attention_heads=32,
             num_key_value_heads=8, head_dim=128, moe_intermediate_size=2048,
             n_routed_experts=128, experts_held=32, num_experts_per_tok=4,
             shared_intermediate_size=2048, vocab_size=32768)
    attn = 2 * 4096 * 32 * 128 + 2 * 4096 * 8 * 128     # q, o; k, v
    assert attn == 41_943_040
    expert = 3 * 4096 * 2048
    shared = expert + 4096                      # and its hidden x 1 gate
    router = 4096 * 128
    assert expert == 25_165_824
    layer = attn + shared + router + 32 * expert
    assert layer == 872_943_616 == work.layer_matmul_params(c)
    head = 4096 * 32768
    assert work.matmul_params(c) == 6 * layer + head

    # expected distinct held experts: 32 x (1 - (1 - 4/128)^batch)
    own = work.load_module(os.path.join(HERE, "fixtures", "routed.work.py"))
    assert own.distinct_experts(c, 1) == pytest.approx(1.0)
    assert own.distinct_experts(c, 64) == pytest.approx(
        32 * (1 - (31 / 32) ** 64)) == pytest.approx(27.8058, abs=1e-3)
    assert own.distinct_experts(c, 4096) == pytest.approx(32.0)
    for batch in (1, 64, 4096):
        touched = own.distinct_experts(c, batch)
        assert work.weight_bytes_step(c, batch, "bfloat16") == pytest.approx(
            2.0 * (6 * (attn + shared + router + touched * expert) + head))
    # at batch 1 a step reads one held expert a layer, not 32
    assert work.weight_bytes_step(c, 1, "bfloat16") == pytest.approx(
        2.0 * (6 * (attn + shared + router + expert) + head))

    # a token meets 4 x 32/128 = 1 held expert a layer
    assert work.matmul_flops_per_token(c) == 2.0 * (
        6 * (attn + shared + router + 1.0 * expert) + head)
    # the cache and a query against it are grouped-query attention's, which
    # the work file leaves to work.py: 2 x 8 heads x (128 codes + a scale)
    assert own_names(own) == {"layer_matmul_params", "weight_bytes_step",
                              "matmul_flops_per_token"}
    assert work.kv_bytes_per_token(c, "int8") == 6 * 2 * 8 * 132 == 12_672
    assert work.kv_bytes_per_token(c, "bfloat16") == 6 * 2 * 8 * 256
    assert work.attn_flops_per_pair(c) == 4 * 6 * 32 * 128
    step = work.decode_step(c, 64, 100_000, "bfloat16", "int8")
    assert step["flops"] == (work.matmul_flops_per_token(c) * 64
                             + 4 * 6 * 32 * 128 * 100_000)
    assert step["bytes"] == (work.weight_bytes_step(c, 64, "bfloat16")
                             + (100_000 + 64) * 12_672 + 64 * 32768 * 4.0)


def own_names(module):
    """Which of ``work.py``'s five askable functions a work file defines."""
    return {n for n in ("layer_matmul_params", "weight_bytes_step",
                        "matmul_flops_per_token", "kv_bytes_per_token",
                        "attn_flops_per_pair") if hasattr(module, n)}


def test_the_fixtures_own_sizes_by_hand():
    c = work.load_conf(os.path.join(HERE, "fixtures", "routed.json"))
    attn = 512 * 8 * 64 * 2 + 2 * 512 * 2 * 64
    layer = attn + (3 * 512 * 128 + 512) + 512 * 64 + 64 * 3 * 512 * 128
    assert work.layer_matmul_params(c) == layer
    assert work.kv_bytes_per_token(c, "int8") == 2 * 4 * 2 * (64 + 4)
    assert work.attn_flops_per_pair(c) == 4 * 4 * 8 * 64


def test_a_work_file_is_asked_for_each_function_it_defines(tmp_path):
    """The cache and the attention too, for a layer that is not grouped-query
    (the fixture defines neither): whatever a work file defines is what
    ``decode_step`` and ``prefill`` count, the rest stays ``work.py``'s."""
    (tmp_path / "odd.work.py").write_text(
        "def kv_bytes_per_token(conf, kv):\n"
        "    return conf['num_hidden_layers'] * {'int8': 324}[kv]\n"
        "def attn_flops_per_pair(conf):\n"
        "    return conf['num_hidden_layers'] * 1000\n")
    c = dict(conf("starcoder2-3b"), work="odd.work.py", _dir=str(tmp_path))
    dense = conf("starcoder2-3b")
    assert work.kv_bytes_per_token(c, "int8") == 30 * 324
    assert work.attn_flops_per_pair(c) == 30_000
    assert work.matmul_flops_per_token(c) == work.matmul_flops_per_token(dense)
    step = work.decode_step(c, 10, 5_000, "int8", "int8")
    assert step["flops"] == (work.matmul_flops_per_token(dense) * 10
                             + 30_000 * 5_000)
    assert step["bytes"] == (work.weight_bytes(dense, "int8")
                             + 5_010 * 30 * 324 + 10 * 49152 * 4.0)


def test_least_seconds_names_its_bound_and_unknown_devices_are_an_error():
    peaks = work.load_peaks(os.path.join(BENCH, "peaks.json"), "TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    least = work.least_seconds({"bytes": 819e9 * 0.01, "flops": 197e12 * 0.002},
                               peaks)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(0.01)
    with pytest.raises(KeyError):
        work.load_peaks(os.path.join(BENCH, "peaks.json"), "TPU v9 imaginary")


# -- /metrics deltas ---------------------------------------------------------

SCRAPE_A = """# HELP x
tpu_model_queue_wait_seconds_bucket{le="0.1"} 10
tpu_model_queue_wait_seconds_bucket{le="1.0"} 10
tpu_model_queue_wait_seconds_bucket{le="+Inf"} 10
tpu_model_useful_tokens_total{kind="decode"} 100
tpu_model_useful_tokens_total{kind="prefill"} 50
"""
SCRAPE_B = """tpu_model_queue_wait_seconds_bucket{le="0.1"} 10
tpu_model_queue_wait_seconds_bucket{le="1.0"} 110
tpu_model_queue_wait_seconds_bucket{le="+Inf"} 110
tpu_model_useful_tokens_total{kind="decode"} 400
tpu_model_useful_tokens_total{kind="prefill"} 90
"""


def test_prom_deltas_and_histogram_percentile():
    a, b = prom.parse(SCRAPE_A), prom.parse(SCRAPE_B)
    assert prom.delta(a, b, "tpu_model_useful_tokens_total",
                      kind="decode") == 300
    assert prom.delta(a, b, "tpu_model_useful_tokens_total") == 340
    assert prom.delta(a, b, "no_such_metric") is None
    # all 100 new observations fell in (0.1, 1.0]: the 95th sits at 0.955
    assert prom.hist_percentile(a, b, "tpu_model_queue_wait_seconds",
                                0.95) == pytest.approx(0.1 + 0.9 * 0.95)


# -- the trace reduction -----------------------------------------------------

def plane(name, lines):
    def ev(s, d, n):
        return types.SimpleNamespace(start_ns=s, duration_ns=d, name=n)
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
        for ln, evs in lines.items()])


def test_reduce_planes_on_a_trace_worked_by_hand():
    dev = plane("/device:TPU:0", {
        "XLA Modules": [(0, 400, "jit_step(11)"), (600, 300, "jit_step(11)"),
                        (950, 50, "jit_other(7)")],
        "XLA Ops": [(0, 100, "fusion.1"), (50, 150, "copy.2"),   # overlap
                    (300, 100, "fusion.1"), (600, 300, "fusion.1"),
                    (950, 50, "reduce.3")]})
    host = plane("/host:CPU", {"python3": [(0, 5000, "ignored")]})
    out = reduce_trace.reduce_planes([host, dev])
    assert out["window_s"] == pytest.approx(1000e-9)
    # busy: [0,200) + [300,400) + [600,900) + [950,1000) = 650 ns
    assert out["busy_s"] == pytest.approx(650e-9)
    assert out["modules"]["jit_step"]["runs"] == 2
    assert out["modules"]["jit_step"]["seconds"] == pytest.approx(700e-9)
    assert out["modules"]["jit_step"]["median_run_s"] == pytest.approx(350e-9)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(500e-9)]
    assert out["idle_gaps"][0] == ["jit_step -> jit_step",
                                   pytest.approx(200e-9)]
    assert reduce_trace.reduce_planes([host])["error"]


FIXTURE = os.path.join(HERE, "small.xplane.pb")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="the recorded trace is not in this checkout")
def test_reduce_file_on_the_recorded_chip_trace():
    """One small trace recorded on a TPU v5 lite (four runs each of two tiny
    jitted programs; ``expected.json`` beside it holds what was read off it
    when it was recorded)."""
    out = reduce_trace.reduce_file(FIXTURE)
    with open(os.path.join(HERE, "small.expected.json")) as f:
        want = json.load(f)
    assert out["devices"] == want["devices"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert 0.0 < out["busy_s"] < out["window_s"]
    for name, m in want["modules"].items():
        assert out["modules"][name]["runs"] == m["runs"]
        assert out["modules"][name]["seconds"] == pytest.approx(m["seconds"])


# -- the stream's frames carry its text ---------------------------------------

def test_frames_check_counts_requests_with_fewer_text_frames_than_chunks():
    ok = rec(0, 0.0, 0.0, [0.1, 1.0, 2.0, 3.0], 3.1, n=100)    # 4 due, 4
    held = rec(1, 0.0, 0.0, [0.1, 3.0], 3.1, n=100)            # 4 due, 2
    more = rec(2, 0.0, 0.0, [0.1, 0.2, 1.0, 2.0, 3.0], 3.1, n=100)
    out = stats.frames_check([ok, held, more], 32)
    assert out["frames_due"] == 12 and out["text_frames"] == 11
    assert out["short"] == 1
    assert out["short_share"] == pytest.approx(1 / 3)


def test_every_token_of_the_synthetic_vocabulary_streams_as_whole_text():
    """Whatever tokens random weights emit, the program's StreamDecoder gives
    each chunk's text at once and holds nothing back (a vocabulary with lone
    bytes of 0x80-0xFF held text back for chunks on end: PERF.md, PR 23)."""
    import numpy as np
    from benchmark.server_child import byte_tokenizer
    from ollama_operator_tpu.tokenizer import StreamDecoder
    tok = byte_tokenizer(51200)
    assert len(tok.tokens) == 51200
    assert tok.encode("abc", add_bos=False)[-3:] == [
        tok.vocab[f"<0x{ord(c):02X}>"] for c in "abc"]
    sd = StreamDecoder(tok)
    rng = np.random.default_rng(7)
    for ids in (range(3, 3 + 256), rng.integers(3, 51200, 4096)):
        for i in ids:
            assert sd.feed_many([int(i)]) and not sd._buf


def test_overlap_tok_s_takes_the_windows_share_of_each_request():
    r = [rec(0, 95.0, 95.0, [96.0], 104.0, n=80),      # half of it inside
         rec(1, 101.0, 101.0, [102.0], 106.0, n=40),   # all of it inside
         rec(2, 108.0, 108.0, [109.0], 113.0, n=40)]   # a quarter inside
    assert stats.overlap_tok_s(r, 100.0, 10.0) == pytest.approx(9.0)
