"""Utilization & goodput accounting (runtime/accounting.py).

The FLOPs formulas are checked against hand-computed values for two
model configs (tiny and llama2) plus the MoE and sliding-window
variants; the goodput/occupancy split is checked across padded buckets
including spec k>0 and chunked prefill; the engine's recompile detector
must fire exactly once per unwarmed executable signature and never for
AOT-warmed ones.
"""

import dataclasses
import time

import numpy as np
import pytest

from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.runtime import accounting
from ollama_operator_tpu.runtime.accounting import (NULL_ACCOUNTING,
                                                    UtilizationAccounting,
                                                    attn_span_flops,
                                                    decode_flops,
                                                    detect_peak_flops,
                                                    device_peaks,
                                                    make_accounting,
                                                    per_token_flops,
                                                    prefill_flops,
                                                    _ctx_sum)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

TINY = cfglib.PRESETS["tiny"]
LLAMA2 = cfglib.PRESETS["llama2"]
TINY_MOE = cfglib.PRESETS["tiny-moe"]


# -- per-position FLOPs vs hand-computed values ------------------------

def test_per_token_flops_tiny_hand_computed():
    # tiny: d=64 q=64 kv=32 L=2 ffn=128 vocab=256, gated MLP
    # proj = 2*(64*64 + 2*64*32 + 64*64) = 24576
    # mlp  = 6*64*128                    = 49152
    # head = 2*64*256                    = 32768
    assert per_token_flops(TINY) == 2 * (24576 + 49152) + 32768 == 180224


def test_per_token_flops_llama2_hand_computed():
    # llama2 7B: d=4096 q=kv=4096 L=32 ffn=11008 vocab=32000
    # proj = 2*4*4096^2        = 134217728
    # mlp  = 6*4096*11008      = 270532608
    # head = 2*4096*32000      = 262144000
    expect = 32 * (134217728 + 270532608) + 262144000
    assert per_token_flops(LLAMA2) == expect == 13214154752
    # sanity: ~2 FLOPs per weight per token for a 7B-class model
    assert 1.8 * LLAMA2.n_params < expect < 2.5 * LLAMA2.n_params


def test_per_token_flops_moe_counts_topk_plus_router():
    # tiny-moe: 4 experts top-2 → mlp = 2*(6*64*128) + router 2*64*4
    expect = 2 * (24576 + (2 * 49152 + 512)) + 32768
    assert per_token_flops(TINY_MOE) == expect == 279552


def test_ctx_sum_closed_forms():
    # pure arithmetic series
    assert _ctx_sum(0, 4) == 1 + 2 + 3 + 4
    assert _ctx_sum(9, 2) == 10 + 11
    # window caps: linear head then flat tail
    assert _ctx_sum(0, 16, window=8) == sum(min(p + 1, 8) for p in range(16))
    # fully capped span
    assert _ctx_sum(10, 4, window=8) == 4 * 8
    assert _ctx_sum(5, 0) == 0.0


def test_attn_span_and_prefill_tiny_hand_computed():
    # tiny is full attention on both layers: span [0,4) attends 1+2+3+4
    # keys per layer, 4*q_dim FLOPs per key
    assert attn_span_flops(TINY, 0, 4) == 4 * 64 * (2 * 10) == 5120
    assert prefill_flops(TINY, 0, 4) == 4 * 180224 + 5120


def test_decode_flops_continues_the_series():
    # 2 steps from 10 attended keys: steps attend 10 then 11
    assert decode_flops(TINY, 10, 2) == 2 * 180224 + 4 * 64 * (2 * 21)
    # decode IS a width-n prefill starting one position back
    assert decode_flops(TINY, 10, 2) == prefill_flops(TINY, 9, 2)


def test_sliding_window_layers_split_and_cap():
    sw = dataclasses.replace(TINY, sliding_window=8)
    # all layers sliding: span past the window costs window keys/step
    assert attn_span_flops(sw, 100, 2) == 4 * 64 * (2 * 2 * 8)
    # gemma-style alternation: layer i%3==2 is full, rest sliding
    alt = dataclasses.replace(TINY, n_layers=6, sliding_window=8,
                              altern_sliding=True, sliding_pattern=3)
    full_keys = _ctx_sum(100, 2)
    assert attn_span_flops(alt, 100, 2) == \
        4 * 64 * (2 * full_keys + 4 * 2 * 8)


# -- peak detection ----------------------------------------------------

def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("TPU_PEAK_FLOPS", "197e12")
    peak, kind = detect_peak_flops()
    assert peak == 197e12 and kind == "override"


def test_peak_flops_bad_override_falls_through(monkeypatch):
    monkeypatch.setenv("TPU_PEAK_FLOPS", "not-a-number")
    peak, kind = detect_peak_flops()
    assert kind != "override"


@pytest.mark.parametrize("kind,want", [
    ("TPU v5 lite", (197e12, 819e9)),     # what jax reports for a v5e
    ("TPU v4", (275e12, 1228e9)),
])
def test_device_peaks_by_kind(kind, want):
    assert device_peaks(kind) == want


def test_device_peaks_unknown_device_is_an_error():
    # an unlisted device must never read as a v5e
    with pytest.raises(KeyError):
        device_peaks("cpu")


# -- goodput / occupancy accumulator -----------------------------------

def make_acct(cfg=TINY, peak=1e12):
    return UtilizationAccounting(cfg, peak_flops=peak, device_kind="unit")


def _rebucket(acct, ago=2):
    """Move everything in the ring a couple of seconds into the past so
    snapshot()'s in-progress-second exclusion doesn't hide it."""
    with acct._lock:
        cells = list(acct._ring.values())
        acct._ring.clear()
        merged = [sum(c[i] for c in cells) for i in range(4)]
        acct._ring[int(time.monotonic()) - ago] = merged


def test_decode_goodput_counts_padded_slots():
    acct = make_acct()
    acct.on_decode(0.01, ctxs=[5, 9], n_steps=4, capacity=4)
    assert acct.useful_tokens["decode"] == 8      # 2 active x 4 steps
    assert acct.padded_tokens["decode"] == 8      # 2 empty slots x 4
    expect = (4 * per_token_flops(TINY) + attn_span_flops(TINY, 4, 4)
              + 4 * per_token_flops(TINY) + attn_span_flops(TINY, 8, 4))
    assert acct.model_flops == pytest.approx(expect)


def test_prefill_goodput_counts_bucket_padding():
    acct = make_acct()
    acct.on_prefill(0.01, start=0, n_new=10, bucket=16)
    assert acct.useful_tokens["prefill"] == 10
    assert acct.padded_tokens["prefill"] == 6
    # chunked prefill: the second piece starts where the first ended and
    # fills its bucket exactly → no extra padding
    acct.on_prefill(0.01, start=10, n_new=16, bucket=16)
    assert acct.useful_tokens["prefill"] == 26
    assert acct.padded_tokens["prefill"] == 6
    expect = prefill_flops(TINY, 0, 10) + prefill_flops(TINY, 10, 16)
    assert acct.model_flops == pytest.approx(expect)


def test_snapshot_occupancy_waste_and_mfu():
    acct = make_acct(peak=1e9)
    acct.on_decode(0.02, ctxs=[5, 9, 11], n_steps=4, capacity=4)
    _rebucket(acct)
    snap = acct.snapshot(window_s=60)
    assert snap["enabled"] is True
    assert snap["occupancy"] == pytest.approx(12 / 16)
    assert snap["waste_pct"] == pytest.approx(25.0)
    assert snap["mfu"] is not None and snap["mfu"] > 0
    assert snap["totals"]["useful_tokens"]["decode"] == 12
    assert snap["totals"]["dispatches"]["decode"] == 1
    assert snap["busy_s"] == pytest.approx(0.02)


def test_snapshot_without_peak_reads_null_mfu():
    acct = make_acct(peak=0.0)
    acct.on_decode(0.01, ctxs=[5], n_steps=1, capacity=1)
    _rebucket(acct)
    snap = acct.snapshot()
    assert snap["mfu"] is None and snap["peak_flops"] is None
    assert snap["occupancy"] == 1.0 and snap["waste_pct"] == 0.0


def test_breakdown_classifies_wait_idle_host():
    acct = make_acct()
    acct.on_wait(0.5)
    acct.on_idle(0.25)
    bd = acct.breakdown()
    assert bd["dispatch_wait_s"] == pytest.approx(0.5)
    assert bd["idle_s"] == pytest.approx(0.25)
    assert bd["wall_s"] >= 0 and bd["host_s"] >= 0


def test_ring_is_bounded_and_ordered():
    acct = make_acct()
    base = int(time.monotonic())
    with acct._lock:
        # backfill strictly-past seconds; the next dispatch opens the
        # current second's cell, which is what triggers the prune
        for i in range(1, accounting.RING_SECONDS + 41):
            acct._ring[base - i] = [1.0, 1.0, 0.0, 0.0]
    acct.on_decode(0.001, ctxs=[5], n_steps=1, capacity=1)  # prunes
    assert len(acct._ring) <= accounting.RING_SECONDS + 9
    rows = acct.ring(last=10)
    assert len(rows) == 10
    assert [r["t_rel_s"] for r in rows] == \
        sorted(r["t_rel_s"] for r in rows)


def test_counters_mirror_totals():
    before = METRICS.get("tpu_model_useful_tokens_total",
                         '{kind="decode"}')
    flops0 = METRICS.get("tpu_model_model_flops_total")
    acct = make_acct()
    acct.on_decode(0.01, ctxs=[5, 6], n_steps=3, capacity=4)
    assert METRICS.get("tpu_model_useful_tokens_total",
                       '{kind="decode"}') == before + 6
    assert METRICS.get("tpu_model_model_flops_total") > flops0


def test_kill_switch_returns_shared_null(monkeypatch):
    monkeypatch.setattr(accounting, "ACCOUNTING_ENABLED", False)
    acct = make_accounting(TINY)
    assert acct is NULL_ACCOUNTING and acct.enabled is False
    acct.on_decode(0.01, ctxs=[5], n_steps=1, capacity=1)   # inert
    assert acct.snapshot() == {"enabled": False}
    assert acct.ring() == []
    monkeypatch.setattr(accounting, "ACCOUNTING_ENABLED", True)
    assert make_accounting(TINY).enabled is True


def test_accounting_without_cfg_is_safe():
    acct = UtilizationAccounting(None, peak_flops=1e12)
    acct.on_decode(0.01, ctxs=[5], n_steps=1, capacity=1)
    acct.on_prefill(0.01, 0, 4, 16)
    assert acct.model_flops == 0.0


# -- recompile detector (engine-level) ---------------------------------

def test_recompile_detector_fires_once_per_unwarmed_signature():
    from ollama_operator_tpu.runtime.trace import FLIGHT

    from test_scheduler import GREEDY, make_stack
    cfg, params, eng, sched = make_stack(slots=2)
    sched.shutdown()
    rc_metric0 = METRICS.get("tpu_model_recompiles_total",
                             '{kind="decode"}')
    seq0 = FLIGHT.seq
    prompt = np.array([1, 2, 3], np.int32)
    assert sum(eng.recompiles.values()) == 0
    eng.admit(0, prompt)
    assert eng.recompiles["admit"] == 1
    eng.release(0)
    eng.admit(0, prompt)                 # same bucket → cached executable
    assert eng.recompiles["admit"] == 1
    n_dec0 = eng.recompiles["decode"]
    eng.decode_n()
    assert eng.recompiles["decode"] == n_dec0 + 1
    assert METRICS.get("tpu_model_recompiles_total",
                       '{kind="decode"}') == rc_metric0 + n_dec0 + 1
    evs = [e for e in FLIGHT.snapshot()
           if e["seq"] > seq0 and e["kind"] == "recompile"]
    assert any(e["program"] == "admit" for e in evs)
    assert any(e["program"] == "decode" for e in evs)
    eng.release(0)


def test_recompile_detector_silent_after_aot_warm():
    from test_scheduler import make_stack
    cfg, params, eng, sched = make_stack(slots=2)
    sched.shutdown()
    eng.warm_buckets()
    assert sum(eng.recompiles.values()) == 0, \
        "AOT warm must register signatures, not count them"
    eng.admit(0, np.array([1, 2, 3], np.int32))
    eng.decode_n()
    assert sum(eng.recompiles.values()) == 0, \
        "warmed signatures must not count as mid-serving recompiles"
    eng.release(0)


def test_scheduler_surfaces_utilization_stats():
    from test_scheduler import GREEDY, make_stack
    cfg, params, eng, sched = make_stack(slots=2)
    try:
        r = sched.submit(np.array([1, 2, 3], np.int32), GREEDY,
                         max_tokens=5)
        assert len(list(r.tokens())) == 5
        out = sched.utilization_stats()
        assert out["enabled"] is True
        assert out["totals"]["useful_tokens"]["decode"] >= 5
        assert out["totals"]["useful_tokens"]["prefill"] >= 3
        assert "recompiles" in out and isinstance(out["recompiles"], dict)
        assert out["breakdown"]["wall_s"] > 0
    finally:
        sched.shutdown()
