"""The yardstick's own checks of the ``kimi-k2.7-code`` configuration: what its
file holds the program's preset to, its work arithmetic by hand, and the
reader that takes a kernel's device time by the operation's name. Run by hand
with the others: ``python -m pytest benchmark/tests -q`` from the root of the
repo (tier-1 collects ``tests/`` only; ``tests/test_kimi_k2.py`` holds the
program to the reference)."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import kernel_spans, run, server_child, trace_spans, work  # noqa: E402

CONF = os.path.join(BENCH, "configs", "kimi-k2.7-code.json")


def test_the_file_holds_the_preset_and_the_catalogs_widths():
    conf = server_child.load_conf(CONF, False)
    cfg = server_child.model_config(conf, False)      # raises where they differ
    held = dict(conf["holds"])
    assert cfg.index_topk == conf["index_topk"] == 0
    assert {"rope_scaling_type", "rope_scaling", "rope_orig_ctx",
            "rope_yarn_mscale", "rope_yarn_mscale_all_dim"} <= set(held)
    rs = conf["rope_scaling"]
    assert (conf["rope_factor"], conf["rope_original_max_position_embeddings"],
            conf["rope_beta_fast"], conf["rope_beta_slow"], conf["rope_mscale"],
            conf["rope_mscale_all_dim"], conf["rope_scaling_type"]) == (
        rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"], rs["type"])
    assert set(conf["reduced"]) == set(conf["reduced_why"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    # the rehearsal's toy is held by the same pairs
    toy = server_child.load_conf(CONF, True)
    tcfg = server_child.model_config(toy, True)
    assert (tcfg.kv_latent_dim, tcfg.index_topk, tcfg.rope_scaling_type) == (
        32, 0, "yarn")


def test_work_by_hand():
    c = work.load_conf(CONF)
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
            + 64 * 128 * 7168)
    expert = 3 * 7168 * 2048
    fixed = (8 * attn + 3 * 7168 * 18432 + 7 * (expert + 7168 * 384)
             + 7168 * 20480)
    assert (attn, expert) == (101_122_048, 44_040_192)
    assert work.layer_matmul_params(c) == (fixed - 7168 * 20480
                                           + 7 * 12 * expert) / 8
    touched = 12 * (1 - (1 - 8 / 384) ** 64)
    assert work.weight_bytes_step(c, 64, "bfloat16") == pytest.approx(
        2 * (fixed + 7 * touched * expert))
    assert work.kv_bytes_per_token(c, "int8") == 8 * (640 + 8) == 5184
    assert work.attn_flops_per_pair(c) == 8 * 64 * (576 + 512) * 2
    step = work.decode_step(c, 64, 60_000, "bfloat16", "int8")
    assert step["bytes"] == pytest.approx(
        2 * (fixed + 7 * touched * expert) + 60_064 * 5184 + 64 * 20480 * 4)
    assert step["flops"] == pytest.approx(
        64 * 2 * (fixed + 7 * 0.25 * expert) + 60_000 * 8 * 139_264)
    own = work.own(c, "latent_bytes_per_live_position")
    assert own(c, "int8") == 5184
    assert work.own(c, "latent_flops_per_live_position")(c) == 8 * 139_264
    assert work.own(c, "index_bytes_step") is None


def test_a_kernels_time_is_read_by_its_name(tmp_path, monkeypatch):
    meta = {1: ("jit__decode_n(7)", ""),
            2: ("%latent_decode.5 = bf16[] custom-call()",
                "jit(_decode_n)/while/body/attn.core/latent_decode/pallas_call:"),
            3: ("%fusion.3 = f32[] fusion()",
                "jit(_decode_n)/attn.core/dot_general")}
    ops = []
    for t0 in (0, 2000):
        ops += [(t0 + 100, t0 + 500, 2), (t0 + 500, t0 + 900, 3)]
    planes = [{"name": "/device:TPU:0", "meta": meta, "lines": [
        {"name": "XLA Modules", "events": [(0, 1000, 1), (2000, 3000, 1)]},
        {"name": "XLA Ops", "events": ops}]}]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(trace_spans, "find_trace", lambda w=None: str(path))
    monkeypatch.setattr(trace_spans, "reduce",
                        lambda w=None: trace_spans.reduce_planes(planes))
    monkeypatch.setattr(trace_spans, "read_planes", lambda p: planes)
    kernel_spans._CACHE.clear()
    assert kernel_spans.step_seconds(2, "latent_decode") == pytest.approx(
        200e-12)
    assert kernel_spans.step_seconds(2, "ring_decode") is None
    ctx = types.SimpleNamespace(
        conf=work.load_conf(CONF), notes={}, live_tokens=1000.0,
        resolved={"decode_chunk": 2, "kv_dtype": "int8"},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    share = run.layer_reader("latent_attn_roofline").read(ctx)
    assert share == pytest.approx(100 * 1000 * 5184 / 819e9 / 200e-12)
    ctx.peaks = None                  # a rehearsal has no peaks: nothing
    assert run.layer_reader("latent_attn_roofline").read(ctx) is None
