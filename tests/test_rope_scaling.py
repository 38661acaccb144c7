"""RoPE context-extension scaling (ops/rope.scaled_inv_freq).

The reference serves long-context models through llama.cpp inside the
delegated image (/root/reference/pkg/model/pod.go:11), which honors GGUF
``rope.scaling.*`` metadata (linear / YaRN) and the pre-baked
``rope_freqs.weight`` factor tensor of llama3.1-family conversions. These
tests pin our static per-frequency rescale against transformers'
ROPE_INIT_FUNCTIONS (the ecosystem-canonical math, matching llama.cpp) and
cover the GGUF metadata → ModelConfig plumbing.
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from ollama_operator_tpu.gguf import writer as W
from ollama_operator_tpu.gguf.transcode import config_from_gguf
from ollama_operator_tpu.gguf.reader import GGUFFile
from ollama_operator_tpu.models.config import ModelConfig, get_config
from ollama_operator_tpu.ops.rope import (rope_angles, rope_angles_cfg,
                                          scaled_inv_freq)


def test_linear_matches_legacy_position_division():
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    ref_cos, ref_sin = rope_angles(pos, 64, 10000.0, scaling=4.0)
    cfg = ModelConfig(rope_scaling_type="linear", rope_scaling=4.0,
                      head_dim=64).validate()
    got_cos, got_sin = rope_angles_cfg(pos, cfg)
    np.testing.assert_allclose(np.asarray(got_cos), np.asarray(ref_cos),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_sin), np.asarray(ref_sin),
                               rtol=1e-6, atol=1e-6)


def test_none_type_honors_legacy_bare_factor():
    # back-compat: old configs carried rope_scaling as a bare linear factor
    # with no type field
    f_lin, m_lin = scaled_inv_freq(32, 10000.0, scaling_type="linear",
                                   factor=2.0)
    f_leg, m_leg = scaled_inv_freq(32, 10000.0, scaling_type="none",
                                   factor=2.0)
    assert f_lin == f_leg and m_lin == m_leg == 1.0


def test_freq_factors_divide_and_win_over_scheme():
    ff = tuple(float(2 + i) for i in range(16))
    base, _ = scaled_inv_freq(32, 10000.0)
    got, m = scaled_inv_freq(32, 10000.0, scaling_type="linear", factor=8.0,
                             freq_factors=ff)
    assert m == 1.0
    np.testing.assert_allclose(np.array(got),
                               np.array(base) / np.array(ff), rtol=1e-6)


def _hf_rope(rope_scaling: dict, head_dim=32, theta=10000.0, max_pos=4096):
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS
    cfg = transformers.LlamaConfig(
        hidden_size=head_dim * 4, num_attention_heads=4,
        max_position_embeddings=max_pos, rope_theta=theta,
        rope_scaling=dict(rope_scaling))
    fn = ROPE_INIT_FUNCTIONS[rope_scaling["rope_type"]]
    inv_freq, attention_scaling = fn(cfg, device=torch.device("cpu"))
    return np.asarray(inv_freq, np.float64), float(attention_scaling)


def test_llama3_matches_transformers():
    spec = {"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 8192}
    ref, ref_m = _hf_rope(spec, head_dim=128, theta=500000.0)
    got, m = scaled_inv_freq(128, 500000.0, scaling_type="llama3",
                             factor=8.0, orig_ctx=8192,
                             low_freq_factor=1.0, high_freq_factor=4.0)
    assert m == ref_m == 1.0
    np.testing.assert_allclose(np.array(got), ref, rtol=1e-6)


def test_llama3_covers_all_three_bands():
    # orig_ctx 32, theta 1e4, hd 16: dim 0 keeps, dim 1 blends, rest scale
    got, _ = scaled_inv_freq(16, 10000.0, scaling_type="llama3", factor=4.0,
                             orig_ctx=32, low_freq_factor=1.0,
                             high_freq_factor=4.0)
    base, _ = scaled_inv_freq(16, 10000.0)
    ratio = np.array(base) / np.array(got)
    assert ratio[0] == pytest.approx(1.0)
    assert 1.0 < ratio[1] < 4.0
    np.testing.assert_allclose(ratio[2:], 4.0, rtol=1e-6)


def test_yarn_matches_transformers():
    spec = {"rope_type": "yarn", "factor": 4.0,
            "original_max_position_embeddings": 2048}
    ref, ref_m = _hf_rope(spec, head_dim=64, theta=10000.0, max_pos=8192)
    got, m = scaled_inv_freq(64, 10000.0, scaling_type="yarn", factor=4.0,
                             orig_ctx=2048)
    assert m == pytest.approx(ref_m)     # 0.1*ln(4)+1
    np.testing.assert_allclose(np.array(got), ref, rtol=1e-6)


def test_yarn_explicit_attention_factor():
    spec = {"rope_type": "yarn", "factor": 4.0, "attention_factor": 1.5,
            "original_max_position_embeddings": 2048}
    _, ref_m = _hf_rope(spec, head_dim=64, theta=10000.0, max_pos=8192)
    _, m = scaled_inv_freq(64, 10000.0, scaling_type="yarn", factor=4.0,
                           orig_ctx=2048, attn_factor=1.5)
    assert m == pytest.approx(ref_m) == pytest.approx(1.5)


def test_yarns_two_magnitudes_at_their_defaults_are_the_llama_convention():
    """The DeepSeek-V3 convention's fields (ModelConfig.rope_yarn_mscale /
    rope_yarn_mscale_all_dim) at 0, their default: the GGUF families' YaRN
    keeps its cos / sin magnitude (0.1 ln(factor) + 1, or the explicit
    attn_factor) and its frequencies bit for bit, and no softmax factor."""
    from ollama_operator_tpu.ops.rope import yarn_softmax_factor
    for kw, want_m in ((dict(), 0.1 * np.log(4.0) + 1.0),
                       (dict(attn_factor=1.5), 1.5)):
        old = scaled_inv_freq(64, 10000.0, scaling_type="yarn", factor=4.0,
                              orig_ctx=2048, **kw)
        new = scaled_inv_freq(64, 10000.0, scaling_type="yarn", factor=4.0,
                              orig_ctx=2048, yarn_mscale=0.0,
                              yarn_mscale_all_dim=0.0, **kw)
        assert old == new and old[1] == want_m
    cfg = ModelConfig(rope_scaling_type="yarn", rope_scaling=4.0,
                      rope_orig_ctx=2048).validate()
    assert (cfg.rope_yarn_mscale, cfg.rope_yarn_mscale_all_dim) == (0.0, 0.0)
    assert yarn_softmax_factor(cfg) == 1.0
    pos = jnp.arange(0, 9000, 977, dtype=jnp.int32)[None]
    cos, sin = rope_angles_cfg(pos, cfg)
    inv, m = scaled_inv_freq(128, 10000.0, scaling_type="yarn", factor=4.0,
                             orig_ctx=2048)
    c0, s0 = rope_angles(pos, 128, 10000.0, inv_freq=inv, mscale=m)
    assert np.array_equal(cos, c0) and np.array_equal(sin, s0)
    # stated, the two move the magnitude to the scores: cos / sin by their
    # ratio, the softmax by the second one squared
    _, m = scaled_inv_freq(64, 10000.0, scaling_type="yarn", factor=4.0,
                           orig_ctx=2048, yarn_mscale=1.0,
                           yarn_mscale_all_dim=1.0)
    assert m == 1.0
    stated = ModelConfig(rope_scaling_type="yarn", rope_scaling=4.0,
                         rope_orig_ctx=2048, rope_yarn_mscale=1.0,
                         rope_yarn_mscale_all_dim=1.0)
    assert yarn_softmax_factor(stated) == pytest.approx(
        (0.1 * np.log(4.0) + 1.0) ** 2)


@pytest.mark.parametrize("fields", [dict(rope_yarn_mscale=1.0),
                                    dict(rope_yarn_mscale_all_dim=1.0),
                                    dict(rope_yarn_mscale=1.0,
                                         rope_yarn_mscale_all_dim=1.0)],
                         ids=lambda f: "+".join(f))
def test_yarns_two_magnitudes_are_refused_outside_latent_attention(fields):
    """The softmax's share of mscale / mscale_all_dim is applied where
    latent attention scales its scores and nowhere else: an ordinary yarn
    model that states either would run at a wrong attention magnitude with
    nothing said, so ``validate`` refuses it; a latent stack takes them."""
    from ollama_operator_tpu.models.config import PRESETS
    with pytest.raises(AssertionError, match="latent attention alone"):
        ModelConfig(rope_scaling_type="yarn", rope_scaling=4.0,
                    rope_orig_ctx=2048, **fields).validate()
    with pytest.raises(AssertionError, match="are yarn's"):
        ModelConfig(**fields).validate()
    dataclasses.replace(PRESETS["tiny-kimi-k2"], **fields).validate()


def test_presets_llama31_32_scaled():
    for name, factor in (("llama3.1", 8.0), ("llama3.2:1b", 32.0),
                         ("llama3.2:3b", 32.0)):
        cfg = get_config(name)
        assert cfg.rope_scaling_type == "llama3"
        assert cfg.rope_scaling == factor
        assert cfg.rope_orig_ctx == 8192
        assert cfg.max_seq_len == 131072
        # the scheme actually moves the low-frequency rates
        got, _ = scaled_inv_freq(cfg.rotary_dim, cfg.rope_theta,
                                 scaling_type=cfg.rope_scaling_type,
                                 factor=cfg.rope_scaling,
                                 orig_ctx=cfg.rope_orig_ctx)
        base, _ = scaled_inv_freq(cfg.rotary_dim, cfg.rope_theta)
        assert got[-1] == pytest.approx(base[-1] / factor, rel=1e-6)
        assert got[0] == pytest.approx(base[0], rel=1e-6)


# ---------------------------------------------------------------------------
# GGUF metadata plumbing
# ---------------------------------------------------------------------------

def _tiny_gguf(tmp_path, extra_meta=(), extra_tensors=(), name="m.gguf"):
    path = str(tmp_path / name)
    w = W.GGUFWriter(path)
    w.add_meta("general.architecture", "llama")
    w.add_meta("llama.block_count", 1)
    w.add_meta("llama.embedding_length", 16)
    w.add_meta("llama.attention.head_count", 2)
    w.add_meta("llama.attention.head_count_kv", 2)
    w.add_meta("llama.feed_forward_length", 32)
    w.add_meta("llama.context_length", 256)
    w.add_meta("tokenizer.ggml.model", "llama")
    w.add_meta("tokenizer.ggml.tokens", [f"t{i}" for i in range(8)])
    w.add_meta("tokenizer.ggml.scores", [0.0] * 8)
    w.add_meta("tokenizer.ggml.token_type", [1] * 8)
    for k, v in extra_meta:
        w.add_meta(k, v)
    # minimal tensor so tie detection has something to look at
    w.add_tensor_f32("output.weight", np.zeros((8, 16), np.float32))
    for name, arr in extra_tensors:
        w.add_tensor_f32(name, arr)
    w.write()
    return path


def test_gguf_yarn_metadata(tmp_path):
    path = _tiny_gguf(tmp_path, extra_meta=[
        ("llama.rope.scaling.type", "yarn"),
        ("llama.rope.scaling.factor", 4.0),
        ("llama.rope.scaling.original_context_length", 64),
        ("llama.rope.scaling.attn_factor", 1.2)])
    with GGUFFile(path) as f:
        cfg = config_from_gguf(f)
    assert cfg.rope_scaling_type == "yarn"
    assert cfg.rope_scaling == 4.0
    assert cfg.rope_orig_ctx == 64
    assert cfg.rope_attn_factor == pytest.approx(1.2)


def test_gguf_yarn_missing_orig_ctx_falls_back(tmp_path):
    path = _tiny_gguf(tmp_path, extra_meta=[
        ("llama.rope.scaling.type", "yarn"),
        ("llama.rope.scaling.factor", 4.0)])
    with GGUFFile(path) as f:
        cfg = config_from_gguf(f)
    assert cfg.rope_orig_ctx == 64     # context_length 256 / factor 4


def test_gguf_legacy_scale_linear(tmp_path):
    path = _tiny_gguf(tmp_path, extra_meta=[
        ("llama.rope.scale_linear", 2.0)])
    with GGUFFile(path) as f:
        cfg = config_from_gguf(f)
    assert cfg.rope_scaling_type == "linear"
    assert cfg.rope_scaling == 2.0


def test_gguf_rope_freqs_tensor(tmp_path):
    ff = np.linspace(1.0, 8.0, 4).astype(np.float32)
    path = _tiny_gguf(tmp_path, extra_tensors=[("rope_freqs.weight", ff)])
    with GGUFFile(path) as f:
        cfg = config_from_gguf(f)
    assert cfg.rope_freq_factors == tuple(float(x) for x in ff)
    # the factors reach the angle computation
    got, _ = scaled_inv_freq(cfg.rotary_dim, cfg.rope_theta,
                             freq_factors=cfg.rope_freq_factors)
    base, _ = scaled_inv_freq(cfg.rotary_dim, cfg.rope_theta)
    np.testing.assert_allclose(np.array(got), np.array(base) / ff,
                               rtol=1e-6)


def test_gguf_unsupported_scaling_type_fails_loudly(tmp_path):
    # a genuinely unknown scheme is rejected outright
    path = _tiny_gguf(tmp_path, extra_meta=[
        ("llama.rope.scaling.type", "ntk-parts-v9")])
    with GGUFFile(path) as f:
        with pytest.raises(NotImplementedError):
            config_from_gguf(f)
    # longrope is supported (phi3 family, round 5) but ONLY via its
    # rope_factors_* tensors — declaring the type without them must fail
    # loudly, not serve unscaled rope
    path = _tiny_gguf(tmp_path, extra_meta=[
        ("llama.rope.scaling.type", "longrope")], name="lr.gguf")
    with GGUFFile(path) as f:
        with pytest.raises(ValueError, match="rope_factors"):
            config_from_gguf(f)


def test_config_roundtrips_freq_factors_as_json():
    # gguf/store.py meta is JSON: tuples come back as lists; validate()
    # re-coerces so the config stays hashable for jit static args
    import json
    cfg = ModelConfig(rope_freq_factors=(1.0, 2.0, 3.0, 4.0, 5.0, 6.0,
                                         7.0, 8.0),
                      head_dim=16).validate()
    back = ModelConfig(**json.loads(json.dumps(cfg.__dict__))).validate()
    assert back.rope_freq_factors == cfg.rope_freq_factors
    hash(back)   # must stay usable as a jit static
