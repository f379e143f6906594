"""RoPE frequency tables must match the HF reference formulas exactly.

Round-1 advisor finding: self-consistency tests (rotation preserves norm) hold
for ANY frequency table, so they missed a doubled exponent and an inverted
YaRN ramp. These tests pin our tables to transformers' rope-utils output.
"""
import numpy as np
import pytest
import torch

from transformers import PretrainedConfig
from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

from localai_tpu.ops.rope import RopeConfig, rope_freqs


def _hf_config(head_dim, base, max_pos, rope_scaling=None):
    cfg = PretrainedConfig()
    cfg.head_dim = head_dim
    cfg.hidden_size = head_dim * 4
    cfg.num_attention_heads = 4
    cfg.rope_theta = base
    cfg.max_position_embeddings = max_pos
    cfg.rope_scaling = rope_scaling
    # transformers >=4.54 reads rope params through rope_parameters
    rp = {"rope_theta": base, "rope_type": "default"}
    if rope_scaling:
        rp.update(rope_scaling)
    cfg.rope_parameters = rp
    return cfg


def _hf_freqs(rope_type, head_dim, base, max_pos, rope_scaling=None):
    cfg = _hf_config(head_dim, base, max_pos, rope_scaling)
    inv_freq, attn_scale = ROPE_INIT_FUNCTIONS[rope_type](cfg, device="cpu")
    return np.asarray(inv_freq.to(torch.float64)), float(attn_scale)


def test_default_matches_hf():
    ours, _ = rope_freqs(RopeConfig(head_dim=128, base=500000.0))
    theirs, scale = _hf_freqs("default", 128, 500000.0, 8192)
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-6)
    assert scale == 1.0


def test_linear_matches_hf():
    ours, _ = rope_freqs(
        RopeConfig(head_dim=64, base=10000.0, scaling="linear", scale_factor=4.0)
    )
    theirs, _ = _hf_freqs(
        "linear", 64, 10000.0, 4096, {"rope_type": "linear", "factor": 4.0}
    )
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-6)


def test_llama3_matches_hf():
    scaling = {
        "rope_type": "llama3",
        "factor": 8.0,
        "low_freq_factor": 1.0,
        "high_freq_factor": 4.0,
        "original_max_position_embeddings": 8192,
    }
    ours, _ = rope_freqs(
        RopeConfig(
            head_dim=128, base=500000.0, scaling="llama3", scale_factor=8.0,
            original_max_position=8192, low_freq_factor=1.0, high_freq_factor=4.0,
        )
    )
    theirs, _ = _hf_freqs("llama3", 128, 500000.0, 8192, scaling)
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-6)


def test_yarn_long_context_clamp_matches_hf():
    """original_max_position large enough that the upper correction bound
    exceeds head_dim//2 — HF clamps to head_dim-1, a round-1 divergence."""
    scaling = {
        "rope_type": "yarn",
        "factor": 4.0,
        "beta_fast": 32.0,
        "beta_slow": 1.0,
        "original_max_position_embeddings": 131072,
    }
    ours, _ = rope_freqs(
        RopeConfig(
            head_dim=128, base=10000.0, scaling="yarn", scale_factor=4.0,
            original_max_position=131072, beta_fast=32.0, beta_slow=1.0,
        )
    )
    theirs, _ = _hf_freqs("yarn", 128, 10000.0, 131072, scaling)
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-5)


def test_yarn_explicit_attention_factor_used_verbatim():
    scaling = {
        "rope_type": "yarn",
        "factor": 4.0,
        "beta_fast": 32.0,
        "beta_slow": 1.0,
        "attention_factor": 0.9,
        "original_max_position_embeddings": 4096,
    }
    _, mscale = rope_freqs(
        RopeConfig(
            head_dim=128, base=10000.0, scaling="yarn", scale_factor=4.0,
            original_max_position=4096, attn_factor=0.9,
        )
    )
    _, hf_mscale = _hf_freqs("yarn", 128, 10000.0, 4096, scaling)
    assert mscale == pytest.approx(hf_mscale) == 0.9


def test_yarn_matches_hf():
    scaling = {
        "rope_type": "yarn",
        "factor": 4.0,
        "beta_fast": 32.0,
        "beta_slow": 1.0,
        "original_max_position_embeddings": 4096,
    }
    ours, mscale = rope_freqs(
        RopeConfig(
            head_dim=128, base=10000.0, scaling="yarn", scale_factor=4.0,
            original_max_position=4096, beta_fast=32.0, beta_slow=1.0,
        )
    )
    theirs, hf_mscale = _hf_freqs("yarn", 128, 10000.0, 4096, scaling)
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-5)
    assert mscale == pytest.approx(hf_mscale, rel=1e-6)


def test_two_rope_configs_from_rope_parameters_keyed_by_layer_type(tmp_path):
    """One config.json whose rope_parameters is keyed by layer type (window
    layers plain, full layers YaRN: Mellum2's published values) gives two
    RopeConfigs, and each matches HF's table for its own section."""
    import json

    from localai_tpu.engine.loader import load_config
    from localai_tpu.models.llama import FULL, WINDOW

    full = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    window = {"rope_type": "default", "rope_theta": 500000}
    (tmp_path / "config.json").write_text(json.dumps({
        "model_type": "mellum", "vocab_size": 64, "hidden_size": 512,
        "intermediate_size": 64, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
        "max_position_embeddings": 131072, "sliding_window": 1024,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "mlp_layer_types": ["sparse"] * 4, "num_experts": 8,
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "rope_parameters": {"full_attention": full,
                            "sliding_attention": window}}))
    cfg = load_config(str(tmp_path))
    assert cfg.rope_of(FULL) != cfg.rope_of(WINDOW)
    ours, mscale = rope_freqs(cfg.rope_of(FULL))
    theirs, hf_mscale = _hf_freqs("yarn", 128, 500000.0, 131072, full)
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-5)
    assert mscale == pytest.approx(hf_mscale) == 1.2772588722239782
    ours, mscale = rope_freqs(cfg.rope_of(WINDOW))
    theirs, _ = _hf_freqs("default", 128, 500000.0, 131072)
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-6)
    assert mscale == 1.0
