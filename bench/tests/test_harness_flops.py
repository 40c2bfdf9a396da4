"""The operation count and the peak table that the metrics divide by."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("config,seq,expected", [
    ("qwen3-0.6b", 1024, 3.93e9),
    ("qwen3-0.6b", 2048, 4.28e9),
    ("qwen1.5-4b", 1024, 10.41e9),
])
def test_flops_per_token(config, seq, expected):
    flops = spec.family_module("flops", "dense_decoder")
    got = flops.flops_per_token(_config(config), seq)
    assert abs(got - expected) <= 0.01 * expected


def test_flops_count_six_per_matmul_parameter_plus_causal_attention():
    flops = spec.family_module("flops", "dense_decoder")
    c = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
         "intermediate_size": 16, "head_dim": 4, "num_hidden_layers": 3,
         "vocab_size": 10}
    per_layer = 8 * (2 + 2) * 4 + 2 * 4 * 8 + 3 * 8 * 16
    matmul_params = 3 * per_layer + 8 * 10
    attention = 3 * 2 * 2 * 2 * 4 * (6 / 2)
    assert flops.flops_per_token(c, 6) == 6 * matmul_params + 3 * attention


def _flops_per_token_as_one_formula(c, seq_len):
    """The count as one formula, before it was split into terms."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    kv, ff = int(c["num_key_value_heads"]), int(c["intermediate_size"])
    hd = int(c.get("head_dim") or d // h)
    layers, vocab = int(c["num_hidden_layers"]), int(c["vocab_size"])
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff
    matmul_params = layers * per_layer + d * vocab
    attention = layers * 2 * 2 * h * hd * (seq_len / 2)
    return 6.0 * matmul_params + 3.0 * attention


@pytest.mark.parametrize("seq", [1024, 2048])
@pytest.mark.parametrize("config", ["qwen3-0.6b", "qwen1.5-4b"])
def test_terms_sum_to_the_whole_count_to_the_bit(config, seq):
    flops = spec.family_module("flops", "dense_decoder")
    c = _config(config)
    terms = flops.terms(c, seq)
    assert set(terms) == {"matmul", "attention"}
    assert set(flops.KERNEL_SCOPES) <= set(terms)
    assert flops.flops_per_token(c, seq) == _flops_per_token_as_one_formula(
        c, seq)
    assert sum(terms.values()) == flops.flops_per_token(c, seq)


@pytest.mark.parametrize("config,matmul_share", [("qwen3-0.6b", 0.91031),
                                                 ("qwen1.5-4b", 0.96979)])
def test_matmul_share_of_the_count_at_the_cells_length(config, matmul_share):
    """The factor by which ``kernels.matmul_roofline`` falls where attention
    runs as the flash kernels, at S=1024."""
    flops = spec.family_module("flops", "dense_decoder")
    terms = flops.terms(_config(config), 1024)
    assert terms["matmul"] / sum(terms.values()) == pytest.approx(
        matmul_share, abs=5e-6)


def test_v5e_peaks():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16 * 2**30


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        spec.peaks("TPU v9 imaginary")
