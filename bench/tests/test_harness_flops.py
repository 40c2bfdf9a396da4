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


def test_v5e_peaks():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16 * 2**30


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        spec.peaks("TPU v9 imaginary")
