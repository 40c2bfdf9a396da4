"""``kernels.flash_roofline``: the attention core's needed work over peak
times the device time under ``kernels.flash``, summed over devices.

Checked on made-up reductions: silent without the scope (a program whose
attention takes another path, or one that names no such scope), and the
share against the attention term of ``bench/flops`` worked by hand."""

import json
import os
import sys
import types

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)

from harness import scopes, spec  # noqa: E402

NAME = "kernels.flash_roofline"


def _record(config, steps=2, batch=16, seq=1024):
    cell = types.SimpleNamespace(config=config)
    return types.SimpleNamespace(
        cell=cell, steps_traced=steps, tokens_traced=steps * batch * seq,
        seq=seq, peaks={"bf16_flops_per_s": 197e12})


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_silent_without_the_kernels_scope(monkeypatch):
    read = spec.metric_reader(NAME)
    rec = _record(_config("qwen3-0.6b"))
    for per_device in ([], [{}], [{"model.attention": 0.5}]):
        monkeypatch.setattr(scopes, "seconds", lambda: per_device)
        assert read(rec) is None
    monkeypatch.setattr(scopes, "seconds",
                        lambda: [{"kernels.flash": 0.5}])
    assert read(_record(_config("qwen3-0.6b"), steps=0)) is None


@pytest.mark.parametrize("config, heads", [("qwen3-0.6b", 16),
                                           ("qwen1.5-4b", 20)])
def test_needed_attention_work_over_peak_times_summed_time(
        config, heads, monkeypatch):
    c = _config(config)
    layers = int(c["num_hidden_layers"])
    monkeypatch.setattr(scopes, "seconds", lambda: [
        {"kernels.flash": 0.25, "model.attention": 0.3},
        {"kernels.flash": 0.15}])
    rec = _record(c, steps=3, batch=8)
    work = 3 * layers * 4 * heads * 128 * 512 * (3 * 8 * 1024)
    assert spec.metric_reader(NAME)(rec) == pytest.approx(
        100 * work / (197e12 * 0.40))
