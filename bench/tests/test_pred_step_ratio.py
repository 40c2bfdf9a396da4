"""``planner.pred_step_ratio`` prices the cell from the family's planner
profile alone: the depth is the profile's length less the embedding and
the head, not a key of the configuration.

Checked on made-up records: a toy profile with a configuration that names
no depth, worked against ``latency.total_latency`` with the cuts by hand,
and both accepted configurations read as with their ``num_hidden_layers``."""

import json
import os
import sys
import types

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402

ARCH = spec.family_module("arch", "dense_decoder")
READ = spec.metric_reader("planner.pred_step_ratio")


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _record(profile, config, stages, batch, q, steps_ms=(800.0, 820.0, 810.0)):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(config=config), stages=stages,
        chips=stages, batch=batch, q=q, planner_profile=profile,
        steps_ms=list(steps_ms))


def _predicted(profile, cuts, stages, batch, q):
    from repro.core import latency
    from repro.core.latency import SplitSolution
    from repro.core.network import tpu_stage_network

    sol = SplitSolution(cuts=cuts, placement=tuple(range(stages)))
    return latency.total_latency(profile, tpu_stage_network(stages, 1), sol,
                                 batch // q, batch)


def test_depth_comes_from_the_profile():
    profile = ARCH.planner_profile(ARCH.tiny(_config("qwen3-0.6b")), 32)
    assert len(profile.fp_work) == 4 + 2      # embedding, 4 layers, head
    rec = _record(profile, {}, stages=2, batch=8, q=4)
    # 4 layers over 2 stages: embedding + 2 layers, then 2 layers + head
    assert READ(rec) == _predicted(profile, (3, 6), 2, 8, 4) / 0.810


@pytest.mark.parametrize("config, stages, batch, q", [
    ("qwen3-0.6b", 1, 16, 8), ("qwen1.5-4b", 4, 8, 4)])
def test_the_cells_read_as_with_their_configured_depth(config, stages, batch,
                                                       q):
    c = _config(config)
    profile = ARCH.planner_profile(c, 1024)
    layers, per = c["num_hidden_layers"], c["num_hidden_layers"] // stages
    cuts = tuple(1 + per * (k + 1) for k in range(stages - 1)) + (layers + 2,)
    rec = _record(profile, c, stages, batch, q)
    assert READ(rec) == pytest.approx(
        _predicted(profile, cuts, stages, batch, q) / 0.810, rel=1e-12)
