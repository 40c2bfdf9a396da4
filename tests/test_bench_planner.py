"""ISSUE 3 acceptance: the threshold-batched planner must beat the legacy
scan by >= 10x on the 24-server x 30-layer x B=64 ``exhaustive_joint``
instance, result-for-result identical, and the wall-clocks must be tracked
in the repo-root BENCH_planner.json."""

import json
import os
import time

import pytest

from benchmarks.bench_planner import JSON_PATH, acceptance_instance
from repro.core import exhaustive_joint, solve_msp

B = 64
B_STEP = 16          # 4 micro-batch sizes: keeps the scan side test-sized
                     # (measured ~47x vs the >= 10x bar, so CI timing noise
                     # has generous headroom)


def test_batched_exhaustive_joint_10x_faster_than_scan():
    prof, net = acceptance_instance()
    assert prof.num_layers == 30 and net.num_servers == 24
    t0 = time.perf_counter()
    p_bat = exhaustive_joint(prof, net, B, b_step=B_STEP, solver="batched")
    t_bat = time.perf_counter() - t0
    t0 = time.perf_counter()
    p_scan = exhaustive_joint(prof, net, B, b_step=B_STEP, solver="scan")
    t_scan = time.perf_counter() - t0
    # result-for-result identical plans ...
    assert p_bat.solution == p_scan.solution
    assert p_bat.b == p_scan.b and p_bat.L_t == p_scan.L_t
    # ... at >= 10x the speed
    assert t_scan / t_bat >= 10.0, (t_scan, t_bat)


def test_batched_solver_does_fewer_sweeps():
    prof, net = acceptance_instance()
    r_scan = solve_msp(prof, net, 8, B, solver="scan")
    r_bat = solve_msp(prof, net, 8, B, solver="batched")
    assert r_bat.thresholds_scanned <= 5
    assert r_scan.thresholds_scanned > r_bat.thresholds_scanned


def test_bench_planner_json_tracks_acceptance():
    """The perf trajectory file exists, and the recorded acceptance run
    meets the >= 10x bar with identical plans."""
    assert os.path.isfile(JSON_PATH), "run `make bench-planner` to record"
    with open(JSON_PATH) as f:
        data = json.load(f)
    acc = data["acceptance"]
    assert (acc["servers"], acc["layers"], acc["B"]) == (24, 30, 64)
    assert acc["identical_plans"] is True
    assert acc["speedup"] >= 10.0


def test_bench_planner_json_tracks_fleet_bars():
    """Fleet acceptance: the recorded fleet section covers the full
    64-size ``solve_many`` sweep and shows incremental ``Planner.update``
    replans >= 5x a cold re-solve, plan-identical."""
    assert os.path.isfile(JSON_PATH), "run `make bench-planner` to record"
    with open(JSON_PATH) as f:
        data = json.load(f)
    fleet = data["fleet"]
    sm = fleet["solve_many"]
    assert (sm["servers"], sm["layers"], sm["B"]) == (24, 30, 64)
    assert sm["num_bs"] == 64
    inc = fleet["incremental"]
    assert inc["identical_plans"] is True
    assert inc["speedup"] >= 5.0
