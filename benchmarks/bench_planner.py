"""Planner scaling benchmark (ISSUE 3 grid + ISSUE 9 fleet).

Grid: nodes x layers x B for ``solve_msp`` / ``bcd_solve`` /
``exhaustive_joint``, threshold-batched vs the legacy scan, with
wall-clocks and DP sweep counts.

Fleet (ISSUE 9): plans-per-second numbers for the planner-as-a-service
paths on the acceptance instance (24 servers x 30 layers x B = 64) —
  - the stacked ``solve_many`` b-sweep's wall-clock,
  - cold solve vs incremental ``Planner.update`` warm replans on
    single-edge deltas (>= 5x bar),
  - an N-topology sweep: cold / incremental plans per second.

Outputs:
  results/bench/bench_planner.csv   the full grid
  BENCH_planner.json (repo root)    summary incl. acceptance + fleet —
                                    the perf trajectory tracked across PRs

``--smoke`` shrinks the grid for the CI invocation (a few seconds) and
asserts the fleet speedup bars instead of recording them.
"""

from __future__ import annotations

import argparse
import json
import os
import time

from repro.core import (Planner, bcd_solve, exhaustive_joint,
                        make_edge_network, solve_msp, transformer_profile)
from repro.ft import RateChange, Straggler
from .common import Timer, emit

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
JSON_PATH = os.path.join(REPO_ROOT, "BENCH_planner.json")


def bench_instance(servers: int, blocks: int, *, seed: int = 1):
    """A transformer-profile edge instance; total layers I = blocks + 2."""
    prof = transformer_profile(
        f"bench{blocks + 2}", num_layers=blocks, d_model=512, n_heads=8,
        n_kv=8, d_ff=2048, vocab=32000, seq_len=128)
    net = make_edge_network(num_servers=servers, num_clients=4, seed=seed,
                            kappa=1 / 32.0, f_range=(1e12, 10e12),
                            mem_range=(4 * 2**30, 32 * 2**30))
    return prof, net


def acceptance_instance():
    """The ISSUE-3 acceptance point: 24 servers x 30 layers."""
    return bench_instance(24, 28)


def _grid_cell(servers, blocks, B, rows):
    prof, net = bench_instance(servers, blocks)
    b = max(1, B // 8)
    with Timer() as t_bat:
        r_bat = solve_msp(prof, net, b, B, solver="batched")
    with Timer() as t_scan:
        r_scan = solve_msp(prof, net, b, B, solver="scan")
    with Timer() as t_bcd:
        bcd_solve(prof, net, B)
    with Timer() as t_ex:
        exhaustive_joint(prof, net, B, solver="batched")
    rows.append([servers, blocks + 2, B,
                 round(t_bat.seconds, 4), r_bat.thresholds_scanned,
                 round(t_scan.seconds, 4), r_scan.thresholds_scanned,
                 round(t_bcd.seconds, 4), round(t_ex.seconds, 4)])
    return rows


def acceptance_run(b_step: int = 1):
    """exhaustive_joint, batched vs legacy scan, on the acceptance instance."""
    prof, net = acceptance_instance()
    B = 64
    with Timer() as t_bat:
        p_bat = exhaustive_joint(prof, net, B, b_step=b_step, solver="batched")
    with Timer() as t_scan:
        p_scan = exhaustive_joint(prof, net, B, b_step=b_step, solver="scan")
    identical = (p_bat.solution == p_scan.solution and p_bat.b == p_scan.b
                 and p_bat.L_t == p_scan.L_t)
    return {
        "servers": 24, "layers": 30, "B": B, "b_step": b_step,
        "scan_seconds": round(t_scan.seconds, 3),
        "batched_seconds": round(t_bat.seconds, 3),
        "speedup": round(t_scan.seconds / t_bat.seconds, 2),
        "identical_plans": bool(identical),
        "L_t": round(p_bat.L_t, 6), "b": p_bat.b,
    }


def fleet_run(smoke: bool = False) -> dict:
    """ISSUE 9 planner-as-a-service numbers on the acceptance instance."""
    prof, net = acceptance_instance()
    B = 64
    bs = list(range(1, B + 1, 8 if smoke else 1))

    # -- the stacked solve_many b-sweep ------------------------------------
    pl_np = Planner(prof, net)
    pl_np.solve_many(bs, B)                      # warm graph/DP caches
    with Timer() as t_np:
        pl_np.solve_many(bs, B)
    solve_many = {
        "servers": 24, "layers": 30, "B": B, "num_bs": len(bs),
        "numpy_seconds": round(t_np.seconds, 4),
    }

    # -- incremental: warm Planner.update vs cold re-solve -----------------
    b = 8
    deltas = []
    n = len(net.nodes)
    for k in range(8 if smoke else 16):
        if k % 2 == 0:
            deltas.append(RateChange(n_from=1 + k % (n - 1),
                                     n_to=1 + (k + 1) % (n - 1),
                                     factor=0.8 if k % 4 else 1.25))
        else:
            deltas.append(Straggler(node=1 + k % (n - 1),
                                    slowdown=1.5 if k % 4 == 1 else 1 / 1.5))
    warm_pl = Planner(prof, net)
    warm_pl.solve(b, B, solver="batched")        # seed the warm hint
    identical = True
    with Timer() as t_warm:
        warm_results = []
        for d in deltas:
            warm_pl.update(d)
            warm_results.append(warm_pl.solve(b, B, solver="batched"))
    # cold baseline: what _full_replan paid before ISSUE 9 — a fresh
    # Planner (factory + graph build) per delta on the mutated net
    from repro.ft.coordinator import Coordinator
    cold_net = net
    with Timer() as t_cold:
        for d, wr in zip(deltas, warm_results):
            cold_net, _ = Coordinator.preview(cold_net, None, d)
            cr = Planner(prof, cold_net).solve(b, B, solver="batched")
            identical = identical and (cr.objective == wr.objective
                                       and cr.solution == wr.solution)
    incremental = {
        "deltas": len(deltas), "b": b, "B": B,
        "cold_seconds": round(t_cold.seconds, 4),
        "warm_seconds": round(t_warm.seconds, 4),
        "speedup": round(t_cold.seconds / t_warm.seconds, 2),
        "identical_plans": bool(identical),
    }

    # -- N-topology fleet: plans per second, cold vs incremental ------------
    topo_bs = [4, 8, 16, 32]
    seeds = range(2 if smoke else 8)
    nets = [bench_instance(24, 28, seed=3 + s)[1] for s in seeds]
    rates = {}
    for name in ("cold", "incremental"):
        plans = 0
        with Timer() as t:
            for topo in nets:
                if name == "cold":
                    for bb in topo_bs:
                        Planner(prof, topo).solve(bb, B, solver="batched")
                        plans += 1
                else:
                    p = Planner(prof, topo)
                    for bb in topo_bs:
                        p.solve(bb, B, solver="batched")
                        plans += 1
                    for d in deltas[:4]:
                        p.update(d)
                        for bb in topo_bs:
                            p.solve(bb, B, solver="batched")
                            plans += 1
        rates[name] = {"plans": plans, "seconds": round(t.seconds, 4),
                       "plans_per_sec": round(plans / t.seconds, 2)}

    fleet = {"solve_many": solve_many, "incremental": incremental,
             "topologies": {"n": len(nets), "b_grid": topo_bs, **rates}}
    # CI bar: incremental replans >= 5x a cold re-solve
    assert incremental["speedup"] >= 5.0, incremental
    assert incremental["identical_plans"], incremental
    return fleet


def run(smoke: bool = False, b_step: int | None = None) -> dict:
    rows = []
    grid = ([(4, 8, 32)] if smoke else
            [(6, 14, 64), (12, 28, 64), (24, 28, 64), (48, 28, 128)])
    for servers, blocks, B in grid:
        _grid_cell(servers, blocks, B, rows)
    emit("bench_planner", rows,
         ["servers", "layers", "B", "msp_batched_s", "batched_sweeps",
          "msp_scan_s", "scan_sweeps", "bcd_s", "exhaustive_batched_s"])
    acc = acceptance_run(b_step=b_step if b_step is not None
                         else (32 if smoke else 1))
    fleet = fleet_run(smoke=smoke)
    summary = {
        "issue": 9,
        "generated_unix": int(time.time()),
        "smoke": smoke,
        "acceptance": acc,
        "fleet": fleet,
        "grid": [dict(zip(["servers", "layers", "B", "msp_batched_s",
                           "batched_sweeps", "msp_scan_s", "scan_sweeps",
                           "bcd_s", "exhaustive_batched_s"], r))
                 for r in rows],
    }
    if not smoke:                      # the tracked trajectory file
        with open(JSON_PATH, "w") as f:
            json.dump(summary, f, indent=2)
            f.write("\n")
        print(f"# wrote {JSON_PATH}")
    print(json.dumps(acc, indent=2))
    print(json.dumps(fleet, indent=2))
    return summary


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI (no BENCH_planner.json rewrite)")
    ap.add_argument("--b-step", type=int, default=None)
    args = ap.parse_args()
    from repro import obs

    from .common import dump_registry
    obs.enable()
    run(smoke=args.smoke, b_step=args.b_step)
    dump_registry("bench_planner")
