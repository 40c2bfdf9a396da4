"""ISSUE 3 — threshold-batched Algorithm 1: deterministic coverage.

The hypothesis property tests live in tests/test_msp.py (optional dev dep);
this module keeps the scan == batched equivalence contract, the sweep
accounting, the Planner reuse guarantees and the kernel against its
plain-numpy oracle (tests/sweep_ref.py) under test with no optional
dependencies.
"""

import math

import numpy as np
import pytest

from repro.core import (GraphFactory, Planner, brute_force_msp, build_graph,
                        make_edge_network, random_profile, solve_msp)
from repro.core.latency import (bp_latency, bwd_bytes, comm_latency,
                                fp_latency, fwd_bytes)
from repro.core.shortest_path import _LayeredDP
from conftest import same_msp_result as _same_result, small_instance
from sweep_ref import sweep_ref


@pytest.mark.parametrize(
    "seed,K", [pytest.param(s, 3, id=str(s)) for s in range(0, 40, 4)]
    # the planner's default depth on these instances
    + [pytest.param(s, 4, id=f"{s}-K4") for s in (0, 1, 2, 7, 11)])
def test_batched_equals_scan_randomized(seed, K):
    """Bit-identical (objective, cuts, placement, T_1) across solvers, and
    both optimal vs brute force."""
    prof, net = small_instance(seed, num_layers=5, num_servers=3)
    for b, B in ((4, 32), (8, 64), (64, 64)):
        r_scan = solve_msp(prof, net, b, B, K=K, solver="scan")
        r_bat = solve_msp(prof, net, b, B, K=K, solver="batched")
        assert _same_result(r_scan, r_bat), (seed, b, B)
        bf, _ = brute_force_msp(prof, net, b, B, K=K, objective="paper")
        if r_scan.feasible:
            assert r_scan.objective == pytest.approx(bf, rel=1e-9)
        else:
            assert bf == math.inf


@pytest.mark.parametrize("seed", (1, 5, 9))
def test_batched_equals_scan_restricted(seed):
    rng = np.random.default_rng(seed)
    prof, net = small_instance(seed, num_layers=6, num_servers=3)
    cuts = tuple(sorted(rng.choice(np.arange(1, 6), 2, replace=False))) + (6,)
    placement = (0,) + tuple(
        int(x) for x in rng.permutation(list(net.server_indices()))[:2])
    for kw in ({"restrict_cuts": cuts, "K": 3},
               {"restrict_placement": placement, "K": 3}):
        r_scan = solve_msp(prof, net, 8, 64, solver="scan", **kw)
        r_bat = solve_msp(prof, net, 8, 64, solver="batched", **kw)
        assert _same_result(r_scan, r_bat), (seed, kw)


def test_batched_equals_scan_memory_edges():
    """Infeasible and client-only-path instances agree across solvers."""
    prof = random_profile(np.random.default_rng(1), 4)
    # servers memoryless, roomy client -> the client-only path must win
    net = make_edge_network(num_servers=2, num_clients=2, seed=2,
                            client_mem=1e18, mem_range=(1.0, 1.0))
    r_scan = solve_msp(prof, net, 8, 64, solver="scan")
    r_bat = solve_msp(prof, net, 8, 64, solver="batched")
    assert r_scan.feasible and _same_result(r_scan, r_bat)
    assert r_scan.solution.placement == (0,)
    # nothing fits anywhere -> both infeasible
    net = make_edge_network(num_servers=2, num_clients=2, seed=2,
                            client_mem=1.0, mem_range=(1.0, 1.0))
    r_scan = solve_msp(prof, net, 8, 64, solver="scan")
    r_bat = solve_msp(prof, net, 8, 64, solver="batched")
    assert not r_scan.feasible and not r_bat.feasible


def test_solve_many_matches_per_b_solve():
    """Planner.solve_many (the stacked b-sweep under exhaustive_joint) is
    bit-identical to independent per-b batched solves."""
    for seed in (0, 3, 7):
        prof, net = small_instance(seed, num_layers=6, num_servers=4)
        pl = Planner(prof, net)
        B = 32
        bs = list(range(1, B + 1))
        for b, many in zip(bs, pl.solve_many(bs, B)):
            solo = pl.solve(b, B, solver="batched")
            assert _same_result(many, solo), (seed, b)


def test_sweep_accounting(vgg_profile, paper_network):
    """thresholds_scanned counts ALL DP sweeps: the scan solver pays the
    full-graph run + every binary-search probe + every scanned threshold;
    a batched multi-threshold kernel invocation counts as 1."""
    r_scan = solve_msp(vgg_profile, paper_network, 16, 512, solver="scan")
    r_bat = solve_msp(vgg_profile, paper_network, 16, 512, solver="batched")
    # scan: 1 (full graph) + ceil(log2(|B|)) probes + >= 1 scanned threshold
    assert r_scan.thresholds_scanned >= 3
    # batched: full + min-max + beta* probe + window kernel (+ reconstruct)
    assert 4 <= r_bat.thresholds_scanned <= 5
    assert r_bat.thresholds_scanned < r_scan.thresholds_scanned
    assert r_scan.solver == "scan" and r_bat.solver == "batched"


def test_planner_reuses_graphs_and_dp_buffers(vgg_profile, paper_network):
    """The Planner caches GraphFactory output per b and rebinds DP buffers
    instead of rebuilding them (ISSUE 3 reuse contract)."""
    pl = Planner(vgg_profile, paper_network)
    r1 = pl.solve(16, 512)
    g1 = pl.graph(16)
    r2 = pl.solve(16, 512)
    assert pl.graph(16) is g1          # same cached graph object
    assert _same_result(r1, r2)
    dp_keys = set(pl._dps)
    pl.solve(8, 512)                   # new b: same DP buffers, rebound
    assert set(pl._dps) == dp_keys


def test_graph_factory_matches_scalar_latency_model(vgg_profile,
                                                    paper_network):
    """GraphFactory's broadcast assembly reproduces the per-edge scalar
    Eqs. (2)-(11) used by the latency module (the old per-entry loops)."""
    prof, net = vgg_profile, paper_network
    b = 16
    g = GraphFactory(prof, net).graph(b)
    rng = np.random.default_rng(0)
    I, N = prof.num_layers, len(net.nodes)
    for _ in range(64):
        n = int(rng.integers(0, N))
        i = int(rng.integers(0, I))
        j = int(rng.integers(i + 1, I + 1))
        fp = fp_latency(prof, net, i, j, n, b)
        bp = bp_latency(prof, net, i, j, n, b)
        if np.isfinite(g.seg_cost[n, i, j]):
            assert g.seg_cost[n, i, j] == pytest.approx(fp + bp, rel=1e-12)
            assert g.seg_beta[n, i, j] == pytest.approx(max(fp, bp), rel=1e-12)
        cut = int(rng.integers(1, I + 1))
        m = int(rng.integers(0, N))
        if m != n:
            fb = fwd_bytes(prof, net, cut, b, from_client=(n == 0))
            gb = bwd_bytes(prof, net, cut, b, to_client=(n == 0))
            want = comm_latency(net, n, m, fb) + comm_latency(net, m, n, gb)
            assert g.comm_cost[cut, n, m] == pytest.approx(want, rel=1e-12)


def test_dense_reference_run_matches_kernel(vgg_profile, paper_network):
    """run_dense (the legacy dense-tensor sweep kept behind solver='scan')
    and the two-stage kernel return identical (dist, path) per threshold."""
    from repro.core.shortest_path import _LayeredDP
    g = build_graph(vgg_profile, paper_network, 16)
    dp = _LayeredDP(g, 7)
    for t in list(dp.all_betas()[::7]) + [np.inf]:
        d1, p1 = dp.run(float(t))
        d2, p2 = dp.run_dense(float(t))
        assert (d1 == d2) or (math.isinf(d1) and math.isinf(d2))
        assert p1 == p2


# ---------------------------------------------------------------------------
# The kernel against independent references: the plain-numpy oracle, the
# dense reference sweep, fresh per-b solves and the scan solver
# ---------------------------------------------------------------------------

def _oracle_dp(seed, b=8, K=4):
    prof, net = small_instance(seed, num_layers=6, num_servers=3)
    return _LayeredDP(build_graph(prof, net, b), K)


def _oracle(dp, ts, mode="sum"):
    return sweep_ref(dp._Ccom[0], dp._Bcom[0], dp._Sseg[0], dp._Bseg[0],
                     dp._src_cost[0], dp._src_beta[0], dp.K, ts, mode=mode)


@pytest.mark.parametrize("seed", [0, 1, 5])
@pytest.mark.parametrize("mode", ["sum", "max"])
def test_sweep_matches_oracle(seed, mode):
    """One threshold-batched ``_LayeredDP.sweep`` returns, per threshold,
    the oracle's best terminal value to the bit, in both algebras."""
    dp = _oracle_dp(seed)
    ts = dp.all_betas()[::3]
    got = dp.sweep(ts, mode=mode).best_val
    assert np.array_equal(got, _oracle(dp, ts, mode)), (seed, mode)


@pytest.mark.parametrize("seed", [0, 2])
def test_dist_at_matches_oracle(seed):
    dp = _oracle_dp(seed)
    ts = dp.all_betas()[::2]
    assert np.array_equal(dp.dist_at(ts), _oracle(dp, ts)), seed


@pytest.mark.parametrize("restricted", [False, True])
def test_dist_at_matches_dense_reference(vgg_profile, paper_network,
                                         restricted):
    """dist(t) from the batched window sweep equals the dense reference
    sweep threshold by threshold, with and without restriction masks."""
    g = build_graph(vgg_profile, paper_network, 16)
    dp = (_LayeredDP(g, 3, restrict_placement=(0, 2, 1)) if restricted
          else _LayeredDP(g, 7))
    betas = dp.all_betas()
    ts = np.append(betas[:: max(1, len(betas) // 24)], np.inf)
    want = np.array([dp.run_dense(float(t))[0] for t in ts])
    assert np.isfinite(want).any()
    assert np.array_equal(dp.dist_at(ts), want)


@pytest.mark.parametrize("seed", [0, 3, 9])
def test_solve_many_matches_fresh_per_b_solve(seed):
    """The stacked b-sweep against per-b solves on planners that share no
    cache with it."""
    prof, net = small_instance(seed, num_layers=5, num_servers=3)
    B = 32
    bs = list(range(1, B + 1, 5))
    many = Planner(prof, net).solve_many(bs, B)
    assert len(many) == len(bs)
    for b, m in zip(bs, many):
        solo = Planner(prof, net).solve(b, B, solver="batched")
        assert _same_result(m, solo), (seed, b, m, solo)


def test_solve_many_matches_scan_per_b():
    prof, net = small_instance(5, num_layers=6, num_servers=4)
    bs = [2, 7, 16, 31]
    many = Planner(prof, net).solve_many(bs, 32)
    for b, m in zip(bs, many):
        scan = Planner(prof, net).solve(b, 32, solver="scan")
        assert _same_result(m, scan), (b, m, scan)
