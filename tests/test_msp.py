"""Algorithm 1 (bottleneck-aware shortest path) — optimality + properties."""

import math

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep: skip module, not error
from hypothesis import given, settings, strategies as st

from repro.core import (Planner, brute_force_msp, build_graph, graph_stats,
                        make_edge_network, random_profile, solve_msp,
                        total_latency, validate_solution)
from repro.core.shortest_path import path_cost, _path_bottleneck
from conftest import same_msp_result as _same_result, small_instance


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 200), b=st.sampled_from([4, 8, 16]),
       B=st.sampled_from([32, 64]))
def test_alg1_matches_brute_force_paper_objective(seed, b, B):
    """Theorem 2: Algorithm 1 is optimal for the MSP objective."""
    prof, net = small_instance(seed, num_layers=5, num_servers=3)
    res = solve_msp(prof, net, b, B, K=3)
    bf, bf_sol = brute_force_msp(prof, net, b, B, K=3, objective="paper")
    if not res.feasible:
        assert bf == math.inf
    else:
        assert res.objective == pytest.approx(bf, rel=1e-9)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 100))
def test_alg1_solution_is_valid(seed):
    prof, net = small_instance(seed, num_layers=6, num_servers=4)
    res = solve_msp(prof, net, 8, 64, K=4)
    if res.feasible:
        validate_solution(res.solution, prof, net)
        # reported L_t is the true Eq.14 value of the returned solution
        assert res.L_t == pytest.approx(
            total_latency(prof, net, res.solution, 8, 64), rel=1e-9)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 100))
def test_paper_gap_to_true_objective_is_bounded(seed):
    """Paper-mode search vs the TRUE objective (co-location sums, joint
    memory): the found solution evaluates within 25% of the true optimum on
    small instances (usually exact; DESIGN.md §6 discusses why not always)."""
    prof, net = small_instance(seed, num_layers=5, num_servers=3)
    res = solve_msp(prof, net, 8, 64, K=3)
    bf, bf_sol = brute_force_msp(prof, net, 8, 64, K=3, objective="true")
    if res.feasible and bf_sol is not None:
        assert res.L_t <= bf * 1.25 + 1e-9


def test_path_cost_equals_fill_latency(vgg_profile, paper_network):
    from repro.core import fill_latency
    res = solve_msp(vgg_profile, paper_network, 16, 512)
    g = build_graph(vgg_profile, paper_network, 16)
    path = list(zip(res.solution.placement, res.solution.cuts))
    assert path_cost(g, path) == pytest.approx(
        fill_latency(vgg_profile, paper_network, res.solution, 16), rel=1e-9)


def test_restricted_cuts_respected(vgg_profile, paper_network):
    cuts = (4, 10, 16)
    res = solve_msp(vgg_profile, paper_network, 16, 512,
                    restrict_cuts=cuts, K=len(cuts))
    assert res.feasible
    assert res.solution.cuts == cuts


def test_restricted_placement_respected(vgg_profile, paper_network):
    placement = (0, 2, 1)
    res = solve_msp(vgg_profile, paper_network, 16, 512,
                    restrict_placement=placement, K=3)
    if res.feasible:
        assert tuple(res.solution.placement) == \
            placement[:len(res.solution.placement)]


def test_no_pipeline_solves_pure_min_sum(vgg_profile):
    """b = B => xi = 0: Algorithm 1 degenerates to plain shortest path.
    (Needs roomy nodes: the paper's Eq. 11 scales the WHOLE footprint by b,
    so b = 512 on 2-16 GB nodes is memory-infeasible — that infeasibility
    is itself one of the paper's arguments for micro-batching.)"""
    net = make_edge_network(num_servers=6, num_clients=4, seed=1,
                            kappa=1 / 32.0, mem_range=(1e15, 1e15),
                            client_mem=1e15)
    res = solve_msp(vgg_profile, net, 512, 512)
    assert res.feasible
    assert res.thresholds_scanned == 1
    # objective must equal T_f exactly (no bottleneck contribution)
    assert res.objective == pytest.approx(res.T_f, rel=1e-9)


def test_bottleneck_consistency(vgg_profile, paper_network):
    res = solve_msp(vgg_profile, paper_network, 16, 512)
    g = build_graph(vgg_profile, paper_network, 16)
    path = list(zip(res.solution.placement, res.solution.cuts))
    assert _path_bottleneck(g, path) == pytest.approx(res.T_1, rel=1e-9)


def test_graph_stats_reports_paper_scale(vgg_profile, paper_network):
    g = build_graph(vgg_profile, paper_network, 16)
    s = graph_stats(g)
    assert s["paper_vertices"] > 0
    assert s["paper_edges_upper"] > 0


# ---------------------------------------------------------------------------
# ISSUE 3: threshold-batched solver — standing randomized cross-check
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 400), b=st.sampled_from([1, 4, 8, 16, 64]),
       B=st.sampled_from([32, 64]),
       mem_scale=st.sampled_from([1.0, 1.0, 1e-3, 1e-9]),
       restrict=st.sampled_from(["free", "cuts", "placement"]))
def test_batched_equals_scan_equals_brute_force(seed, b, B, mem_scale, restrict):
    """solver='batched' returns bit-identical (objective, cuts, placement,
    T_1) results to the legacy solver='scan', and both match brute force —
    across free/restricted solves, memory-tight (infeasible / client-only)
    instances and micro-batch sizes (incl. b >= B, i.e. xi = 0)."""
    rng = np.random.default_rng(seed)
    prof = random_profile(rng, 5)
    net = make_edge_network(
        num_servers=3, num_clients=2, seed=seed,
        mem_range=(mem_scale * 2 * 2**30, mem_scale * 16 * 2**30),
        client_mem=4 * 2**30)   # roomy client: tight servers -> client-only
    kw = {"K": 3}
    if restrict == "cuts":
        cuts = tuple(sorted(rng.choice(np.arange(1, 5), 2, replace=False)))
        kw["restrict_cuts"] = cuts + (5,)
    elif restrict == "placement":
        kw["restrict_placement"] = (0,) + tuple(
            int(x) for x in rng.permutation(list(net.server_indices()))[:2])
    b = min(b, B)
    r_scan = solve_msp(prof, net, b, B, solver="scan", **kw)
    r_bat = solve_msp(prof, net, b, B, solver="batched", **kw)
    assert _same_result(r_scan, r_bat), (r_scan, r_bat)
    if restrict == "free":
        bf, _ = brute_force_msp(prof, net, b, B, K=3, objective="paper")
        if r_scan.feasible:
            assert r_scan.objective == pytest.approx(bf, rel=1e-9)
        else:
            assert bf == math.inf


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 60))
def test_solve_many_matches_per_b_solve(seed):
    """Planner.solve_many (the stacked b-sweep under exhaustive_joint) is
    bit-identical to independent per-b batched solves."""
    prof, net = small_instance(seed, num_layers=5, num_servers=3)
    pl = Planner(prof, net)
    B = 32
    bs = list(range(1, B + 1, 3))
    for b, many in zip(bs, pl.solve_many(bs, B)):
        solo = pl.solve(b, B, solver="batched")
        assert _same_result(many, solo), (b, many, solo)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 60), b=st.sampled_from([4, 8, 16]))
def test_batched_matches_brute_force_randomized(seed, b):
    """Standing randomized gate on the batched solver at the planner's
    default depth: per-b solve and the stacked solve_many both reach the
    brute-force optimum of the paper objective."""
    prof, net = small_instance(seed, num_layers=5, num_servers=3)
    pl = Planner(prof, net)
    B = 32
    bs = [max(1, b - 2), b]
    for bb, many in zip(bs, pl.solve_many(bs, B)):
        solo = Planner(prof, net).solve(bb, B, solver="batched")
        assert _same_result(many, solo), (bb, many, solo)
        bf, _ = brute_force_msp(prof, net, bb, B, K=4, objective="paper")
        if many.feasible:
            assert many.objective == pytest.approx(bf, rel=1e-9)
        else:
            assert bf == math.inf


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50))
def test_more_servers_never_hurt(seed):
    """Fig. 5(a): latency is non-increasing in N (the planner can ignore
    extra servers)."""
    rng = np.random.default_rng(seed)
    prof = random_profile(rng, 6)
    net_small = make_edge_network(num_servers=3, seed=seed)
    net_big = make_edge_network(num_servers=3, seed=seed)  # same base
    r1 = solve_msp(prof, net_small, 8, 64)
    r2 = solve_msp(prof, net_big, 8, 64)
    assert r2.objective <= r1.objective * (1 + 1e-9)
