"""Discrete-event engine (repro.sim) — cross-validation vs Eqs. (12)-(14),
event/FIFO semantics, admission policies (FIFO vs 1F1B memory claims),
vectorized-vs-heap engine equivalence, capacity traces, scenarios, and the
replanning driver."""

import json
import math

import numpy as np
import pytest

from repro.core import (EdgeNetwork, Node, SplitSolution,
                        evaluate_under_fluctuation, fill_latency,
                        make_edge_network, ours, pipeline_interval,
                        total_latency, uniform_profile, vgg16_profile)
from repro.core.profiles import ModelProfile
from repro.ft import RateChange, Straggler
from repro.sim import (FIFO, NetworkScenario, OneFOneB, PiecewiseTrace,
                       ReplanTrigger, activation_occupancy, build_tasks,
                       compare_engines, constant, cross_validate,
                       cross_validate_many, gauss_markov,
                       gauss_markov_scenario, iid_piecewise, piecewise,
                       piecewise_cv_scenario, random_instance, resolve_policy,
                       simulate_plan, simulate_with_replanning,
                       stage_activation_highwater, vectorizable,
                       write_chrome_trace)


@pytest.fixture(scope="module")
def paper_plan():
    prof = vgg16_profile(work_units="bytes")
    net = make_edge_network(num_servers=4, num_clients=4, seed=1,
                            kappa=1 / 32.0)
    plan = ours(prof, net, B=64, b0=8)
    return prof, net, plan


# ---------------------------------------------------------------------------
# The standing cross-validation: sim == analytic on deterministic networks
# ---------------------------------------------------------------------------

def test_cross_validation_randomized_triples():
    """>= 20 randomized (profile, network, plan) triples: simulated T_f, T_i
    and L_t match Eqs. (12)-(14) within 1e-6 relative tolerance."""
    checks = cross_validate_many(trials=24, seed=11, rtol=1e-6)
    assert len(checks) == 24
    for c in checks:
        assert c.ok, (c.max_rel_err, c.cuts, c.placement, c.b, c.B)
    assert max(c.max_rel_err for c in checks) < 1e-9


def test_cross_validation_on_planner_output(paper_plan):
    prof, net, plan = paper_plan
    c = cross_validate(prof, net, plan.solution, plan.b, plan.B)
    assert c.ok
    assert c.L_t_ana == pytest.approx(plan.L_t, rel=1e-9)


def test_single_microbatch_degenerates_to_fill():
    prof, net, sol, b, _ = random_instance(3)
    rep = simulate_plan(prof, net, sol, b, B=b)   # one slot: L_t == T_f
    assert rep.num_microbatches == 1
    assert rep.T_i == 0.0
    assert rep.L_t == pytest.approx(fill_latency(prof, net, sol, b), rel=1e-9)


# ---------------------------------------------------------------------------
# Event ordering + resource-contention semantics
# ---------------------------------------------------------------------------

def _per_resource(records):
    by_res = {}
    for r in records:
        by_res.setdefault(r.resource, []).append(r)
    return by_res


def test_event_ordering_and_fifo():
    prof, net, sol, b, B = random_instance(5)
    rep = simulate_plan(prof, net, sol, b, B=B)
    for recs in _per_resource(rep.records).values():
        recs = sorted(recs, key=lambda r: r.start)
        # one-at-a-time service: intervals never overlap
        for a, c in zip(recs, recs[1:]):
            assert c.start >= a.end - 1e-12
        # FIFO: a linear pipeline visits each resource in micro-batch order
        assert [r.microbatch for r in recs] == sorted(
            r.microbatch for r in recs)
    # chain precedence: within a micro-batch, records appear in chain order
    for m in range(rep.num_microbatches):
        chain = [r for r in rep.records if r.microbatch == m]
        chain.sort(key=lambda r: (r.start, r.end))
        for a, c in zip(chain, chain[1:]):
            assert c.start >= a.end - 1e-12


def test_colocated_stages_contend():
    """Two submodels on one node serialize on its FP/BP engines (the C9-C16
    co-location sums), and the fill latency still equals Eq. (12)."""
    prof = uniform_profile(8, fp=1.0, bp=2.0, act=1.0)
    net = make_edge_network(num_servers=3, num_clients=1, seed=0)
    sol = SplitSolution(cuts=(2, 4, 6, 8), placement=(0, 1, 2, 1))
    b, B = 4, 32
    # a solo micro-batch sees no contention: fill == Eq. (12) exactly
    solo = simulate_plan(prof, net, sol, b, num_microbatches=1)
    assert solo.L_t == pytest.approx(fill_latency(prof, net, sol, b),
                                     rel=1e-9)
    rep = simulate_plan(prof, net, sol, b, B=B)
    # under pipelining, trailing micro-batches occupy the shared engine
    # before mb0 returns to it — observed fill can only inflate
    assert rep.T_f >= solo.L_t - 1e-12
    by_res = _per_resource(rep.records)
    # node 1 hosts stages 1 and 3: its fp engine serves both, serialized
    fp1 = sorted(by_res[("fp", 1)], key=lambda r: r.start)
    assert {r.stage for r in fp1} == {1, 3}
    for a, c in zip(fp1, fp1[1:]):
        assert c.start >= a.end - 1e-12
    # work conservation: makespan >= the busiest resource's total work
    for recs in by_res.values():
        assert rep.L_t >= sum(r.duration for r in recs) - 1e-9
    # Eq. (14) assumes a perfectly interleaved cyclic schedule on the shared
    # engine; greedy FIFO on a reentrant line deviates from it in either
    # direction, but only by bounded idle time — a gross engine bug (e.g.
    # lost serialization, double service) would blow well past this
    ana = total_latency(prof, net, sol, b, B)
    assert rep.L_t == pytest.approx(ana, rel=0.25)


# ---------------------------------------------------------------------------
# Piecewise traces: integration, outage stalls, Gauss-Markov statistics
# ---------------------------------------------------------------------------

def test_trace_integration_across_breakpoints():
    tr = piecewise((0.0, 1.0, 3.0), (2.0, 0.5, 4.0))
    assert tr.time_to_complete(0.0, 1.0) == pytest.approx(0.5)
    # 2.0 work: [0,1) serves 2.0 exactly
    assert tr.time_to_complete(0.0, 2.0) == pytest.approx(1.0)
    # 2.5 work: 2.0 in [0,1), 0.5 more at rate 0.5 -> t=2.0
    assert tr.time_to_complete(0.0, 2.5) == pytest.approx(2.0)
    # starting mid-segment
    assert tr.time_to_complete(0.5, 1.0) == pytest.approx(0.5)
    assert tr.value_at(2.9) == 0.5 and tr.value_at(3.0) == 4.0


def test_trace_zero_segment_stalls_and_trailing_zero_is_inf():
    tr = piecewise((0.0, 1.0, 2.0), (1.0, 0.0, 1.0))
    # 1.5 work from t=0: 1.0 by t=1, stall on [1,2), finish 0.5 at t=2.5
    assert tr.time_to_complete(0.0, 1.5) == pytest.approx(2.5)
    dead = piecewise((0.0, 1.0), (1.0, 0.0))
    assert math.isinf(dead.time_to_complete(0.5, 1.0))


def test_trace_product_merges_breakpoints():
    a = piecewise((0.0, 2.0), (1.0, 3.0))
    b = piecewise((0.0, 1.0), (2.0, 0.5))
    p = a * b
    for t in (0.0, 0.5, 1.0, 1.5, 2.0, 5.0):
        assert p.value_at(t) == pytest.approx(a.value_at(t) * b.value_at(t))


def test_gauss_markov_stationary_stats():
    rng = np.random.default_rng(0)
    tr = gauss_markov(rng, cv=0.2, dt=1.0, horizon=20000.0, corr=0.9)
    vals = np.asarray(tr.values)
    assert vals.mean() == pytest.approx(1.0, abs=0.03)
    assert vals.std() == pytest.approx(0.2, abs=0.03)
    # correlated: lag-1 autocorrelation near corr
    v = vals - vals.mean()
    rho = (v[:-1] * v[1:]).mean() / (v.var() + 1e-12)
    assert rho == pytest.approx(0.9, abs=0.05)


def test_cv_zero_scenarios_are_constant():
    rng = np.random.default_rng(0)
    assert iid_piecewise(rng, 0.0, dt=1.0, horizon=10.0).is_constant()
    assert gauss_markov(rng, 0.0, dt=1.0, horizon=10.0).is_constant()


# ---------------------------------------------------------------------------
# Scenario injection: stragglers, outages, time-varying capacity
# ---------------------------------------------------------------------------

def test_straggler_window_slows_pipeline(paper_plan):
    prof, net, plan = paper_plan
    base = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B)
    node = plan.solution.placement[1]
    scen = NetworkScenario().with_straggler(node, 0.0, base.L_t, 8.0)
    slow = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B,
                         scenario=scen)
    assert slow.L_t > base.L_t


def test_outage_stalls_transfer(paper_plan):
    prof, net, plan = paper_plan
    base = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B)
    a, c = plan.solution.placement[0], plan.solution.placement[1]
    t_out = 5.0 * base.L_t
    scen = NetworkScenario().with_outage(a, c, 0.0, t_out)
    rep = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B,
                        scenario=scen)
    # the first activation transfer cannot complete before the outage lifts
    assert rep.T_f >= t_out


def test_time_varying_scenarios_run(paper_plan):
    prof, net, plan = paper_plan
    base = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B)
    rng = np.random.default_rng(1)
    for make in (piecewise_cv_scenario, gauss_markov_scenario):
        scen = make(net, 0.3, rng, dt=base.L_t / 16, horizon=4 * base.L_t)
        rep = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B,
                            scenario=scen)
        assert np.isfinite(rep.L_t) and rep.L_t > 0
        assert np.all(np.diff(rep.mb_complete) > -1e-12)


# ---------------------------------------------------------------------------
# Trace-driven fluctuation evaluation (Fig. 6b path)
# ---------------------------------------------------------------------------

def test_fluctuation_trace_mode(paper_plan):
    prof, net, plan = paper_plan
    r0 = evaluate_under_fluctuation(prof, net, plan, 0.0, draws=2, seed=0,
                                    mode="trace")
    assert r0.degradation == pytest.approx(1.0, rel=1e-9)
    for model in ("piecewise", "gauss_markov"):
        r = evaluate_under_fluctuation(prof, net, plan, 0.25, draws=4,
                                       seed=0, mode="trace",
                                       trace_model=model)
        assert np.isfinite(r.mean_latency) and r.mean_latency > 0
        assert r.p95_latency >= r.mean_latency - 1e-12


def test_fluctuation_iid_mode_unchanged(paper_plan):
    """The default path must keep producing the original i.i.d. numbers."""
    import repro.core.latency as L
    prof, net, plan = paper_plan
    r = evaluate_under_fluctuation(prof, net, plan, 0.1, draws=8, seed=3)
    rng = np.random.default_rng(3)
    expect = [L.total_latency(prof, net.with_fluctuation(rng, 0.1),
                              plan.solution, plan.b, plan.B)
              for _ in range(8)]
    assert r.mean_latency == pytest.approx(float(np.mean(expect)), rel=1e-12)


def test_fluctuation_rejects_unknown_mode(paper_plan):
    prof, net, plan = paper_plan
    with pytest.raises(ValueError):
        evaluate_under_fluctuation(prof, net, plan, 0.1, mode="nope")


# ---------------------------------------------------------------------------
# Replanning driven by simulated time
# ---------------------------------------------------------------------------

def test_replanning_driver(paper_plan):
    prof, net, plan = paper_plan
    base = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B)
    node = plan.solution.placement[1]
    trig = [ReplanTrigger(0.4 * base.L_t, Straggler(node, 6.0)),
            ReplanTrigger(0.9 * base.L_t, RateChange(0, node, 0.5))]
    rep = simulate_with_replanning(prof, net, plan.B, trig)
    assert rep.num_replans == 2
    assert np.isfinite(rep.makespan)
    # a straggler + rate drop can only hurt vs the undisturbed run
    assert rep.makespan >= base.L_t - 1e-9
    # every sample is accounted for across segments
    samples = sum(s.completed * s.plan.b for s in rep.segments)
    assert samples >= plan.B
    assert all(s.outcome.action in ("replan", "microbatch")
               for s in rep.segments if s.outcome is not None)


def test_replanning_consumes_scenario_triggers(paper_plan):
    """Triggers composed onto the scenario via with_replan fire too."""
    prof, net, plan = paper_plan
    base = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B)
    node = plan.solution.placement[1]
    scen = NetworkScenario().with_replan(0.5 * base.L_t, Straggler(node, 6.0))
    rep = simulate_with_replanning(prof, net, plan.B, scenario=scen)
    assert rep.num_replans == 1


def test_replanning_rejects_node_failure_with_scenario(paper_plan):
    """NodeFailure renumbers indices; index-keyed scenario traces would
    silently land on the wrong nodes — must be rejected."""
    from repro.ft import NodeFailure
    prof, net, plan = paper_plan
    scen = NetworkScenario().with_straggler(1, 0.0, 1.0, 2.0)
    with pytest.raises(ValueError, match="NodeFailure"):
        simulate_with_replanning(prof, net, plan.B,
                                 [ReplanTrigger(0.01, NodeFailure(2))],
                                 scenario=scen)


def test_replanning_no_triggers_matches_plain_sim(paper_plan):
    prof, net, plan = paper_plan
    rep = simulate_with_replanning(prof, net, plan.B, [])
    plain = simulate_plan(prof, net, rep.coordinator.plan.solution,
                          rep.coordinator.plan.b, B=plan.B)
    assert rep.makespan == pytest.approx(plain.L_t, rel=1e-9)


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_export(tmp_path, paper_plan):
    prof, net, plan = paper_plan
    rep = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B)
    path = write_chrome_trace(rep.records, str(tmp_path / "trace.json"))
    with open(path) as f:
        data = json.load(f)
    evs = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert len(evs) == len(rep.records)
    assert all({"name", "ts", "dur", "pid", "tid"} <= set(e) for e in evs)
    names = [e["args"]["name"] for e in data["traceEvents"]
             if e["ph"] == "M"]
    assert any(n.startswith("node0:fp") for n in names)


def test_build_tasks_chain_shape():
    prof, net, sol, b, _ = random_instance(9)
    m = 3
    tasks = build_tasks(prof, net, sol, b, m)
    K = len(list(sol.segments()))
    assert len(tasks) == m * (2 * K + 2 * (K - 1))
    roots = [t for t in tasks if t.dep is None]
    assert len(roots) == m                       # one chain per micro-batch
    assert all(t.resource == ("fp", 0) for t in roots)


# ---------------------------------------------------------------------------
# Admission policies: FIFO bit-identity, 1F1B windows, memory claims
# ---------------------------------------------------------------------------

def _saturating_instance(S=4, Q=12):
    """Distinct-placement chain whose *final* BP dominates everything: every
    earlier resource drains instantly, so each stage buffers as many live
    activations as its admission policy permits — the claims are achieved
    exactly, not just bounded."""
    fp = np.full(S, 1e-3)
    bp = np.full(S, 1e-3)
    bp[-1] = 10.0
    prof = ModelProfile(name="sat", fp_work=fp, bp_work=bp,
                        act_bytes=np.full(S, 1.0),
                        grad_bytes=np.full(S, 1.0),
                        param_bytes=np.zeros(S), opt_bytes=np.zeros(S))
    nodes = [Node("c", f=1.0, t0=0.0, t1=0.0, b_th=0, is_client=True)]
    nodes += [Node(f"s{i}", f=1.0, t0=0.0, t1=0.0, b_th=0)
              for i in range(1, S)]
    rate = np.full((S, S), 1e6)
    np.fill_diagonal(rate, 0.0)
    net = EdgeNetwork(nodes=nodes, rate=rate, num_clients=1)
    sol = SplitSolution(cuts=tuple(range(1, S + 1)),
                        placement=tuple(range(S)))
    return prof, net, sol, Q


def _record_tuple(rec):
    return (rec.microbatch, rec.stage, rec.kind, rec.resource, rec.start,
            rec.end)


def test_fifo_policy_is_the_pr1_engine():
    """FIFO must reproduce PR 1 timelines bit-identically: it contributes
    zero extra edges, so the default heap event loop is untouched."""
    prof, net, sol, b, B = random_instance(7)
    tasks = build_tasks(prof, net, sol, b, 4)
    assert FIFO().extra_dependencies(tasks) == []
    rep = simulate_plan(prof, net, sol, b, B=B)        # defaults
    assert rep.engine == "event" and rep.policy == "fifo"
    explicit = simulate_plan(prof, net, sol, b, B=B, policy="fifo",
                             engine="event")
    assert [_record_tuple(r) for r in rep.records] == \
           [_record_tuple(r) for r in explicit.records]


def test_policy_resolution_and_windows():
    assert resolve_policy("gpipe").name == "fifo"
    assert resolve_policy(OneFOneB()).name == "1f1b"
    with pytest.raises(ValueError, match="unknown admission policy"):
        resolve_policy("round-robin")
    one = OneFOneB()
    assert [one.window(4, j) for j in range(4)] == [4, 3, 2, 1]
    assert FIFO().window(4, 0) is None


def test_engines_agree_under_both_policies():
    """Heap engine vs vectorized engine: identical micro-batch completion
    times (to float noise) wherever the vectorized engine is eligible."""
    hits = 0
    for seed in range(12):
        prof, net, sol, b, B = random_instance(31 * seed + 2)
        if not vectorizable(prof, net, sol, b):
            continue
        hits += 1
        Q = 1 + math.ceil((B - b) / b)
        for pol in ("fifo", "1f1b"):
            assert compare_engines(prof, net, sol, b, Q, policy=pol) < 1e-9
    assert hits >= 8        # the generator yields distinct placements


def test_vectorized_engine_covers_reentrant_and_traces():
    """The ISSUE 5 generalizations: co-located (reentrant) placements run
    the merged-scan fixpoint and time-varying scenarios the segmented trace
    scans — both vectorized, both matching the heap engine exactly."""
    prof = uniform_profile(8, fp=1.0, bp=2.0, act=1.0)
    net = make_edge_network(num_servers=3, num_clients=1, seed=0)
    colocated = SplitSolution(cuts=(2, 4, 6, 8), placement=(0, 1, 2, 1))
    assert vectorizable(prof, net, colocated, 4)
    rep = simulate_plan(prof, net, colocated, 4, B=16, engine="vectorized")
    assert rep.engine == "vectorized"
    assert "fixpoint" in rep.engine_reason
    assert compare_engines(prof, net, colocated, 4, 8) < 1e-9
    # solo micro-batch still matches Eq. (12) exactly (no contention)
    solo = simulate_plan(prof, net, colocated, 4, num_microbatches=1,
                         engine="vectorized")
    assert solo.L_t == pytest.approx(fill_latency(prof, net, colocated, 4),
                                     rel=1e-9)
    # a time-varying scenario stays vectorized under "auto" as well
    distinct = SplitSolution(cuts=(2, 4, 8), placement=(0, 1, 2))
    scen = NetworkScenario().with_straggler(1, 0.0, 1.0, 2.0)
    rep = simulate_plan(prof, net, distinct, 4, num_microbatches=2,
                        scenario=scen, engine="auto")
    assert rep.engine == "vectorized"
    assert "trace" in rep.engine_reason
    assert compare_engines(prof, net, distinct, 4, 6, scenario=scen) < 1e-9
    # ... and an all-constant scenario uses the constant-capacity scans
    rep = simulate_plan(prof, net, distinct, 4, num_microbatches=2,
                        scenario=NetworkScenario(), engine="auto")
    assert rep.engine == "vectorized"
    assert "constant-capacity" in rep.engine_reason


def test_vectorized_raises_with_violated_precondition():
    """No silent fallback under engine='vectorized': the error names the
    violated precondition (here: a used resource that can never finish),
    while engine='auto' records why the event engine ran."""
    prof = uniform_profile(4, fp=1.0, bp=1.0, act=1.0)
    nodes = [Node("c", f=1.0, t0=0.0, t1=0.0, b_th=0, is_client=True),
             Node("s", f=1.0, t0=0.0, t1=0.0, b_th=0)]
    net = EdgeNetwork(nodes=nodes, rate=np.zeros((2, 2)), num_clients=1)
    sol = SplitSolution(cuts=(2, 4), placement=(0, 1))
    assert not vectorizable(prof, net, sol, 1)
    with pytest.raises(ValueError, match="cannot finish its work"):
        simulate_plan(prof, net, sol, 1, num_microbatches=2,
                      engine="vectorized")
    rep = simulate_plan(prof, net, sol, 1, num_microbatches=1,
                        engine="auto")
    assert rep.engine == "event"
    assert "cannot finish its work" in rep.engine_reason
    # a dead *trace* (outage that never lifts) is detected the same way
    net2 = EdgeNetwork(nodes=nodes, rate=np.full((2, 2), 10.0),
                       num_clients=1)
    dead = NetworkScenario(link_mult={(0, 1): constant(0.0)})
    assert not vectorizable(prof, net2, sol, 1, scenario=dead)
    with pytest.raises(ValueError, match="zero trailing capacity"):
        simulate_plan(prof, net2, sol, 1, num_microbatches=2, scenario=dead,
                      engine="vectorized")


def test_engine_reason_reported():
    prof, net, sol, b, B = random_instance(5)
    assert simulate_plan(prof, net, sol, b, B=B).engine_reason \
        == "event: requested"
    assert "column scans" in simulate_plan(
        prof, net, sol, b, B=B, engine="auto").engine_reason
    assert "windowed scan" in simulate_plan(
        prof, net, sol, b, B=B, engine="auto", policy="1f1b").engine_reason


# ---------------------------------------------------------------------------
# Randomized parity grid: traces x reentrant placements x policies
# ---------------------------------------------------------------------------

def _grid_instance(seed: int, reentrant: bool, cv: float, model: str):
    """One randomized instance for the engine-parity grid."""
    from repro.core.profiles import random_profile
    from repro.sim import random_chain_solution, random_reentrant_solution
    rng = np.random.default_rng(seed)
    prof = random_profile(rng, int(rng.integers(5, 11)))
    net = make_edge_network(num_servers=int(rng.integers(2, 5)),
                            num_clients=int(rng.integers(1, 4)), seed=seed)
    if reentrant:
        sol = random_reentrant_solution(rng, prof, net)
    else:
        sol = random_chain_solution(rng, prof, net)
    b = int(rng.integers(1, 9))
    Q = int(rng.integers(2, 14))
    scen = None
    if cv > 0:
        maker = (piecewise_cv_scenario if model == "piecewise"
                 else gauss_markov_scenario)
        scen = maker(net, cv, rng, dt=0.02, horizon=5.0)
    return prof, net, sol, b, Q, scen


@pytest.mark.parametrize("reentrant", [False, True])
@pytest.mark.parametrize("cv,model", [(0.0, "piecewise"),
                                      (0.3, "piecewise"),
                                      (0.3, "gauss_markov")])
def test_engine_parity_grid(reentrant, cv, model):
    """Heap vs vectorized on randomized piecewise traces x reentrant plans
    x all three admission policies: identical completion times to float
    noise (the ISSUE 5 acceptance grid)."""
    hits = 0
    for seed in range(6):
        prof, net, sol, b, Q, scen = _grid_instance(
            101 * seed + 13, reentrant, cv, model)
        for pol in ("fifo", "1f1b", "memory"):
            try:
                gap = compare_engines(prof, net, sol, b, Q, policy=pol,
                                      scenario=scen)
            except ValueError:
                continue          # memory-infeasible under the budget
            assert gap < 1e-9, (seed, pol, gap)
            hits += 1
    assert hits >= 10


def test_engine_parity_hypothesis():
    """Property-based twin of the parity grid (skips without hypothesis)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10_000), reentrant=st.booleans(),
           cv=st.sampled_from([0.0, 0.25, 0.5]),
           pol=st.sampled_from(["fifo", "1f1b", "memory"]),
           model=st.sampled_from(["piecewise", "gauss_markov"]))
    def run(seed, reentrant, cv, pol, model):
        prof, net, sol, b, Q, scen = _grid_instance(seed, reentrant, cv,
                                                    model)
        try:
            gap = compare_engines(prof, net, sol, b, Q, policy=pol,
                                  scenario=scen)
        except ValueError:
            return                # memory-infeasible under the budget
        assert gap < 1e-9

    run()


@pytest.mark.parametrize("seed", [3, 5, 6, 7])
def test_random_reentrant_solution_is_valid(seed):
    """Seeds whose first reentrant draw on the grid's stream puts
    consecutive submodels on one node: the generator redraws and returns a
    solution that satisfies Eq. 21."""
    from repro.core import validate_solution
    prof, net, sol, _b, _Q, _scen = _grid_instance(seed, True, 0.0,
                                                   "piecewise")
    validate_solution(sol, prof, net)
    assert sol.placement[0] == 0 and len(sol.placement) >= 2


def test_simulate_plans_mixed_kind_reentrant_group_exact():
    """A reentrant resource whose visits mix serving kinds (a zero-work
    visit co-located with a traced one) must not be silently mis-served by
    the stacked fixpoint — the batched path declines such structures and
    the per-plan merged scan (scalar kind) stays exact."""
    import dataclasses
    from repro.sim import simulate_plans
    prof = uniform_profile(8, fp=1.0, bp=2.0, act=1.0)
    fp = np.ones(8)
    fp[6:] = 0.0                   # stage at layers 7-8: zero FP work
    prof = dataclasses.replace(prof, fp_work=fp)
    nodes = [Node("c", f=1.0, t0=0.0, t1=0.0, b_th=0, is_client=True)]
    nodes += [Node(f"s{i}", f=1.0, t0=0.0, t1=0.0, b_th=0)
              for i in (1, 2)]
    rate = np.full((3, 3), 10.0)
    np.fill_diagonal(rate, 0.0)
    net = EdgeNetwork(nodes=nodes, rate=rate, num_clients=1)
    sol = SplitSolution(cuts=(2, 4, 6, 8), placement=(0, 1, 2, 1))
    rng = np.random.default_rng(7)
    scen = gauss_markov_scenario(net, 0.4, rng, dt=0.05, horizon=500.0)
    plans = [(sol, b) for b in (1, 2, 3)]
    loop = [simulate_plan(prof, net, s, b, B=9, scenario=scen,
                          engine="auto") for s, b in plans]
    bat = simulate_plans(prof, net, plans, B=9, scenario=scen,
                         engine="auto")
    ev = [simulate_plan(prof, net, s, b, B=9, scenario=scen,
                        engine="event") for s, b in plans]
    for lr, br, er in zip(loop, bat, ev):
        assert np.array_equal(lr.mb_complete, br.mb_complete)
        gap = np.max(np.abs(er.mb_complete - br.mb_complete)
                     / np.maximum(np.abs(er.mb_complete), 1e-30))
        assert gap < 1e-9


def test_simulate_plans_matches_looped_simulate_plan():
    """The batched multi-plan path (stacked plan axis + stacked fixpoint)
    returns exactly the per-plan reports' completion times."""
    from repro.sim import simulate_plans
    prof = uniform_profile(8, fp=1.0, bp=2.0, act=1.0)
    net = make_edge_network(num_servers=3, num_clients=1, seed=0)
    sols = [SplitSolution(cuts=(2, 4, 8), placement=(0, 1, 2)),     # chain
            SplitSolution(cuts=(2, 4, 6, 8), placement=(0, 1, 2, 1))]  # re.
    for sol in sols:
        plans = [(sol, b) for b in (1, 2, 3, 4)]
        for pol in ("fifo", "1f1b"):
            loop = [simulate_plan(prof, net, s, b, B=12, policy=pol,
                                  engine="auto") for s, b in plans]
            bat = simulate_plans(prof, net, plans, B=12, policy=pol,
                                 engine="auto")
            for lr, br in zip(loop, bat):
                assert np.array_equal(lr.mb_complete, br.mb_complete)


def test_highwater_never_exceeds_schedule_claims():
    """Event-by-event: measured per-stage activation occupancy stays within
    the closed-form claims of pipeline.schedule for every random instance,
    under both policies and both engines."""
    from repro.pipeline.schedule import memory_highwater
    for seed in (1, 5, 9):
        prof, net, sol, b, B = random_instance(seed)
        Q = 1 + math.ceil((B - b) / b)
        S = len(list(sol.segments()))
        for pol in ("fifo", "1f1b"):
            claims = memory_highwater(S, Q, pol)
            for eng in ("event", "auto"):
                rep = simulate_plan(prof, net, sol, b, num_microbatches=Q,
                                    policy=pol, engine=eng)
                occ = activation_occupancy(rep.records)
                assert set(occ) == set(claims)
                for j, series in occ.items():
                    for _, level in series:       # every event, every stage
                        assert level <= claims[j]


def test_1f1b_highwater_matches_schedule_claims_exactly():
    """On a pipeline whose claims are achievable, the engine's measured
    high-water marks equal pipeline.schedule's closed form — stage by
    stage, for both the GPipe and the 1F1B claim."""
    from repro.pipeline.schedule import memory_highwater
    prof, net, sol, Q = _saturating_instance(S=4, Q=12)
    for pol in ("fifo", "1f1b"):
        rep = simulate_plan(prof, net, sol, 1, num_microbatches=Q,
                            policy=pol, engine="event")
        assert stage_activation_highwater(rep.records) == \
            memory_highwater(4, Q, pol)
    # and with fewer micro-batches than stages the claims clip at Q
    small = simulate_plan(prof, net, sol, 1, num_microbatches=2,
                          policy="1f1b", engine="event")
    assert stage_activation_highwater(small.records) == \
        memory_highwater(4, 2, "1f1b")


def test_1f1b_trades_latency_for_memory():
    prof, net, sol, Q = _saturating_instance(S=4, Q=12)
    fifo = simulate_plan(prof, net, sol, 1, num_microbatches=Q,
                         policy="fifo")
    one = simulate_plan(prof, net, sol, 1, num_microbatches=Q,
                        policy="1f1b")
    assert one.L_t >= fifo.L_t - 1e-9          # admission can only delay
    hw_f = stage_activation_highwater(fifo.records)
    hw_1 = stage_activation_highwater(one.records)
    assert all(hw_1[j] <= hw_f[j] for j in hw_f)
    assert hw_1[0] < hw_f[0]                   # strictly fewer live buffers


def test_zero_microbatches_empty_report_on_both_engines():
    prof, net, sol, b, _ = random_instance(3)
    for pol in ("fifo", "1f1b"):
        for eng in ("event", "vectorized"):
            rep = simulate_plan(prof, net, sol, b, num_microbatches=0,
                                policy=pol, engine=eng)
            assert rep.num_microbatches == 0
            assert len(rep.mb_complete) == 0 and rep.records == []
            assert rep.L_t == 0.0 and rep.resource_busy == {}


def test_single_microbatch_identical_across_policies_and_engines():
    prof, net, sol, b, _ = random_instance(3)
    want = fill_latency(prof, net, sol, b)
    for pol in ("fifo", "1f1b"):
        for eng in ("event", "auto"):
            rep = simulate_plan(prof, net, sol, b, B=b, policy=pol,
                                engine=eng)
            assert rep.num_microbatches == 1
            assert rep.T_i == 0.0
            assert rep.L_t == pytest.approx(want, rel=1e-9)


def test_vectorized_report_timeline_and_lazy_records():
    prof, net, sol, b, B = random_instance(5)
    rep = simulate_plan(prof, net, sol, b, B=B, engine="vectorized")
    assert rep.engine == "vectorized" and rep.timeline is not None
    Q, R = rep.timeline.starts.shape
    assert Q == rep.num_microbatches
    assert len(rep.records) == Q * R             # materialized on demand
    assert rep.records is rep.records            # and cached
    # the dense timeline respects chain order and non-negative service
    assert np.all(rep.timeline.ends >= rep.timeline.starts - 1e-12)
    assert np.all(np.diff(rep.timeline.ends, axis=1) >= -1e-12)


# ---------------------------------------------------------------------------
# Scenario edge cases: zero-length segments/windows, overlapping windows
# ---------------------------------------------------------------------------

def test_piecewise_coalesces_zero_length_segments():
    tr = piecewise((0.0, 1.0, 1.0, 2.0), (1.0, 99.0, 2.0, 3.0))
    assert tr.times == (0.0, 1.0, 2.0)
    assert tr.values == (1.0, 2.0, 3.0)          # last value wins at t=1
    # the strict dataclass keeps rejecting non-increasing breakpoints
    with pytest.raises(ValueError, match="strictly increasing"):
        PiecewiseTrace((0.0, 1.0, 1.0), (1.0, 2.0, 3.0))


def test_zero_length_windows_are_identity(paper_plan):
    prof, net, plan = paper_plan
    base = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B)
    node = plan.solution.placement[1]
    scen = NetworkScenario().with_straggler(node, 2.0, 2.0, 8.0)
    scen = scen.with_outage(plan.solution.placement[0], node, 1.0, 1.0)
    same = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B,
                         scenario=scen)
    assert same.L_t == pytest.approx(base.L_t, rel=1e-12)


def test_outage_overlapping_straggler_compounds(paper_plan):
    """An outage window overlapping a straggler window on the same span:
    the run stays finite, and the combination is at least as slow as either
    perturbation alone (slower resources cannot speed a FIFO pipeline)."""
    prof, net, plan = paper_plan
    base = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B)
    node = plan.solution.placement[1]
    a = plan.solution.placement[0]
    t_mid = 0.5 * base.L_t
    strag = NetworkScenario().with_straggler(node, 0.0, t_mid, 6.0)
    outage = NetworkScenario().with_outage(a, node, 0.25 * base.L_t, t_mid)
    both = strag.with_outage(a, node, 0.25 * base.L_t, t_mid)
    r_s = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B,
                        scenario=strag)
    r_o = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B,
                        scenario=outage)
    r_b = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B,
                        scenario=both)
    assert np.isfinite(r_b.L_t)
    assert r_b.L_t >= max(r_s.L_t, r_o.L_t) - 1e-9
    # ... under 1F1B admission too
    r_b1 = simulate_plan(prof, net, plan.solution, plan.b, B=plan.B,
                         scenario=both, policy="1f1b")
    assert np.isfinite(r_b1.L_t) and r_b1.L_t >= r_b.L_t - 1e-9
