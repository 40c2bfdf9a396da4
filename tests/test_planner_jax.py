"""JAX planner backend (ISSUE 9) — dtype contract, parity, counters.

Runtime companion to the hypothesis cross-check in tests/test_msp.py
(which skips wholesale when hypothesis is absent): seeded grids here run
unconditionally.
"""

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import Planner, build_graph
from repro.core import planner_jax
from repro.core.shortest_path import _LayeredDP
from conftest import same_msp_result as _same_result, small_instance


class _x64:
    """Temporarily force the x64 flag; restores the prior value on exit."""

    def __init__(self, enable: bool):
        self.enable = enable

    def __enter__(self):
        self.prev = jax.config.jax_enable_x64
        jax.config.update("jax_enable_x64", self.enable)

    def __exit__(self, *exc):
        jax.config.update("jax_enable_x64", self.prev)


# -- satellite: dtype detection -------------------------------------------


def test_sweep_dtype_tracks_x64_flag():
    with _x64(False):
        assert planner_jax.sweep_dtype() == "float32"
        assert planner_jax.parity_tolerance() > 0.0
    with _x64(True):
        assert planner_jax.sweep_dtype() == "float64"
        assert planner_jax.parity_tolerance() == 0.0


@pytest.mark.parametrize("enable_x64", [False, True])
def test_dist_at_jax_parity_both_modes(vgg_profile, paper_network,
                                       enable_x64):
    """_dist_at_jax honors the documented tolerance contract in both
    dtype modes: bit-exact under x64, rtol ``parity_tolerance()`` under
    the default float32 config."""
    g = build_graph(vgg_profile, paper_network, 16)
    dp = _LayeredDP(g, 7)
    betas = dp.all_betas()
    ts = betas[:: max(1, len(betas) // 24)]
    d_np = dp.dist_at(ts)
    with _x64(enable_x64):
        d_jx = dp.dist_at(ts, backend="jax")
        rtol = planner_jax.parity_tolerance()
    assert d_jx.dtype == np.float64          # host contract: always f64 out
    finite = np.isfinite(d_np)
    assert (finite == np.isfinite(d_jx)).all()
    if enable_x64:
        assert np.array_equal(d_np, d_jx)
    else:
        assert np.allclose(d_np[finite], d_jx[finite], rtol=rtol)


# -- parity: full solve / solve_many through the jitted pipeline ----------


@pytest.mark.parametrize("seed", [0, 1, 2, 7, 11])
def test_solve_backend_jax_matches_numpy(seed):
    prof, net = small_instance(seed, num_layers=5, num_servers=3)
    B = 32
    for b in (4, 13):
        r_np = Planner(prof, net).solve(b, B, solver="batched")
        r_jx = Planner(prof, net).solve(b, B, solver="batched",
                                        backend="jax")
        rtol = planner_jax.parity_tolerance()
        assert r_np.feasible == r_jx.feasible
        if not r_np.feasible:
            continue
        if rtol == 0.0:
            assert _same_result(r_np, r_jx), (r_np, r_jx)
        else:
            assert r_jx.objective == pytest.approx(r_np.objective, rel=rtol)
            assert r_jx.b == r_np.b


@pytest.mark.parametrize("seed", [0, 3, 9])
def test_solve_many_backend_jax_matches_numpy(seed):
    prof, net = small_instance(seed, num_layers=5, num_servers=3)
    B = 32
    bs = list(range(1, B + 1, 5))
    many_np = Planner(prof, net).solve_many(bs, B)
    many_jx = Planner(prof, net).solve_many(bs, B, backend="jax")
    rtol = planner_jax.parity_tolerance()
    assert len(many_np) == len(many_jx)
    for m_np, m_jx in zip(many_np, many_jx):
        assert m_np.feasible == m_jx.feasible
        if not m_np.feasible:
            continue
        if rtol == 0.0:
            assert _same_result(m_np, m_jx), (m_np, m_jx)
        else:
            assert m_jx.objective == pytest.approx(m_np.objective, rel=rtol)
            # the searched split itself must agree even in f32: a wrong
            # placement would show as a >1e-4 objective gap on reprice
            assert m_jx.b == m_np.b


def test_solve_many_backend_jax_bit_exact_under_x64():
    prof, net = small_instance(5, num_layers=6, num_servers=4)
    bs = [2, 7, 16, 31]
    many_np = Planner(prof, net).solve_many(bs, 32)
    with _x64(True):
        many_jx = Planner(prof, net).solve_many(bs, 32, backend="jax")
    for m_np, m_jx in zip(many_np, many_jx):
        assert _same_result(m_np, m_jx), (m_np, m_jx)


# -- counters --------------------------------------------------------------


def test_jax_dispatch_counter_increments():
    prof, net = small_instance(2, num_layers=5, num_servers=3)
    obs.reset()
    with obs.enabled_scope():
        Planner(prof, net).solve_many([4, 8], 32, backend="jax")
    assert obs.counter("planner.jax_dispatches") > 0
    assert obs.counter("planner.pallas_dispatches") == 0
    obs.reset()
