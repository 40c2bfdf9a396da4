"""Pallas min-plus kernel parity, in explicit interpret mode on the CPU.

The numpy ``_sweep`` stays the contract-bearing reference; the compile for
a TPU is covered by tests/test_tpu_compile.py.
"""

import numpy as np
import pytest

from repro.core import Planner, build_graph
from repro.core.shortest_path import _LayeredDP
from conftest import same_msp_result as _same_result, small_instance

from repro.kernels import minplus

pytestmark = pytest.mark.pallas


def _dp(seed, b=8, K=4):
    prof, net = small_instance(seed, num_layers=6, num_servers=3)
    return _LayeredDP(build_graph(prof, net, b), K)


@pytest.mark.parametrize("seed", [0, 1, 5])
@pytest.mark.parametrize("mode", ["sum", "max"])
def test_kernel_matches_ref_oracle(seed, mode):
    dp = _dp(seed)
    ts = dp.all_betas()[::3]
    args = (dp._Ccom[0], dp._Bcom[0], dp._Sseg[0], dp._Bseg[0],
            dp._src_cost[0], dp._src_beta[0], dp.K, ts)
    got = minplus.sweep_minplus(*args, mode=mode, interpret=True)
    want = minplus.sweep_ref(*args, mode=mode)
    finite = np.isfinite(want)
    assert (finite == np.isfinite(got)).all()
    assert np.allclose(got[finite], want[finite], rtol=1e-4)


@pytest.mark.parametrize("seed", [0, 2])
def test_kernel_matches_numpy_sweep(seed):
    """The end contract: kernel dist values match ``_LayeredDP.dist_at``
    within the float32 tolerance (bit-exact when x64 is on)."""
    dp = _dp(seed)
    ts = dp.all_betas()[::2]
    got = minplus.sweep_minplus(dp._Ccom[0], dp._Bcom[0], dp._Sseg[0],
                                dp._Bseg[0], dp._src_cost[0],
                                dp._src_beta[0], dp.K, ts, interpret=True)
    want = dp.dist_at(ts)
    finite = np.isfinite(want)
    assert (finite == np.isfinite(got)).all()
    assert np.allclose(got[finite], want[finite], rtol=1e-4)


def test_planner_backend_pallas_matches_numpy():
    prof, net = small_instance(3, num_layers=5, num_servers=3)
    for b in (4, 12):
        r_np = Planner(prof, net).solve(b, 32, solver="batched")
        r_pl = Planner(prof, net).solve(b, 32, solver="batched",
                                        backend="pallas-interpret")
        assert r_np.feasible == r_pl.feasible
        if r_np.feasible:
            # the window argmin may tie-break differently under float32,
            # but the repriced objective must agree to kernel tolerance
            assert r_pl.objective == pytest.approx(r_np.objective, rel=1e-4)
