"""Plain-numpy oracle for ``repro.core.shortest_path._sweep``.

A standalone re-statement of the two-stage layered relaxation for a
*single* graph and a batch of thresholds, one threshold at a time, with no
workspace buffers, no parent tracking and no slice axis — small enough to
read side-by-side with the kernel it checks (tests/test_planner_batched.py).
"""

from __future__ import annotations

import numpy as np

_INF = np.inf


def sweep_ref(Ccom, Bcom, Sseg, Bseg, src_cost, src_beta, K, ts,
              mode: str = "sum") -> np.ndarray:
    """Best terminal value per threshold.

    Layouts: ``Ccom/Bcom[n, i, m]``, ``Sseg/Bseg[i, m, j]``,
    ``src_cost/src_beta[i]`` (structural masks pre-folded, as after
    ``_LayeredDP.rebind``).  ``mode="sum"`` is (+, min) shortest path among
    edges with beta <= t; ``mode="max"`` is (max, min) minimal bottleneck."""
    ts = np.asarray(ts, dtype=float)
    S = ts.shape[0]
    N, I1 = Ccom.shape[0], Ccom.shape[1]
    I = I1 - 1
    op = np.add if mode == "sum" else np.maximum
    src_val = src_cost if mode == "sum" else src_beta
    Vc = Ccom if mode == "sum" else Bcom
    Vs = Sseg if mode == "sum" else Bseg

    best = np.full(S, _INF)
    for s in range(S):
        t = ts[s]
        Vc_m = np.where(Bcom <= t, Vc, _INF)
        Vs_m = np.where(Bseg <= t, Vs, _INF)
        dist = np.full((N, I1), _INF)
        dist[0] = np.where(src_beta <= t, src_val, _INF)
        if np.isfinite(dist[0, I]):
            best[s] = dist[0, I]
        for _k in range(2, K + 1):
            A = op(dist[:, :, None], Vc_m).min(axis=0)        # (I1, N)
            nd = op(A[:, :, None], Vs_m).min(axis=0)          # (N, I1)
            dist = nd
            if N > 1:
                best[s] = min(best[s], nd[1:, I].min())
            if not np.isfinite(nd).any():
                break
    return best
