"""Algorithm 1 — bottleneck-aware shortest path for the MSP problem.

The MSP objective (P4) is  min over paths of  T_f(path) + xi(b) * T_1(path)
with T_1 = the path's bottleneck (max edge beta) — a combined min-sum +
min-max problem (Minoux 1989).  Exact strategy: dist(t) — the min-sum value
restricted to edges with beta <= t — is a non-increasing step function that
only changes at the distinct bottleneck values B = {beta(e)}, and

    OPT  =  min over t in B of  dist(t) + xi * t

(attained at t = the bottleneck of an optimal path).  Two solvers share one
layered-DP kernel and return bit-identical results:

``solver="scan"`` (the reference implementation, legacy control flow):
  1. binary-search the smallest feasible t (feasibility monotone in t)
  2. scan B ascending, one kernel sweep per threshold, objective
     dist(t) + xi * beta(path_t); break once dist(inf) + xi * t >= best
     (the paper's admissible lower-bound pruning, DESIGN.md §6)

``solver="batched"`` (the default; ISSUE 3 tentpole):
  1. one sweep at t = inf  ->  dist(inf) and the unrestricted path
  2. one *min-max* sweep   ->  beta* = the smallest feasible threshold
     (replaces the binary search: the same kernel with (max, min) algebra)
  3. the admissible window [beta*, (UB - dist(inf)) / xi] of thresholds is
     stacked as a leading axis and ONE masked broadcast min-plus sweep
     returns dist(t) for every candidate simultaneously
  4. argmin over dist(t) + xi * t, one reconstruction sweep at the winner

The kernel itself is a *two-stage* relaxation per DAG layer — first the
communication hop over (n, i, m), then the segment extension over (i, m, j)
— which is O(N^2 I + N I^2) per layer instead of the O(N^2 I^2) dense edge
tensor, and accepts a leading "slice" axis of independent (threshold,
micro-batch) instances.  Because both solvers call the same kernel with the
same float arithmetic and the same argmin tie-breaking, ``batched`` and
``scan`` agree bit-for-bit on (objective, cuts, placement, T_1) — asserted
by the standing randomized cross-check in tests/test_msp.py.

Restrictions (fixed cuts / fixed placement / ordered TPU stages) are
expressed as per-segment masks so the same solver powers the RC+OP / RP+OC
baselines and the TPU stage planner.  ``Planner`` caches the b-independent
``GraphFactory`` precomputation and the DP buffers so BCD iterations and
the b-sweep of ``exhaustive_joint`` (``Planner.solve_many``) reuse them.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Sequence

import numpy as np

from repro import obs

from . import latency as L
from .latency import SplitSolution
from .msp_graph import GraphFactory, MSPGraph, build_graph
from .network import EdgeNetwork
from .profiles import ModelProfile

#: default Algorithm-1 solver; "scan" is the legacy reference implementation
DEFAULT_SOLVER = "batched"

_INF = np.inf


@dataclasses.dataclass
class MSPResult:
    solution: SplitSolution
    objective: float        # T_f + xi * T1  as searched (paper objective)
    T_f: float              # min-sum part of the searched objective
    T_1: float              # bottleneck of the chosen path (searched beta)
    L_t: float              # true Eq. (14) latency of the solution
    T_i_true: float         # true Eq. (13) interval (with co-location sums)
    b: int
    B: int
    thresholds_scanned: int = 0   # total DP kernel sweeps (see note below)
    feasible: bool = True
    solver: str = ""

    # ``thresholds_scanned`` counts *every* DP sweep the solve performed —
    # the full-graph run, binary-search probes, per-threshold scan sweeps,
    # min-max sweeps and reconstructions alike; a batched multi-threshold
    # kernel invocation counts as 1 (ISSUE 3: the old accounting omitted
    # the binary search and the full-graph run, understating planner work).


# ---------------------------------------------------------------------------
# The shared layered-DP kernel
# ---------------------------------------------------------------------------

class _SweepResult:
    __slots__ = ("best_val", "best_k", "best_m", "parents", "stack")

    def __init__(self, best_val, best_k, best_m, parents, stack=None):
        self.best_val, self.best_k, self.best_m = best_val, best_k, best_m
        self.parents = parents
        self.stack = stack          # per-layer dist copies (want_stack=True)


def _ws_get(ws: dict, name: str, shape: tuple, dtype) -> np.ndarray:
    """Workspace buffer, reused across layers and across sweep calls."""
    a = ws.get(name)
    if a is None or a.shape != shape or a.dtype != dtype:
        a = np.empty(shape, dtype=dtype)
        ws[name] = a
    return a


def _sweep(Ccom, Bcom, Sseg, Bseg, src_cost, src_beta, K, ts, *,
           mode="sum", masks=None, want_parents=False, want_stack=False,
           ws=None):
    """Threshold-batched layered-DP sweep over the (k, n, i) DAG.

    Tensor layouts (a leading slice axis of size 1 broadcasts, size S runs
    S independent instances — thresholds and/or per-b graphs):

      Ccom/Bcom[s, n, i, m]  comm cost / bottleneck crossing cut i, n -> m
                             (structurally inf for m == n and m == 0)
      Sseg/Bseg[s, i, m, j]  segment (i, j] on node m
      src_cost/src_beta[s, i]  client segment (0, i] (inf where disallowed)

    ``mode="sum"`` relaxes with (+, min) — shortest path among edges with
    beta <= ts[s].  ``mode="max"`` relaxes with (max, min) — the minimal
    achievable path bottleneck (min-max), used to find beta* in one sweep.

    Per layer the relaxation is two-stage:  A[s, i, m] = min over n of
    dist[s, n, i] (+|max) Ccom[s, n, i, m],  then  dist'[s, m, j] = min over
    i of A[s, i, m] (+|max) Sseg[s, i, m, j].  Ties break to the smallest n
    and then the smallest i (np.argmin takes the first minimum), identically
    for every slice count — which is what makes scan == batched exact.

    ``want_stack=True`` additionally collects the per-layer ``dist`` tensors
    (``stack[k - 2]`` = dist after layer k) so a path can be reconstructed
    host-side *after* the sweep (``_LayeredDP.backtrace``) without
    paying the argmin parent tracking — the warm-replan reconstruction path.
    """
    ts = np.asarray(ts, dtype=float)
    S = ts.shape[0]
    N, I1 = Ccom.shape[1], Ccom.shape[2]
    I = I1 - 1
    ws = {} if ws is None else ws
    src_val = src_cost if mode == "sum" else src_beta

    dist = np.full((S, N, I1), _INF)
    dist[:, 0, :] = np.where(src_beta <= ts[:, None], src_val, _INF)

    fin0 = np.isfinite(dist[:, 0, I])
    best_val = np.where(fin0, dist[:, 0, I], _INF)
    best_k = np.where(fin0, 1, 0)
    best_m = np.zeros(S, dtype=np.int64)
    parents = []
    stack = [] if want_stack else None

    # the threshold mask is layer-independent: fold beta > t edges to inf
    # ONCE per sweep instead of re-masking per layer (the per-layer work then
    # reduces to one broadcast op and one reduction per stage)
    Vc = Ccom if mode == "sum" else Bcom
    Vs = Sseg if mode == "sum" else Bseg
    if np.isfinite(ts).any():
        t4 = ts[:, None, None, None]
        Vc = np.where(Bcom <= t4, Vc, _INF)
        Vs = np.where(Bseg <= t4, Vs, _INF)
    op = np.add if mode == "sum" else np.maximum

    for k in range(2, K + 1):
        mc, msk = masks(k) if masks is not None else (None, None)
        # stage 1: communication hop (n, i) -> node m across cut i
        cand_c = _ws_get(ws, "cand_c", (S, N, I1, N), np.float64)
        op(dist[:, :, :, None], Vc, out=cand_c)
        if mc is not None:
            cand_c[:, ~mc] = _INF
        if want_parents:
            Ap = cand_c.argmin(axis=1).astype(np.int32)
            A = np.take_along_axis(cand_c, Ap[:, None], axis=1)[:, 0]
        else:
            A = cand_c.min(axis=1)                   # (S, I1, N)
        # stage 2: extend with segment (i, j] on node m
        cand_s = _ws_get(ws, "cand_s", (S, I1, N, I1), np.float64)
        op(A[:, :, :, None], Vs, out=cand_s)
        if msk is not None:
            cand_s[:, ~msk] = _INF
        if want_parents:
            Sp = cand_s.argmin(axis=1).astype(np.int32)
            nd = np.take_along_axis(cand_s, Sp[:, None], axis=1)[:, 0]
            parents.append((Ap, Sp))
        else:
            nd = cand_s.min(axis=1)                  # (S, N, I1)
        dist = nd
        if want_stack:
            stack.append(nd)                         # fresh array (no alias)
        if N > 1:
            term = nd[:, 1:, I]
            v = term.min(axis=1)
            upd = v < best_val
            if upd.any():
                best_val = np.where(upd, v, best_val)
                best_k = np.where(upd, k, best_k)
                best_m = np.where(upd, term.argmin(axis=1) + 1, best_m)
        if not np.isfinite(nd).any():
            break
    return _SweepResult(best_val, best_k, best_m, parents, stack)


def _slices_per_chunk(N: int, I1: int) -> int:
    """Cap the kernel's slice axis so one chunk's workspace stays ~64 MB."""
    return max(1, int(2 ** 23 // max(1, N * I1 * max(N, I1))))


def _walk_parents(parents, s: int, k: int, m: int, j: int) -> list:
    """Reconstruct the [(node, end_layer), ...] path for slice ``s``."""
    if k == 1:
        return [(0, j)]
    path = [(int(m), int(j))]
    for kk in range(k, 1, -1):
        Ap, Sp = parents[kk - 2]
        i = int(Sp[s, m, j])
        n = int(Ap[s, i, m])
        path.append((n, i))
        m, j = n, i
    path.reverse()
    return path


def _betas_from_arrays(Bcom, Bseg, src_beta, lo=-_INF, hi=_INF,
                       mask_c=None, mask_s=None) -> list:
    """Finite candidate bottleneck values max(Bcom, Bseg) within [lo, hi].

    Unmasked case: ``max(a, b)`` is always one of its arguments, so the
    distinct edge-beta *value set* is exactly

        {Bcom[n,i,m]  : Bcom[n,i,m] >= min_j Bseg[i,m,j]}  |
        {Bseg[i,m,j]  : Bseg[i,m,j] >= min_n Bcom[n,i,m]}

    (each side dominating some compatible partner on the shared (i, m)
    pairing) — computed in O(N I N + I N I) instead of materializing the
    O(N^2 I^2) dense max (ISSUE 9: this scan dominated the warm-replan
    wall-clock).  Masked (restricted) calls keep the dense chunked path."""
    vals = [src_beta[(src_beta >= lo) & (src_beta <= hi)
                     & np.isfinite(src_beta)]]
    if mask_c is None and mask_s is None:
        min_seg = Bseg.min(axis=2)                       # (I1, N) over (i, m)
        min_com = Bcom.min(axis=0)                       # (I1, N) over (i, m)
        a_ok = ((Bcom >= lo) & (Bcom <= hi) & np.isfinite(Bcom)
                & (Bcom >= min_seg[None]))
        b_ok = ((Bseg >= lo) & (Bseg <= hi) & np.isfinite(Bseg)
                & (Bseg >= min_com[:, :, None]))
        vals.append(Bcom[a_ok])
        vals.append(Bseg[b_ok])
        return vals
    N = Bcom.shape[0]
    chunk = max(1, int(2 ** 22 // max(1, Bseg.size)))
    for n0 in range(0, N, chunk):
        dense = np.maximum(Bcom[n0:n0 + chunk, :, :, None], Bseg[None])
        if mask_c is not None:
            dense = np.where(mask_c[n0:n0 + chunk, :, :, None], dense, _INF)
        if mask_s is not None:
            dense = np.where(mask_s[None], dense, _INF)
        sel = dense[(dense >= lo) & (dense <= hi) & np.isfinite(dense)]
        vals.append(sel)
    return vals


class _LayeredDP:
    """Rebindable two-stage DP over one MSPGraph (see ``_sweep``).

    Structural masks (servers only for k >= 2, n' != n per Eq. 21, the
    restrict_cuts / restrict_placement selections) and workspace buffers are
    built once; ``rebind`` swaps in a new micro-batch's cost tensors without
    reallocating them (ISSUE 3: reuse across BCD iterations and b-sweeps).
    """

    def __init__(self, g: MSPGraph, K: int,
                 restrict_cuts: Sequence[int] | None = None,
                 restrict_placement: Sequence[int] | None = None):
        self.K = K
        self.restrict_cuts = tuple(restrict_cuts) if restrict_cuts else None
        self.restrict_placement = (tuple(restrict_placement)
                                   if restrict_placement else None)
        self._mask_cache: dict = {}
        self._ws: dict = {}
        self.rebind(g)

    @property
    def restricted(self) -> bool:
        return (self.restrict_cuts is not None or
                self.restrict_placement is not None)

    def rebind(self, g: MSPGraph) -> "_LayeredDP":
        self.g = g
        self.N, self.I = g.N, g.I
        idx = np.arange(self.N)
        # comm-stage tensors over (n, i, m); destinations must be servers
        Ccom = np.ascontiguousarray(g.comm_cost.transpose(1, 0, 2))
        Bcom = np.ascontiguousarray(g.comm_beta.transpose(1, 0, 2))
        Ccom[:, :, 0] = _INF
        Bcom[:, :, 0] = _INF
        Ccom[idx, :, idx] = _INF                     # n' != n (Eq. 21)
        Bcom[idx, :, idx] = _INF
        # seg-stage tensors over (i, m, j)
        Sseg = np.ascontiguousarray(g.seg_cost.transpose(1, 0, 2))
        Bseg = np.ascontiguousarray(g.seg_beta.transpose(1, 0, 2))
        src_ok = np.isfinite(g.src_cost)
        if self.restrict_cuts is not None:
            sel = np.zeros_like(src_ok)
            sel[self.restrict_cuts[0]] = True
            src_ok = src_ok & sel
        self._Ccom, self._Bcom = Ccom[None], Bcom[None]
        self._Sseg, self._Bseg = Sseg[None], Bseg[None]
        self._src_cost = np.where(src_ok, g.src_cost, _INF)[None]
        self._src_beta = np.where(src_ok, g.src_beta, _INF)[None]
        self._dense_beta = None          # legacy dense edge betas, on demand
        return self

    # -- restriction masks ---------------------------------------------------
    def _masks(self, k: int):
        """(comm mask over (n,i,m), seg mask over (i,m,j)) for layer k."""
        got = self._mask_cache.get(k)
        if got is not None:
            return got
        I1, N = self.I + 1, self.N
        mc = ms = None
        if self.restrict_cuts is not None:
            prev, cur = self.restrict_cuts[k - 2], self.restrict_cuts[k - 1]
            mc = np.zeros((N, I1, N), dtype=bool)
            mc[:, prev, :] = True
            ms = np.zeros((I1, N, I1), dtype=bool)
            ms[prev, :, cur] = True
        if self.restrict_placement is not None:
            pn = self.restrict_placement[k - 2]
            cn = self.restrict_placement[k - 1]
            mc2 = np.zeros((N, I1, N), dtype=bool)
            mc2[pn, :, cn] = True
            mc = mc2 if mc is None else (mc & mc2)
            ms2 = np.zeros((I1, N, I1), dtype=bool)
            ms2[:, cn, :] = True
            ms = ms2 if ms is None else (ms & ms2)
        self._mask_cache[k] = (mc, ms)
        return mc, ms

    # -- sweeps --------------------------------------------------------------
    def sweep(self, ts, *, mode="sum", want_parents=False,
              want_stack=False) -> _SweepResult:
        return _sweep(self._Ccom, self._Bcom, self._Sseg, self._Bseg,
                      self._src_cost, self._src_beta, self.K,
                      np.atleast_1d(np.asarray(ts, dtype=float)),
                      mode=mode, masks=self._masks if self.restricted else None,
                      want_parents=want_parents, want_stack=want_stack,
                      ws=self._ws)

    def run(self, t: float):
        """Shortest path with all edge betas <= t. Returns (dist, path)."""
        out = self.sweep([t], want_parents=True)
        if out.best_k[0] == 0:
            return math.inf, None
        path = _walk_parents(out.parents, 0, int(out.best_k[0]),
                             int(out.best_m[0]), self.I)
        return float(out.best_val[0]), path

    def backtrace(self, stack, t: float, k: int, m: int) -> list:
        """Reconstruct the path ending at state ``(k, m, I)`` of a sweep at
        threshold ``t`` from its per-layer dist stack (``want_stack=True``).

        ``stack[k-2]`` is dist *after* layer k; layer 1 is the source row.
        At each step the parent ``(n, i)`` of state ``(k, m, j)`` is found by
        re-running the two-stage relaxation for the single needed column and
        taking ``np.argmin`` — the *same array* the kernel argmin'd over when
        ``want_parents`` was set, so the first-minimum tie-breaking is
        reproduced exactly."""
        j = self.I
        if k == 1:
            return [(0, j)]
        Ccom, Bcom = self._Ccom[0], self._Bcom[0]
        Sseg, Bseg = self._Sseg[0], self._Bseg[0]
        path = [(int(m), j)]
        for kk in range(k, 1, -1):
            if kk >= 3:
                prev = stack[kk - 3]                       # dist after kk-1
            else:
                prev = np.full((self.N, self.I + 1), _INF)
                prev[0] = np.where(self._src_beta[0] <= t,
                                   self._src_cost[0], _INF)
            Vc = np.where(Bcom[:, :, m] <= t, Ccom[:, :, m], _INF)
            A = (prev + Vc).min(axis=0)                    # (I1,)
            Vs = np.where(Bseg[:, m, j] <= t, Sseg[:, m, j], _INF)
            i = int(np.argmin(A + Vs))
            n = int(np.argmin(prev[:, i] + Vc[:, i]))
            path.append((n, i))
            m, j = n, i
        path.reverse()
        return path

    def run_dense(self, t: float):
        """Legacy reference sweep: materializes the dense (i, n, m, j) edge
        tensor per layer per threshold — the pre-ISSUE-3 Algorithm-1 inner
        loop that ``solver="scan"`` keeps as the cross-validation baseline.

        Bit-identical to :meth:`run`: the edge weight is grouped as
        ``(dist + comm) + seg`` and the argmin flattens (i, n)-major, which
        reproduces the two-stage kernel's float rounding and tie-breaking
        exactly (addition of a shared addend preserves float ordering)."""
        N, I = self.N, self.I
        I1 = I + 1
        Ccom_inm = self._Ccom[0].transpose(1, 0, 2)      # (I1, N, N)
        Sseg = self._Sseg[0]                             # (I1, N, I1)
        if self._dense_beta is None:
            self._dense_beta = np.maximum(
                self._Bcom[0].transpose(1, 0, 2)[:, :, :, None],
                self._Bseg[0][:, None, :, :])
        dist = np.full((N, I1), _INF)
        dist[0, :] = np.where(self._src_beta[0] <= t, self._src_cost[0], _INF)
        best_val, best_state = _INF, None
        if np.isfinite(dist[0, I]):
            best_val, best_state = float(dist[0, I]), (1, 0, I)
        parents = []
        for k in range(2, self.K + 1):
            tmp = dist.T[:, :, None] + Ccom_inm          # (I1, N, N) [i,n,m]
            cand = tmp[:, :, :, None] + Sseg[:, None, :, :]   # (I1,N,N,I1)
            ok = self._dense_beta <= t
            if self.restricted:
                mc, msk = self._masks(k)
                if mc is not None:
                    ok = ok & mc.transpose(1, 0, 2)[:, :, :, None]
                if msk is not None:
                    ok = ok & msk[:, None, :, :]
            cand = np.where(ok, cand, _INF)
            flat = cand.reshape(I1 * N, N, I1)
            nd = flat.min(axis=0)
            parents.append(flat.argmin(axis=0))          # encodes i * N + n
            dist = nd
            if N > 1:
                v = nd[1:, I].min()
                if v < best_val:
                    best_val = float(v)
                    best_state = (k, 1 + int(nd[1:, I].argmin()), I)
            if not np.isfinite(nd).any():
                break
        if best_state is None:
            return math.inf, None
        k, m, j = best_state
        path = [(m, j)]
        while k >= 2:
            p = int(parents[k - 2][m, j])
            i, n = divmod(p, N)
            path.append((n, i))
            m, j, k = n, i, k - 1
        path.reverse()
        return best_val, path

    def dist_at(self, ts) -> np.ndarray:
        """dist(t) for every threshold in ``ts`` — one batched sweep
        (slice-chunked so the workspace stays memory-bounded on instances
        with weak pruning)."""
        ts = np.atleast_1d(np.asarray(ts, dtype=float))
        per = _slices_per_chunk(self.N, self.I + 1)
        if len(ts) <= per:
            return self.sweep(ts).best_val
        out = np.empty(len(ts))
        for c0 in range(0, len(ts), per):
            out[c0:c0 + per] = self.sweep(ts[c0:c0 + per]).best_val
        return out

    def min_bottleneck(self) -> float:
        """beta* = min over feasible paths of the path bottleneck, via one
        (max, min) sweep — replaces the legacy feasibility binary search."""
        out = self.sweep([_INF], mode="max")
        return float(out.best_val[0])

    # -- candidate thresholds ------------------------------------------------
    def betas_window(self, lo: float, hi: float) -> np.ndarray:
        """Sorted distinct candidate bottleneck values within [lo, hi]."""
        Bcom, Bseg = self._Bcom[0], self._Bseg[0]
        src_beta = self._src_beta[0]
        if not self.restricted:
            vals = _betas_from_arrays(Bcom, Bseg, src_beta, lo, hi)
        else:
            vals = [src_beta[(src_beta >= lo) & (src_beta <= hi)
                             & np.isfinite(src_beta)]]
            for k in range(2, self.K + 1):
                mc, msk = self._masks(k)
                vals += _betas_from_arrays(Bcom, Bseg, src_beta, lo, hi,
                                           mask_c=mc, mask_s=msk)[1:]
        if not vals:
            return np.empty(0)
        return np.unique(np.concatenate([np.atleast_1d(v) for v in vals]))

    def all_betas(self) -> np.ndarray:
        return self.betas_window(-_INF, _INF)


# ---------------------------------------------------------------------------
# Warm-replan helper: reprice a hinted path in the kernel's float order
# ---------------------------------------------------------------------------

def _reprice_dp_order(g: MSPGraph, path) -> tuple:
    """(cost, beta) of ``path`` on graph ``g`` with the DP's accumulation
    order ``(dist + comm) + seg`` — bit-equal to the kernel's dist."""
    n0, i0 = path[0]
    cost = float(g.src_cost[i0])
    beta = float(g.src_beta[i0])
    prev_n, prev_i = n0, i0
    for (n, i) in path[1:]:
        cost = (cost + float(g.comm_cost[prev_i, prev_n, n])) \
            + float(g.seg_cost[n, prev_i, i])
        beta = max(beta, float(g.comm_beta[prev_i, prev_n, n]),
                   float(g.seg_beta[n, prev_i, i]))
        prev_n, prev_i = n, i
    return cost, beta


# ---------------------------------------------------------------------------
# The reusable planner: factory + DP caches + both solver strategies
# ---------------------------------------------------------------------------

class Planner:
    """Reusable Algorithm-1 engine for one (profile, network, memory model).

    Holds the :class:`~repro.core.msp_graph.GraphFactory` (b-independent
    precomputation) plus per-restriction DP buffers, so repeated solves —
    BCD iterations, baseline restarts, the exhaustive b-sweep — share all
    structural work.  ``solve`` is one Algorithm-1 call; ``solve_many``
    batches a whole micro-batch sweep through the same kernel.
    """

    def __init__(self, profile: ModelProfile, net: EdgeNetwork,
                 memory_model: str = "paper"):
        self.profile, self.net = profile, net
        self.memory_model = memory_model
        self.factory = GraphFactory(profile, net, memory_model)
        self._graphs: dict = {}
        self._dps: dict = {}
        self._solved: dict = {}
        self._hints: dict = {}          # (b, B, K) -> warm-start hint

    # -- caches -------------------------------------------------------------
    def graph(self, b: int) -> MSPGraph:
        g = self._graphs.get(b)
        if g is None:
            obs.inc("planner.graph_cache_miss")
            g = self.factory.graph(b)
            self._graphs[b] = g
        else:
            obs.inc("planner.graph_cache_hit")
        return g

    def _dp(self, b: int, K: int, rc, rp) -> _LayeredDP:
        key = (K, rc, rp)
        g = self.graph(b)
        dp = self._dps.get(key)
        if dp is None:
            obs.inc("planner.dp_cache_miss")
            dp = _LayeredDP(g, K, rc, rp)
            self._dps[key] = dp
        else:
            obs.inc("planner.dp_cache_hit")
            if dp.g is not g:
                dp.rebind(g)
        return dp

    def default_K(self, K: int | None) -> int:
        if K is not None:
            return K
        return min(1 + self.net.num_servers, self.profile.num_layers)

    # -- incremental updates (ISSUE 9 tentpole) -----------------------------
    def update(self, delta) -> "Planner":
        """Apply a single-resource delta *in place* and invalidate exactly
        what it touched — the warm-replan entry point.

        ``delta`` is duck-typed against the ``ft.coordinator`` events:

          - ``RateChange``-like (``n_from``/``n_to``/``factor``): the rate
            mutation is replicated float-op-for-float-op, the factory's rate
            views are swapped, and each cached graph's comm columns for the
            (n_from, n_to) **pair** (both directions use the link) are
            re-assembled via ``GraphFactory.comm_pair`` — bitwise equal to a
            cold rebuild on the mutated network.
          - ``Straggler``-like (``node``/``slowdown``): the node-speed
            mutation, patching that node's seg row (``seg_node``) and, for
            the client tier, the source vectors.
          - ``NodeFailure``-like (``server``): renumbering — everything is
            rebuilt on ``net.degraded([server])`` (shapes change).
          - ``Resync``-like (``net``): full rebuild on the snapshot.

        Warm-start hints survive a patch with their lower bounds scaled by
        ``r_min`` — the largest factor by which any edge weight may have
        *shrunk* (1/factor for a rate increase, the slowdown for a node
        speed-up, 1 otherwise), so the scaled values still lower-bound the
        new ``dist(inf)`` and ``beta*`` and the next ``solve`` runs one
        windowed sweep instead of a cold Algorithm 1 (proof sketch on
        ``_solve_warm``).  ``r_min`` compounds across successive updates:
        bounds only loosen, never break.  Returns ``self``.
        """
        if hasattr(delta, "server"):                      # NodeFailure
            obs.inc("planner.updates[rebuild]")
            self._rebuild(self.net.degraded([delta.server]))
            return self
        if hasattr(delta, "factor"):                      # RateChange
            obs.inc("planner.updates[rate]")
            rate = self.net.rate.copy()
            rate[delta.n_from, delta.n_to] *= delta.factor
            self.net = dataclasses.replace(self.net, rate=rate)
            self.factory.patch_rate(self.net)
            u, v = int(delta.n_from), int(delta.n_to)
            for b, g in list(self._graphs.items()):
                eff = self.factory.effective_batch(b)
                for (a, c) in {(u, v), (v, u)}:
                    cost, beta = self.factory.comm_pair(eff, a, c)
                    g.comm_cost[:, a, c] = cost
                    g.comm_beta[:, a, c] = beta
                # a NEW graph object (sharing the patched arrays) so cached
                # DPs see ``dp.g is not g`` and rebind their buffers
                self._graphs[b] = dataclasses.replace(g, net=self.net)
            r_min = min(1.0, 1.0 / delta.factor) if delta.factor > 0 else 0.0
            self._after_patch(r_min)
            return self
        if hasattr(delta, "slowdown"):                    # Straggler
            obs.inc("planner.updates[speed]")
            w = int(delta.node)
            self.net = dataclasses.replace(
                self.net,
                nodes=[dataclasses.replace(n, f=n.f / delta.slowdown)
                       if i == w else n
                       for i, n in enumerate(self.net.nodes)])
            self.factory.patch_node_speed(self.net)
            for b, g in list(self._graphs.items()):
                eff = self.factory.effective_batch(b)
                sc, sb = self.factory.seg_node(eff, w)
                g.seg_cost[w] = sc
                g.seg_beta[w] = sb
                kw = {"net": self.net}
                if w == 0:
                    kw["src_cost"] = sc[0].copy()
                    kw["src_beta"] = sb[0].copy()
                self._graphs[b] = dataclasses.replace(g, **kw)
            r_min = min(1.0, float(delta.slowdown))
            self._after_patch(r_min)
            return self
        if getattr(delta, "net", None) is not None:       # Resync snapshot
            obs.inc("planner.updates[rebuild]")
            self._rebuild(delta.net)
            return self
        raise TypeError(f"unsupported planner delta: {delta!r}")

    def _after_patch(self, r_min: float) -> None:
        """Invalidate what an in-place patch touched: the solve memos.
        Hints survive with their lower bounds scaled by ``r_min``."""
        self._solved.clear()
        for h in self._hints.values():
            h["lb_dist"] *= r_min
            h["lb_beta"] *= r_min

    def _rebuild(self, net: EdgeNetwork) -> None:
        """Full invalidation (renumbering / snapshot): new factory, drop
        every cache; hints die with the old node indices."""
        self.net = net
        self.factory = GraphFactory(self.profile, net, self.memory_model)
        self._graphs.clear()
        self._dps.clear()
        self._solved.clear()
        self._hints.clear()

    # -- result assembly ----------------------------------------------------
    def _finish(self, g: MSPGraph, dist, path, b, B, xi, sweeps, solver):
        profile, net = self.profile, self.net
        if path is None:
            return MSPResult(solution=SplitSolution((profile.num_layers,), (0,)),
                             objective=math.inf, T_f=math.inf, T_1=math.inf,
                             L_t=math.inf, T_i_true=math.inf, b=b, B=B,
                             thresholds_scanned=sweeps, feasible=False,
                             solver=solver)
        sol = SplitSolution(cuts=tuple(i for _, i in path),
                            placement=tuple(n for n, _ in path))
        T_f = L.fill_latency(profile, net, sol, b)
        T_i = L.pipeline_interval(profile, net, sol, b)
        beta_path = _path_bottleneck(g, path)
        return MSPResult(solution=sol, objective=dist + xi * beta_path,
                         T_f=T_f, T_1=beta_path, L_t=T_f + xi * T_i,
                         T_i_true=T_i, b=b, B=B, thresholds_scanned=sweeps,
                         solver=solver)

    # -- solvers ------------------------------------------------------------
    def solve(self, b: int, B: int, K: int | None = None,
              restrict_cuts: Sequence[int] | None = None,
              restrict_placement: Sequence[int] | None = None,
              solver: str | None = None) -> MSPResult:
        solver = solver or DEFAULT_SOLVER
        K = self.default_K(K)
        rc = tuple(restrict_cuts) if restrict_cuts else None
        rp = tuple(restrict_placement) if restrict_placement else None
        # result memo: Algorithm-1 solves are deterministic in these
        # arguments, and the BCD alternation (plus a sim-scored solve's
        # closed-form warm start) re-requests the same (b, B) repeatedly —
        # the convergence iteration alone re-solves the stabilized b
        key = (b, B, K, rc, rp, solver)
        hit = self._solved.get(key)
        if hit is not None:
            obs.inc("planner.solve_memo_hit")
            return hit
        obs.inc("planner.solve_memo_miss")
        with obs.span("planner.solve", b=b, B=B, solver=solver):
            dp = self._dp(b, K, rc, rp)
            g = self.graph(b)
            xi = L.num_fills(B, b)
            if solver == "scan":
                res = self._solve_scan(dp, g, b, B, xi)
            elif solver == "batched":
                res = None
                hint = (self._hints.get((b, B, K))
                        if rc is None and rp is None else None)
                if hint is not None and xi > 0:
                    res = self._solve_warm(dp, g, b, B, xi, hint)
                if res is not None:
                    obs.inc("planner.incremental_hits")
                else:
                    if rc is None and rp is None:
                        obs.inc("planner.cold_solves")
                    res = self._solve_batched(dp, g, b, B, xi)
            else:
                raise ValueError(
                    f"unknown solver {solver!r} (want 'scan'|'batched')")
        obs.inc("planner.dp_sweeps", res.thresholds_scanned)
        self._solved[key] = res
        return res

    def _solve_scan(self, dp: _LayeredDP, g: MSPGraph, b, B, xi) -> MSPResult:
        """Legacy Algorithm 1: binary search + ascending pruned scan, one
        dense-tensor DP sweep per probed threshold (``_LayeredDP.run_dense``).
        Kept as the reference implementation and benchmark baseline."""
        sweeps = 0

        def run(t):
            nonlocal sweeps
            sweeps += 1
            return dp.run_dense(t)

        if xi == 0:                            # no pipelining: pure min-sum
            dist, path = run(math.inf)
            return self._finish(g, dist, path, b, B, xi, sweeps, "scan")

        betas = dp.all_betas()
        if betas.size == 0:
            return self._finish(g, math.inf, None, b, B, xi, sweeps, "scan")
        dist_full, path_full = run(math.inf)
        if path_full is None:
            return self._finish(g, math.inf, None, b, B, xi, sweeps, "scan")

        # binary search the smallest feasible threshold (monotone in t)
        lo, hi = 0, len(betas) - 1
        while lo < hi:
            mid = (lo + hi) // 2
            d, _ = run(betas[mid])
            if math.isfinite(d):
                hi = mid
            else:
                lo = mid + 1

        best, best_pair = math.inf, None
        for idx in range(lo, len(betas)):
            t = float(betas[idx])
            if dist_full + xi * t >= best:      # admissible prune -> break
                break
            d, p = run(t)
            if p is None:
                continue
            beta_p = _path_bottleneck(g, p)     # actual path bottleneck <= t
            obj = d + xi * beta_p
            if obj < best:
                best, best_pair = obj, (d, p)
        if best_pair is None:
            return self._finish(g, math.inf, None, b, B, xi, sweeps, "scan")
        return self._finish(g, best_pair[0], best_pair[1], b, B, xi, sweeps,
                            "scan")

    def _solve_batched(self, dp: _LayeredDP, g: MSPGraph, b, B,
                       xi) -> MSPResult:
        """Threshold-batched Algorithm 1 (see module docstring)."""
        dist_full, path_full = dp.run(math.inf)
        sweeps = 1
        if xi == 0:
            return self._finish(g, dist_full, path_full, b, B, xi, sweeps,
                                "batched")
        if path_full is None:
            return self._finish(g, math.inf, None, b, B, xi, sweeps, "batched")

        beta_star = dp.min_bottleneck()        # smallest feasible threshold
        sweeps += 1
        d_star, p_star = dp.run(beta_star)
        sweeps += 1
        ub = min(dist_full + xi * _path_bottleneck(g, path_full),
                 d_star + xi * _path_bottleneck(g, p_star))
        cap = (ub - dist_full) / xi            # prune: dist_full + xi*t >= ub
        window = dp.betas_window(beta_star, cap * (1 + 1e-12) + 1e-12)
        if window.size == 0:                   # numerical corner: fall back
            window = np.array([beta_star])
        dvals = dp.dist_at(window)
        sweeps += 1
        j = int(np.argmin(dvals + xi * window))   # first minimum: smallest t
        t_hat = float(window[j])
        if t_hat == beta_star:
            d_hat, p_hat = d_star, p_star
        else:
            d_hat, p_hat = dp.run(t_hat)
            sweeps += 1
        if not dp.restricted and p_hat is not None:
            self._hints[(b, B, dp.K)] = {"lb_dist": dist_full,
                                         "lb_beta": beta_star,
                                         "path": list(p_hat)}
        return self._finish(g, d_hat, p_hat, b, B, xi, sweeps, "batched")

    def _solve_warm(self, dp: _LayeredDP, g: MSPGraph, b, B, xi,
                    hint: dict):
        """Warm-started Algorithm 1 from a surviving hint — bit-identical to
        the cold batched solve, in a fraction of its sweeps.

        The hint carries a known-valid path (the previous optimum, repriced
        here on the patched graph -> upper bound UB) and scaled lower bounds
        ``lb_dist <= dist(inf)`` and ``lb_beta <= beta*``.  Every global
        minimizer t of dist(t) + xi*t then lies in
        ``[lb_beta, (UB - lb_dist) / xi]``: t >= beta* >= lb_beta, and
        xi*t = OPT - dist(t) <= UB - dist(inf) <= UB - lb_dist.  The cold
        solver's window is pruned by the *same* argument with its own valid
        bounds, so both windows contain every global minimizer; the
        first-minimum argmin therefore lands on the same smallest minimizing
        threshold, and the reconstruction at that threshold runs the same
        kernel — same path, same floats (``tests/test_planner_update.py``
        asserts the end-to-end equality).  One windowed sweep + one
        single-threshold stack sweep replace the cold solve's 4-5 sweeps.

        Returns None (caller falls back to a cold solve) when the hinted
        path went infeasible or a numerical corner empties the window."""
        cost, beta_p = _reprice_dp_order(g, hint["path"])
        if not (math.isfinite(cost) and math.isfinite(beta_p)):
            return None
        ub = cost + xi * beta_p
        cap = (ub - hint["lb_dist"]) / xi
        window = dp.betas_window(hint["lb_beta"], cap * (1 + 1e-12) + 1e-12)
        if window.size == 0:
            return None
        # small windows (the common case: a local delta barely moves the
        # optimum) fuse the window sweep and the reconstruction sweep into
        # one want_stack dispatch; big windows keep the stack memory bounded
        # by sweeping values first and re-running only the argmin threshold
        fused = window.size <= 32
        if fused:
            out = dp.sweep(window, want_stack=True)
            dvals = out.best_val
        else:
            dvals = dp.dist_at(window)
        j = int(np.argmin(dvals + xi * window))   # first minimum: smallest t
        t_hat = float(window[j])
        if not math.isfinite(dvals[j]):
            return None
        if not fused:
            out = dp.sweep([t_hat], want_stack=True)
            j = 0
        if out.best_k[j] == 0:
            return None
        path = dp.backtrace([layer[j] for layer in out.stack], t_hat,
                            int(out.best_k[j]), int(out.best_m[j]))
        self._hints[(b, B, dp.K)]["path"] = list(path)
        return self._finish(g, float(out.best_val[j]), path, b, B, xi,
                            1 if fused else 2, "batched")

    # -- batched micro-batch sweep (exhaustive_joint's inner loop) ----------
    def solve_many(self, bs: Sequence[int], B: int,
                   K: int | None = None) -> list:
        """Algorithm 1 for every micro-batch size in ``bs`` at once.

        The b-axis rides the same kernel slice axis as the thresholds: the
        full-graph runs, the min-max beta* sweeps, the beta* probes, the
        stacked threshold windows and the reconstructions each execute as
        ONE multi-slice sweep across all b.  Results are bit-identical to
        ``[self.solve(b, B, K, solver="batched") for b in bs]`` (asserted in
        tests/test_msp.py)."""
        bs = list(bs)
        with obs.span("planner.solve_many", n=len(bs), B=B):
            results = self._solve_many(bs, B, K)
        obs.inc("planner.dp_sweeps",
                sum(r.thresholds_scanned for r in results))
        return results

    def _solve_many(self, bs: list, B: int, K: int | None = None) -> list:
        K = self.default_K(K)
        S = len(bs)
        N, I = len(self.net.nodes), self.profile.num_layers
        I1 = I + 1
        idx = np.arange(N)

        Ccom = np.empty((S, N, I1, N))
        Bcom = np.empty((S, N, I1, N))
        Sseg = np.empty((S, I1, N, I1))
        Bseg = np.empty((S, I1, N, I1))
        src_cost = np.empty((S, I1))
        src_beta = np.empty((S, I1))
        graphs = []
        for s, b in enumerate(bs):
            g = self.graph(b)
            graphs.append(g)
            Ccom[s] = g.comm_cost.transpose(1, 0, 2)
            Bcom[s] = g.comm_beta.transpose(1, 0, 2)
            Sseg[s] = g.seg_cost.transpose(1, 0, 2)
            Bseg[s] = g.seg_beta.transpose(1, 0, 2)
            src_cost[s] = g.src_cost
            src_beta[s] = g.src_beta
        Ccom[:, :, :, 0] = _INF
        Bcom[:, :, :, 0] = _INF
        Ccom[:, idx, :, idx] = _INF
        Bcom[:, idx, :, idx] = _INF

        xi = np.array([L.num_fills(B, b) for b in bs])
        inf_ts = np.full(S, _INF)

        def stacked(sel, ts, **kw):
            """Sweep the selected slices (gathered tensors) at thresholds ts."""
            sel = np.asarray(sel)
            return _sweep(Ccom[sel], Bcom[sel], Sseg[sel], Bseg[sel],
                          src_cost[sel], src_beta[sel], K,
                          np.asarray(ts, dtype=float), **kw)

        # phase A: full-graph runs for every b (one stacked sweep)
        outA = _sweep(Ccom, Bcom, Sseg, Bseg, src_cost, src_beta, K, inf_ts,
                      want_parents=True)
        paths_full = [
            _walk_parents(outA.parents, s, int(outA.best_k[s]),
                          int(outA.best_m[s]), I) if outA.best_k[s] else None
            for s in range(S)]

        results: list = [None] * S
        live = []                               # slices still being solved
        for s in range(S):
            if xi[s] == 0 or paths_full[s] is None:
                results[s] = self._finish(
                    graphs[s], float(outA.best_val[s]), paths_full[s],
                    bs[s], B, int(xi[s]), 1, "batched")
            else:
                live.append(s)
        if not live:
            return results

        # phase B: one (max, min) sweep -> beta* per live b, then one stacked
        # probe at beta* (parents -> the upper-bound path per b)
        outB = stacked(live, [_INF] * len(live), mode="max")
        beta_star = outB.best_val
        outP = stacked(live, beta_star, want_parents=True)
        paths_star, windows = [], []
        for q, s in enumerate(live):
            p_star = _walk_parents(outP.parents, q, int(outP.best_k[q]),
                                   int(outP.best_m[q]), I)
            paths_star.append(p_star)
            ub = min(float(outA.best_val[s])
                     + xi[s] * _path_bottleneck(graphs[s], paths_full[s]),
                     float(outP.best_val[q])
                     + xi[s] * _path_bottleneck(graphs[s], p_star))
            cap = (ub - float(outA.best_val[s])) / xi[s]
            w = _betas_from_arrays(Bcom[s], Bseg[s], src_beta[s],
                                   beta_star[q], cap * (1 + 1e-12) + 1e-12)
            w = np.unique(np.concatenate([np.atleast_1d(v) for v in w]))
            if w.size == 0:
                w = np.array([beta_star[q]])
            windows.append(w)

        # phase C: ONE stacked sweep over every (b, threshold) pair (chunked
        # so the slice axis stays memory-bounded), then argmin per b
        slice_b = np.concatenate(
            [np.full(len(w), s) for s, w in zip(live, windows)])
        slice_t = np.concatenate(windows)
        t_hat = np.empty(len(live))
        per_slice = _slices_per_chunk(N, I1)
        dvals = np.empty(len(slice_t))
        for c0 in range(0, len(slice_t), per_slice):
            c1 = min(c0 + per_slice, len(slice_t))
            dvals[c0:c1] = stacked(slice_b[c0:c1], slice_t[c0:c1]).best_val
        pos = 0
        for q, w in enumerate(windows):
            H = dvals[pos:pos + len(w)] + xi[live[q]] * w
            t_hat[q] = w[int(np.argmin(H))]
            pos += len(w)

        # phase D: one stacked reconstruction sweep at the winners; slices
        # whose winner IS beta* reuse the phase-B probe path instead (same
        # kernel, same threshold), exactly like the per-b solve — which also
        # keeps the 4-vs-5 sweep accounting identical to solve()
        need = [q for q in range(len(live)) if t_hat[q] != beta_star[q]]
        if need:
            outR = stacked([live[q] for q in need], t_hat[need],
                           want_parents=True)
        for r, q in enumerate(need):
            s = live[q]
            if outR.best_k[r] == 0:
                path = None
            else:
                path = _walk_parents(outR.parents, r, int(outR.best_k[r]),
                                     int(outR.best_m[r]), I)
            results[s] = self._finish(graphs[s], float(outR.best_val[r]),
                                      path, bs[s], B, int(xi[s]), 5, "batched")
        for q, s in enumerate(live):
            if results[s] is None:                  # t_hat == beta*
                results[s] = self._finish(graphs[s], float(outP.best_val[q]),
                                          paths_star[q], bs[s], B,
                                          int(xi[s]), 4, "batched")
        return results


def solve_msp(profile: ModelProfile, net: EdgeNetwork, b: int, B: int,
              K: int | None = None, memory_model: str = "paper",
              restrict_cuts: Sequence[int] | None = None,
              restrict_placement: Sequence[int] | None = None,
              solver: str | None = None,
              planner: Planner | None = None) -> MSPResult:
    """Algorithm 1.  Returns the optimal (x, y) for fixed micro-batch b.

    ``solver``: "batched" (default) or "scan" (the legacy reference — same
    results, more sweeps).  Pass a :class:`Planner` to amortize the graph
    factory and DP buffers across calls (it must have been built for the
    same memory model)."""
    if planner is not None and planner.memory_model != memory_model:
        raise ValueError(
            f"planner was built with memory_model={planner.memory_model!r} "
            f"but solve_msp was called with {memory_model!r}")
    pl = planner if planner is not None else Planner(profile, net, memory_model)
    return pl.solve(b, B, K=K, restrict_cuts=restrict_cuts,
                    restrict_placement=restrict_placement, solver=solver)


def _path_bottleneck(g: MSPGraph, path: list) -> float:
    """Max component (paper-mode T_1) along a reconstructed path."""
    (n0, i0) = path[0]
    beta = float(g.src_beta[i0])
    prev_n, prev_i = n0, i0
    for (n, i) in path[1:]:
        beta = max(beta, g.edge_beta(prev_n, prev_i, n, i))
        prev_n, prev_i = n, i
    return beta


def path_cost(g: MSPGraph, path: list) -> float:
    (n0, i0) = path[0]
    c = float(g.src_cost[i0])
    prev_n, prev_i = n0, i0
    for (n, i) in path[1:]:
        c += g.edge_cost(prev_n, prev_i, n, i)
        prev_n, prev_i = n, i
    return c


# ---------------------------------------------------------------------------
# Brute-force verifiers (tests / Fig. 7 "optimal" baseline on small instances)
# ---------------------------------------------------------------------------

def enumerate_solutions(profile: ModelProfile, net: EdgeNetwork, K: int):
    """Yield every feasible-shaped SplitSolution (cuts + placement)."""
    I = profile.num_layers
    servers = list(net.server_indices())
    for s in range(1, K + 1):                 # number of non-empty segments
        for cuts in itertools.combinations(range(1, I), s - 1):
            cuts = cuts + (I,)
            if s == 1:
                yield SplitSolution(cuts=cuts, placement=(0,))
                continue
            for placing in itertools.product(servers, repeat=s - 1):
                placement = (0,) + placing
                if any(placement[a] == placement[a + 1] for a in range(s - 1)):
                    continue
                yield SplitSolution(cuts=cuts, placement=placement)


def brute_force_msp(profile: ModelProfile, net: EdgeNetwork, b: int, B: int,
                    K: int, objective: str = "paper",
                    memory_model: str = "paper"):
    """Exhaustive MSP search.  ``objective='paper'`` replicates Algorithm 1's
    per-segment semantics (for optimality tests); ``'true'`` evaluates the
    full Eq. (13)/(14) with co-location sums and joint memory (C8)."""
    xi = L.num_fills(B, b)
    g = build_graph(profile, net, b, memory_model) if objective == "paper" else None
    best, best_sol = math.inf, None
    for sol in enumerate_solutions(profile, net, K):
        if objective == "paper":
            path = list(zip(sol.placement, sol.cuts))
            ok = np.isfinite(g.src_cost[path[0][1]])
            prev = path[0]
            cost = float(g.src_cost[path[0][1]])
            beta = float(g.src_beta[path[0][1]])
            for (n, i) in path[1:]:
                c = g.edge_cost(prev[0], prev[1], n, i)
                if not math.isfinite(c):
                    ok = False
                    break
                cost += c
                beta = max(beta, g.edge_beta(prev[0], prev[1], n, i))
                prev = (n, i)
            if not ok:
                continue
            val = cost + xi * beta
        else:
            if not L.memory_feasible(profile, net, sol, b, memory_model):
                continue
            val = L.total_latency(profile, net, sol, b, B)
        if val < best:
            best, best_sol = val, sol
    return best, best_sol
