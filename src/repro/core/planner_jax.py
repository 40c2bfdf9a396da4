"""Batched JAX backend for Algorithm 1 — the ISSUE 9 tentpole.

Ports the full planning pipeline (GraphFactory broadcast assembly ->
layered-DP sweep -> threshold window -> argmin finish) to jit'd XLA with a
leading *slice* axis over arbitrary (micro-batch b, threshold t) pairs.

Three design decisions, each forced by measurement on the acceptance
instance (24 servers x 30 layers x B=64):

1. **Threshold-contiguous layout.**  The slice axis is the LAST axis of
   every tensor (``dist[n, i, s]``), so the per-layer min-plus relaxation
   vectorizes across slices.  A slice-first vmap was *slower* than numpy.

2. **On-the-fly graph assembly.**  Graph weights are never materialized per
   slice.  The kernel recomputes ``seg_cost``/``comm_cost`` entries inside
   the layer loop from b-independent *basis* tensors (workload tables, rate
   matrix, node constants — a few hundred KB, shared by every slice) and a
   per-slice effective-batch vector ``e[n, s]``.  This keeps the memory
   traffic of a 450-slice sweep near zero and lets one dispatch mix slices
   of different b — which is what lets ``solve_many`` run phases A-D as a
   handful of compiled dispatches instead of per-instance numpy sweeps.
   (Materializing masked per-slice tensors was measured 1.5-2x slower:
   the sweep becomes bandwidth-bound re-reading ~80 MB per layer.)

3. **No parent tracking on device.**  Reconstruction needs argmin parents,
   which double the numpy kernel's cost.  Instead the jax sweeps optionally
   return the per-layer ``dist`` stack (a few MB) and the path is
   reconstructed host-side by :func:`backtrace_stack` against a host mirror
   of the assembled graph — reproducing ``np.argmin``'s first-minimum
   tie-breaking exactly (see the proof note on :func:`backtrace_stack`).

Numerics: the kernel runs in jax's enabled dtype (float32 unless
``JAX_ENABLE_X64`` / ``jax.config.update("jax_enable_x64", True)``).  Under
x64 every arithmetic op mirrors the numpy reference bit-for-bit, so results
are exactly equal.  Under float32 the documented contract is: feasibility
matches, the returned path is a valid path whose *float64 repriced*
objective is within ``rtol=1e-4`` of the numpy optimum (asserted by the
randomized cross-check in tests/test_msp.py).  See
:func:`sweep_dtype` / :func:`parity_tolerance`.
"""

from __future__ import annotations

import math

import numpy as np

from repro import obs

from . import latency as L

_INF = np.inf

#: slice-axis padding buckets: pad S up to the next bucket so the number of
#: compiled kernel variants stays O(log S); larger sweeps are chunked.
_S_BUCKETS = (8, 16, 32, 64, 128)
_S_MAX = _S_BUCKETS[-1]          # chunk size: keeps worst-case bucket
#                                  padding under ~6% of a large sweep (a
#                                  512 cap padded e.g. 391 -> 512, wasting
#                                  a third of the largest dispatches)


def sweep_dtype() -> str:
    """The dtype the jax backend will actually compute in.

    jax silently truncates float64 requests to float32 unless x64 is
    enabled — the pre-ISSUE-9 ``_dist_at_jax`` documented this in a
    docstring but did not *detect* it (satellite task).  Returns
    ``"float64"`` iff jax will honor 64-bit, else ``"float32"``."""
    import jax
    return "float64" if jax.config.jax_enable_x64 else "float32"


def parity_tolerance() -> float:
    """Relative tolerance vs the numpy reference for the active dtype.

    0.0 under x64 (bit-exact contract); 1e-4 under float32 (covers ~K
    accumulated roundings through the DP plus the argmin near-tie slop)."""
    return 0.0 if sweep_dtype() == "float64" else 1e-4


# ---------------------------------------------------------------------------
# Device state: basis tensors + compiled sweep kernels per (factory, K, dtype)
# ---------------------------------------------------------------------------

class JaxDP:
    """Compiled batched DP over one GraphFactory's basis tensors.

    Holds the b-independent precomputation on device and a cache of jit'd
    sweep kernels keyed by (padded slice count, mode, want_stack).  Invalidate
    by dropping the object (Planner keys its cache on a factory epoch)."""

    def __init__(self, factory, K: int):
        import jax.numpy as jnp

        self.factory = factory
        self.K = K
        self.dtype = sweep_dtype()
        self.N, self.I = factory.N, factory.I
        dt = jnp.float64 if self.dtype == "float64" else jnp.float32
        self._dt = dt
        self.memory_model = factory.memory_model

        as_ = lambda a: jnp.asarray(np.asarray(a), dt)
        self.Wf = as_(factory.W_fp)
        self.Wb = as_(factory.W_bp)
        self.Mps = as_(factory.Mem_ps)
        self.Mact = as_(factory.Mem_act)
        self.Mstat = as_(factory.Mem_static)
        self.tri = jnp.asarray(factory.tri)
        self.rate = as_(factory.rate)
        self.rate_pos = jnp.asarray(factory.rate > 0)
        self.kappa = as_(factory.kappa)
        self.f = as_(factory.f)
        self.t0 = as_(factory.t0)
        self.t1 = as_(factory.t1)
        self.bth = as_(factory.b_th)
        self.mem = as_(factory.mem)
        self.fb1 = as_(factory.fb1)
        self.gb1 = as_(factory.gb1)
        idx = np.arange(self.N)
        self.struct = jnp.asarray((idx[None, :] != idx[:, None])
                                  & (idx[None, :] != 0))      # (n, m) allowed
        self._fns: dict = {}

    def refresh(self) -> None:
        """Re-upload the update-mutable basis tensors after a
        ``Planner.update`` patch (rate change / node slowdown).  Compiled
        kernels take these as traced arguments, so no retrace happens."""
        import jax.numpy as jnp
        fac = self.factory
        self.rate = jnp.asarray(np.asarray(fac.rate), self._dt)
        self.rate_pos = jnp.asarray(fac.rate > 0)
        self.f = jnp.asarray(np.asarray(fac.f), self._dt)

    # -- kernel construction ------------------------------------------------
    def _build(self, S: int, mode: str, want_stack: bool):
        import jax
        import jax.numpy as jnp
        from jax import lax

        N, I, K = self.N, self.I, self.K
        I1 = I + 1
        dt = self._dt
        INF = jnp.asarray(np.asarray(_INF, dtype=self.dtype))
        ZERO = jnp.asarray(np.asarray(0.0, dtype=self.dtype))
        Wf, Wb, tri = self.Wf, self.Wb, self.tri
        Mps, Mact, Mstat = self.Mps, self.Mact, self.Mstat
        struct = self.struct
        kappa, t0, t1 = self.kappa, self.t0, self.t1
        bth, mem = self.bth, self.mem
        fb1, gb1 = self.fb1, self.gb1
        paper_mem = self.memory_model == "paper"
        is_sum = mode == "sum"

        # rate / rate_pos / f ride as ARGUMENTS, not closure constants:
        # Planner.update patches them in place (refresh()) and a traced
        # argument re-binds per call with no retrace, where a captured
        # constant would bake the stale value into the compiled kernel.
        def kern(e, ts, rate, rate_pos, f):
            # e (N, S) per-slice effective batch; ts (S,) thresholds
            t4 = ts[None, None, None, :]
            a1 = e * kappa[:, None]                             # eff * kappa
            a2 = jnp.maximum(e - bth[:, None], ZERO) * kappa[:, None]

            # -- hoisted assembly: every edge value is k-independent, so the
            # masked relaxation operands are built ONCE per sweep instead of
            # once per scan step (the per-k rebuild dominated the kernel
            # wall-clock).  Elementwise op chains are identical to the
            # factory's, so x64 bit-parity with numpy is preserved.
            # segments (i, m, j, s): factory formulas over all cuts at once
            fp = (a1[None, :, None, :] * Wf[:, None, :, None]) \
                / f[None, :, None, None] + t0[None, :, None, None]
            bpw = a2[None, :, None, :] * Wb[:, None, :, None]
            bp = jnp.where(bpw == ZERO, t1[None, :, None, None],
                           bpw / f[None, :, None, None]
                           + t1[None, :, None, None])
            if paper_mem:
                mok = (e[None, :, None, :] * Mps[:, None, :, None]
                       <= mem[None, :, None, None])
            else:
                mok = (e[None, :, None, :] * Mact[:, None, :, None]
                       + Mstat[:, None, :, None] <= mem[None, :, None, None])
            ok = tri[:, None, :, None] & mok
            sc = jnp.where(ok, fp + bp, INF)
            sb = jnp.where(ok, jnp.maximum(fp, bp), INF)
            Vs = jnp.where(sb <= t4, sc if is_sum else sb, INF)  # (I1,N,I1,S)
            # comms (i, n, m, s): threshold-masked edge values
            fbn = fb1[:, None, None] * e[None]                   # (I1, N, S)
            gbn = gb1[:, None, None] * e[None]
            tf = jnp.where(
                fbn[:, :, None, :] == ZERO, ZERO,
                jnp.where(rate_pos[None, :, :, None],
                          fbn[:, :, None, :] / rate[None, :, :, None], INF))
            tb = jnp.where(
                gbn[:, :, None, :] == ZERO, ZERO,
                jnp.where(rate_pos.T[None, :, :, None],
                          gbn[:, :, None, :] / rate.T[None, :, :, None], INF))
            cb = jnp.maximum(tf, tb)
            cv = tf + tb if is_sum else cb
            okc = struct[None, :, :, None] & (cb <= t4)
            Vc = jnp.where(okc, cv, INF)                         # (I1,N,N,S)

            src_v = sc[0, 0] if is_sum else sb[0, 0]
            dist0 = jnp.where(sb[0, 0] <= ts[None, :], src_v, INF)  # (I1, S)
            dist = jnp.full((N, I1, S), INF, dt).at[0].set(dist0)
            fin0 = jnp.isfinite(dist[0, I])
            best = jnp.where(fin0, dist[0, I], INF)
            best_k = jnp.where(fin0, 1, 0).astype(jnp.int32)
            best_m = jnp.zeros(S, jnp.int32)

            def layer(dist):
                # two-stage relaxation; the i loop stays sequential — the
                # (N, I1, S) working set fits cache where a fully-vectorized
                # (I1, N, I1, S) pass does not (measured slower)
                def per_i(i, nd):
                    dcol = dist[:, i, :][:, None, :]
                    if is_sum:
                        cand = dcol + Vc[i]
                    else:
                        cand = jnp.maximum(dcol, Vc[i])
                    Ai = cand.min(axis=0)                       # (m, S)
                    if is_sum:
                        cand2 = Ai[:, None, :] + Vs[i]
                    else:
                        cand2 = jnp.maximum(Ai[:, None, :], Vs[i])
                    return jnp.minimum(nd, cand2)
                return lax.fori_loop(1, I1, per_i,
                                     jnp.full((N, I1, S), INF, dt))

            def body(carry, k):
                dist, best, best_k, best_m = carry
                nd = layer(dist)
                term = nd[1:, I]                                # (N-1, S)
                v = term.min(axis=0)
                upd = v < best
                best = jnp.where(upd, v, best)
                best_k = jnp.where(upd, k, best_k)
                best_m = jnp.where(upd, term.argmin(axis=0).astype(jnp.int32)
                                   + 1, best_m)
                return (nd, best, best_k, best_m), (nd if want_stack else None)

            ks = jnp.arange(2, K + 1, dtype=jnp.int32)
            (dist, best, best_k, best_m), stack = lax.scan(
                body, (dist, best, best_k, best_m), ks)
            return best, best_k, best_m, stack

        return jax.jit(kern)

    # -- dispatch -----------------------------------------------------------
    def _fn(self, S: int, mode: str, want_stack: bool):
        key = (S, mode, want_stack)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build(S, mode, want_stack)
            self._fns[key] = fn
        return fn

    def sweep(self, e: np.ndarray, ts: np.ndarray, *, mode: str = "sum",
              want_stack: bool = False):
        """Run the batched DP for slices (e[:, s], ts[s]).

        Returns ``(best_val, best_k, best_m, stack)`` as numpy arrays;
        ``stack`` is the per-layer dist tensor ``(K-1, N, I1, S)`` (or None).
        The slice axis is padded to a size bucket and chunked at 512."""
        import jax.numpy as jnp

        obs.inc("planner.jax_dispatches")
        e = np.asarray(e, dtype=self.dtype)
        ts = np.asarray(ts, dtype=self.dtype)
        S = ts.shape[0]
        if S > _S_MAX:
            parts = [self.sweep(e[:, c:c + _S_MAX], ts[c:c + _S_MAX],
                                mode=mode, want_stack=want_stack)
                     for c in range(0, S, _S_MAX)]
            stack = (np.concatenate([p[3] for p in parts], axis=3)
                     if want_stack else None)
            return (np.concatenate([p[0] for p in parts]),
                    np.concatenate([p[1] for p in parts]),
                    np.concatenate([p[2] for p in parts]), stack)
        Sp = next(b for b in _S_BUCKETS if b >= max(S, 1))
        if Sp != S:
            e = np.concatenate(
                [e, np.ones((self.N, Sp - S), dtype=self.dtype)], axis=1)
            ts = np.concatenate(
                [ts, np.full(Sp - S, -_INF, dtype=self.dtype)])
        out = self._fn(Sp, mode, want_stack)(jnp.asarray(e), jnp.asarray(ts),
                                             self.rate, self.rate_pos, self.f)
        best = np.asarray(out[0])[:S]
        best_k = np.asarray(out[1])[:S]
        best_m = np.asarray(out[2])[:S]
        stack = np.asarray(out[3])[:, :, :, :S] if want_stack else None
        return best, best_k, best_m, stack


# ---------------------------------------------------------------------------
# Host mirror of the assembled graph (for windows + backtrace), in kernel dtype
# ---------------------------------------------------------------------------

def host_mirror(factory, b: int, dtype: str):
    """Assemble the DP-layout graph tensors for micro-batch b on the host,
    replicating the kernel's arithmetic op-for-op in the kernel's dtype.

    Returns ``(Ccom, Bcom, Sseg, Bseg, src_cost, src_beta)`` with rebind's
    structural folds applied — layouts match ``_LayeredDP`` (``Ccom[n,i,m]``,
    ``Sseg[i,m,j]``).  numpy and XLA both implement IEEE-754 elementwise
    mul/div/add/max, so these values equal the kernel's assembled values
    bit-for-bit in either dtype — which is what makes the host backtrace and
    the host beta windows consistent with device sweeps."""
    dt = np.dtype(dtype)
    eff = factory.effective_batch(b).astype(dt)
    N, I1 = factory.N, factory.I + 1
    kappa = factory.kappa.astype(dt)
    f = factory.f.astype(dt)
    t0 = factory.t0.astype(dt)
    t1 = factory.t1.astype(dt)
    bth = factory.b_th.astype(dt)
    mem = factory.mem.astype(dt)
    Wf = factory.W_fp.astype(dt)
    Wb = factory.W_bp.astype(dt)

    e = eff[:, None, None]
    a1 = (eff * kappa)[:, None, None]
    a2 = (np.maximum(eff - bth, dt.type(0.0)) * kappa)[:, None, None]
    fp = (a1 * Wf[None]) / f[:, None, None] + t0[:, None, None]
    bpw = a2 * Wb[None]
    bp = np.where(bpw == 0.0, t1[:, None, None],
                  bpw / f[:, None, None] + t1[:, None, None])
    if factory.memory_model == "paper":
        mok = e * factory.Mem_ps.astype(dt)[None] <= mem[:, None, None]
    else:
        mok = (e * factory.Mem_act.astype(dt)[None]
               + factory.Mem_static.astype(dt)[None] <= mem[:, None, None])
    ok = factory.tri[None] & mok
    seg_cost = np.where(ok, fp + bp, _INF).astype(dt)     # (n, i, j)
    seg_beta = np.where(ok, np.maximum(fp, bp), _INF).astype(dt)

    fb = eff[None, :] * factory.fb1.astype(dt)[:, None]   # (I1, N)
    gb = eff[None, :] * factory.gb1.astype(dt)[:, None]
    rate = factory.rate.astype(dt)
    rpos = factory.rate > 0
    with np.errstate(divide="ignore", invalid="ignore"):
        tf = np.where(fb[:, :, None] == 0.0, dt.type(0.0),
                      np.where(rpos[None], fb[:, :, None] / rate[None], _INF))
        tb = np.where(gb[:, :, None] == 0.0, dt.type(0.0),
                      np.where(rpos.T[None],
                               gb[:, :, None] / rate.T[None], _INF))
    comm_cost = (tf + tb).astype(dt)                      # (i, n, m)
    comm_beta = np.maximum(tf, tb).astype(dt)
    comm_cost[0] = _INF
    comm_beta[0] = _INF
    idx = np.arange(N)
    comm_cost[:, idx, idx] = _INF
    comm_beta[:, idx, idx] = _INF

    Ccom = np.ascontiguousarray(comm_cost.transpose(1, 0, 2))   # (n, i, m)
    Bcom = np.ascontiguousarray(comm_beta.transpose(1, 0, 2))
    Ccom[:, :, 0] = _INF
    Bcom[:, :, 0] = _INF
    Ccom[idx, :, idx] = _INF
    Bcom[idx, :, idx] = _INF
    Sseg = np.ascontiguousarray(seg_cost.transpose(1, 0, 2))    # (i, m, j)
    Bseg = np.ascontiguousarray(seg_beta.transpose(1, 0, 2))
    src_cost = seg_cost[0, 0, :].copy()
    src_beta = seg_beta[0, 0, :].copy()
    return Ccom, Bcom, Sseg, Bseg, src_cost, src_beta


def backtrace_stack(stack, mirror, t: float, k: int, m: int, j: int) -> list:
    """Reconstruct the path for one slice from its per-layer dist stack.

    ``stack[k-2]`` is dist *after* layer k (``stack`` covers k = 2..K);
    layer 1 is the source row.  At each step the parent ``(n, i)`` of state
    ``(k, m, j)`` is found by re-running the two-stage relaxation for the
    single needed column and taking ``np.argmin`` — the *same array* the
    numpy kernel argmin'd over when ``want_parents`` was set, so the
    first-minimum tie-breaking is reproduced exactly (values are bit-equal
    because host mirror assembly matches the kernel op-for-op)."""
    Ccom, Bcom, Sseg, Bseg, src_cost, src_beta = mirror
    if k == 1:
        return [(0, j)]
    path = [(int(m), int(j))]
    N, I1 = Ccom.shape[0], Ccom.shape[1]
    dt = Ccom.dtype
    src = np.where(src_beta <= t, src_cost, dt.type(_INF))
    for kk in range(k, 1, -1):
        prev = (stack[kk - 3] if kk >= 3 else
                _src_dist(N, I1, src))                     # dist after kk-1
        Vc = np.where(Bcom[:, :, m] <= t, Ccom[:, :, m], dt.type(_INF))
        A = (prev + Vc).min(axis=0)                        # (I1,)
        Vs = np.where(Bseg[:, m, j] <= t, Sseg[:, m, j], dt.type(_INF))
        i = int(np.argmin(A + Vs))
        n = int(np.argmin(prev[:, i] + Vc[:, i]))
        path.append((n, i))
        m, j = n, i
    path.reverse()
    return path


def _src_dist(N: int, I1: int, src: np.ndarray) -> np.ndarray:
    d = np.full((N, I1), _INF, dtype=src.dtype)
    d[0] = src
    return d


# ---------------------------------------------------------------------------
# Repricing helpers (float64 — final objectives are exact for the chosen path)
# ---------------------------------------------------------------------------

def reprice_dp_order(g, path) -> tuple:
    """(cost, beta) of ``path`` on graph ``g`` with the DP's accumulation
    order ``(dist + comm) + seg`` — bit-equal to the numpy kernel's dist."""
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
# The batched solve_many driver (phases A-D on device)
# ---------------------------------------------------------------------------

def solve_many_jax(planner, bs: list, B: int, K: int | None = None) -> list:
    """Full-jax ``Planner.solve_many``: phases A-D as batched device sweeps.

    Mirrors ``Planner._solve_many`` phase-for-phase; additionally shares
    upper bounds *across* b (every phase-A/B path is repriced on every live
    graph, float64) which shrinks the phase-C windows — valid because any
    real path's objective upper-bounds OPT, and a window that contains every
    global minimizer yields the same argmin winner."""
    from repro.core.shortest_path import _betas_from_arrays

    K = planner.default_K(K)
    jdp = planner._jax_dp(K)
    dtype = jdp.dtype
    fac = planner.factory
    S = len(bs)
    N, I = fac.N, fac.I

    e = np.empty((N, S), dtype=dtype)
    for s, b in enumerate(bs):
        e[:, s] = fac.effective_batch(b).astype(dtype)
    xi = np.array([L.num_fills(B, b) for b in bs])
    mirrors = [planner._jax_mirror(b, dtype) for b in bs]
    graphs = [planner.graph(b) for b in bs]

    # phase A: full-graph run for every b (dist stack -> host backtrace)
    bestA, kA, mA, stackA = jdp.sweep(e, np.full(S, _INF), want_stack=True)
    paths_full = [
        backtrace_stack(stackA[:, :, :, s], mirrors[s], _INF,
                        int(kA[s]), int(mA[s]), I) if kA[s] else None
        for s in range(S)]

    results: list = [None] * S
    live = []
    for s in range(S):
        if xi[s] == 0 or paths_full[s] is None:
            results[s] = _finish_repriced(planner, graphs[s], paths_full[s],
                                          bs[s], B, int(xi[s]), 1)
        else:
            live.append(s)
    if not live:
        return results

    # phase B: (max, min) sweep -> beta*, then a probe run at beta*
    el = e[:, live]
    beta_star, _, _, _ = jdp.sweep(el, np.full(len(live), _INF), mode="max")
    bestP, kP, mP, stackP = jdp.sweep(el, beta_star, want_stack=True)
    paths_star = [
        backtrace_stack(stackP[:, :, :, q], mirrors[live[q]],
                        float(beta_star[q]), int(kP[q]), int(mP[q]), I)
        if kP[q] else None
        for q in range(len(live))]

    # cross-b upper bounds: every candidate path repriced on every live b
    pool = [p for p in paths_full if p is not None] \
        + [p for p in paths_star if p is not None]
    windows = []
    for q, s in enumerate(live):
        g = graphs[s]
        ub = _INF
        for p in pool:
            c, beta = reprice_dp_order(g, p)
            if math.isfinite(c):
                ub = min(ub, c + xi[s] * beta)
        cap = (ub - float(bestA[s])) / xi[s]
        Ccom_m, Bcom_m, _, Bseg_m, _, src_beta_m = mirrors[s]
        w = _betas_from_arrays(Bcom_m, Bseg_m, src_beta_m,
                               float(beta_star[q]),
                               cap * (1 + 1e-12) + 1e-12)
        w = np.unique(np.concatenate(
            [np.atleast_1d(np.asarray(v, dtype=np.float64)) for v in w]))
        if w.size == 0:
            w = np.array([float(beta_star[q])])
        windows.append(w)

    # phase C: one flat sweep over every (b, threshold) pair
    slice_q = np.concatenate(
        [np.full(len(w), q, dtype=int) for q, w in zip(range(len(live)),
                                                       windows)])
    slice_t = np.concatenate(windows)
    eC = el[:, slice_q]
    dvals, _, _, _ = jdp.sweep(eC, slice_t)
    t_hat = np.empty(len(live))
    pos = 0
    for q, w in enumerate(windows):
        H = dvals[pos:pos + len(w)].astype(np.float64) + xi[live[q]] * w
        t_hat[q] = w[int(np.argmin(H))]
        pos += len(w)

    # phase D: reconstruction at the winners (reuse the probe when t̂ == β*)
    need = [q for q in range(len(live)) if t_hat[q] != beta_star[q]]
    if need:
        eD = el[:, need]
        bestR, kR, mR, stackR = jdp.sweep(eD, t_hat[need], want_stack=True)
        for r, q in enumerate(need):
            s = live[q]
            path = (backtrace_stack(stackR[:, :, :, r], mirrors[s],
                                    float(t_hat[q]), int(kR[r]), int(mR[r]),
                                    I) if kR[r] else None)
            results[s] = _finish_repriced(planner, graphs[s], path,
                                          bs[s], B, int(xi[s]), 5)
    for q, s in enumerate(live):
        if results[s] is None:
            results[s] = _finish_repriced(planner, graphs[s], paths_star[q],
                                          bs[s], B, int(xi[s]), 4)
    return results


def _finish_repriced(planner, g, path, b, B, xi, sweeps):
    """Assemble an MSPResult, repricing the chosen path in float64 so the
    reported objective/T_f are exact for the (possibly float32-chosen)
    solution — under x64 this equals the numpy result bit-for-bit."""
    if path is None:
        return planner._finish(g, _INF, None, b, B, xi, sweeps, "batched")
    cost, _beta = reprice_dp_order(g, path)
    return planner._finish(g, cost, path, b, B, xi, sweeps, "batched")


def dist_at_jax(dp, ts: np.ndarray, planner) -> np.ndarray:
    """dist(t) per threshold for one bound ``_LayeredDP`` via the batched
    kernel (used by ``Planner.solve(..., backend='jax')``'s window sweep).

    Requires the owning planner's factory (on-the-fly assembly); a
    restricted DP sweeps in numpy (the kernel implements the unrestricted
    DP)."""
    if dp.restricted:
        return dp.sweep(ts).best_val
    jdp = planner._jax_dp(dp.K)
    b = dp.g.b
    e = np.tile(planner.factory.effective_batch(b)[:, None], (1, len(ts)))
    best, _, _, _ = jdp.sweep(e.astype(jdp.dtype), ts)
    return best.astype(np.float64)
