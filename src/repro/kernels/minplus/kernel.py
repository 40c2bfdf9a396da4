"""Pallas implementation of the masked min-plus / min-max DP sweep.

Follows the ``kernels/flash`` idiom: a 1-D grid over threshold tiles, the
graph tensors passed as whole blocks shared by every grid step (their
``index_map`` pins block 0), and per-tile threshold/output blocks.

Layout is chosen for Mosaic: thresholds ride the sublanes and nodes the
lanes, so every tile is a ``(thresholds, nodes)`` slab and a graph row
broadcasts along sublanes for free.  The graph tensors arrive transposed so
that the cut index ``i`` (the ``lax.fori_loop`` variable) is always a
leading ref index, and the DP state lives in two VMEM scratch buffers
indexed the same way; nothing indexes a value dynamically, and no
O(N^2 I^2) candidate tensor is materialized.

Interpret mode is the caller's explicit choice (``interpret=True``, as the
CPU parity tests pass it); the default compiles with Mosaic for the TPU.
"""

from __future__ import annotations

import functools

import numpy as np

_INF = np.inf


def _sweep_kernel(ts_ref, Cc_ref, Bc_ref, Ss_ref, Bs_ref, sc_ref, sb_ref,
                  out_ref, dist_ref, nd_ref, *, K: int, N: int, I1: int,
                  mode: str):
    """ts (St, 1); Cc/Bc [i, n, m]; Ss/Bs [i, j, m]; sc/sb (1, I1);
    out (St, 1); dist/nd scratch [layer, threshold, node]."""
    import jax.numpy as jnp
    from jax import lax

    dt = dist_ref.dtype
    INF = jnp.asarray(np.asarray(_INF, dtype=dt))
    op = jnp.add if mode == "sum" else jnp.maximum
    Vc_ref = Cc_ref if mode == "sum" else Bc_ref
    Vs_ref = Ss_ref if mode == "sum" else Bs_ref
    I = I1 - 1

    ts = ts_ref[...]                                   # (St, 1)
    St = ts.shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (St, N), 1)
    sb = sb_ref[...]                                   # (1, I1)
    src = (sc_ref if mode == "sum" else sb_ref)[...]

    # source layer: node 0 holds every feasible prefix cut
    for i in range(I1):
        d0 = jnp.where(sb[:, i:i + 1] <= ts, src[:, i:i + 1], INF)
        dist_ref[i] = jnp.where(lane == 0, d0, INF)
    best = dist_ref[I][:, 0:1]                         # (St, 1)

    def per_i(i, carry):
        d = dist_ref[i]                                # (St, n)
        Ai = jnp.full((St, N), INF, dt)                # (St, m)
        for n in range(N):
            vc = jnp.where(Bc_ref[i, n:n + 1, :] <= ts,
                           Vc_ref[i, n:n + 1, :], INF)
            Ai = jnp.minimum(Ai, op(d[:, n:n + 1], vc))
        for j in range(I1):
            vs = jnp.where(Bs_ref[i, j:j + 1, :] <= ts,
                           Vs_ref[i, j:j + 1, :], INF)
            nd_ref[j] = jnp.minimum(nd_ref[j], op(Ai, vs))
        return carry

    def layer(_k, best):
        nd_ref[...] = jnp.full(nd_ref.shape, INF, dt)
        lax.fori_loop(0, I1, per_i, 0)
        dist_ref[...] = nd_ref[...]
        last = jnp.where(lane >= 1, nd_ref[I], INF)    # terminal, off-client
        return jnp.minimum(best, last.min(axis=1, keepdims=True))

    out_ref[...] = lax.fori_loop(2, K + 1, layer, best)


def sweep_call(N: int, I1: int, K: int, Sp: int, *, mode: str = "sum",
               dtype=np.float32, block_s: int = 128,
               interpret: bool = False):
    """The ``pallas_call`` for one graph size, on kernel layouts: a callable
    of ``(ts (Sp, 1), Cc/Bc [i, n, m], Ss/Bs [i, j, m], sc/sb (1, I1))`` ->
    ``best (Sp, 1)`` (``Sp`` a multiple of ``block_s``).  Split out of
    :func:`sweep_minplus` so a compile for a described chip can lower it
    from shapes alone."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shared = lambda *shape: pl.BlockSpec(shape, lambda s: (0,) * len(shape))
    tile = pl.BlockSpec((block_s, 1), lambda s: (s, 0))
    return pl.pallas_call(
        functools.partial(_sweep_kernel, K=int(K), N=N, I1=I1, mode=mode),
        grid=(Sp // block_s,),
        in_specs=[tile,
                  shared(I1, N, N), shared(I1, N, N),
                  shared(I1, I1, N), shared(I1, I1, N),
                  shared(1, I1), shared(1, I1)],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((Sp, 1), dtype),
        scratch_shapes=[pltpu.VMEM((I1, block_s, N), dtype),
                        pltpu.VMEM((I1, block_s, N), dtype)],
        interpret=interpret,
        name="minplus_sweep",
    )


def sweep_minplus(Ccom, Bcom, Sseg, Bseg, src_cost, src_beta, K, ts, *,
                  mode: str = "sum", interpret: bool = False,
                  block_s: int = 128) -> np.ndarray:
    """Best terminal DP value per threshold, via one ``pallas_call``.

    Layouts match ``_LayeredDP`` buffers: ``Ccom/Bcom[n, i, m]``,
    ``Sseg/Bseg[i, m, j]``, structural masks pre-folded.  Returns a float
    array the shape of ``ts``.  Parity oracle: :func:`repro.kernels.minplus.
    ref.sweep_ref` (and transitively the numpy ``_sweep``).

    ``interpret=True`` runs the Pallas interpreter (the CPU tests' explicit
    choice).  Compiled, the kernel computes in float32, the widest float
    Mosaic lowers; interpreted, it follows jax's enabled dtype (float64
    under ``JAX_ENABLE_X64``, bit-exact with the numpy sweep)."""
    import jax
    import jax.numpy as jnp

    ts = np.atleast_1d(np.asarray(ts))
    S = ts.shape[0]
    N, I1 = Ccom.shape[0], Ccom.shape[1]
    dt = np.dtype("float64" if interpret and jax.config.jax_enable_x64
                  else "float32")
    Sp = ((S + block_s - 1) // block_s) * block_s
    ts_p = np.full(Sp, -_INF, dtype=dt)
    ts_p[:S] = ts.astype(dt)

    fn = sweep_call(N, I1, K, Sp, mode=mode, dtype=dt, block_s=block_s,
                    interpret=interpret)
    cut_major = lambda a: np.asarray(a, dtype=dt).transpose(1, 0, 2)
    out = fn(jnp.asarray(ts_p[:, None]),
             jnp.asarray(cut_major(Ccom)), jnp.asarray(cut_major(Bcom)),
             jnp.asarray(np.asarray(Sseg, dtype=dt).transpose(0, 2, 1)),
             jnp.asarray(np.asarray(Bseg, dtype=dt).transpose(0, 2, 1)),
             jnp.asarray(np.asarray(src_cost, dtype=dt)[None]),
             jnp.asarray(np.asarray(src_beta, dtype=dt)[None]))
    return np.asarray(out)[:S, 0].astype(np.float64)
