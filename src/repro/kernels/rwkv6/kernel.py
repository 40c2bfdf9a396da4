"""Chunked RWKV6 WKV scan — Pallas TPU kernel.

TPU adaptation of the data-dependent-decay linear-attention recurrence:
the sequence is processed in chunks along the *innermost grid dimension*
(sequential on a TPU core), with the running state S (hd x hd, fp32) held
in VMEM scratch across chunks.  Inside a chunk everything is matmul-shaped
for the MXU:

    y  = q @ S  +  tril(q' k'^T, -1) @ v  +  diag-bonus
    S <- exp(L_C) * S  +  (k * exp(L_C - L))^T @ v

where q = r * exp(L_{t-1}), k' = k * exp(-L) and L = cumsum(log w) within
the chunk, computed as a lower-triangular matmul.  All exponents are
differences of a non-increasing L (<= 0), so no overflow.  Grid:
(B*H, S/chunk); blocks (chunk, hd) live in VMEM.  CPU callers pass
``interpret=True``; the default compiles with Mosaic for the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                 y_ref, s_out_ref, s_scr, *, chunk: int, hd: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)           # (C, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # (1, hd)
    S = s_scr[...]                             # (hd, hd)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum lowering; the MXU does this in one pass)
    tril = jnp.where(cols <= rows, 1.0, 0.0).astype(jnp.float32)
    L = jax.lax.dot_general(tril, lw, (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (C, hd)
    Lm1 = L - lw                               # exclusive
    q = r * jnp.exp(Lm1)
    kd = k * jnp.exp(-L)

    # cross-chunk: q @ S
    y = jax.lax.dot_general(q, S, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk, strictly below the diagonal
    att = jax.lax.dot_general(q, kd, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (C, C)
    att = jnp.where(cols < rows, att, 0.0)
    y += jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # current-token bonus: (r . u . k) v
    y += jnp.sum(r * u * k, axis=-1, keepdims=True) * v
    y_ref[0] = y.astype(y_ref.dtype)

    # state to chunk end: S rows (the key index) decay by exp(L_C); the
    # row scaling is a diagonal matmul, which keeps L_C in its lane layout
    L_C = L[chunk - 1:chunk, :]                            # (1, hd)
    hrows = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
    hcols = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    decay = jnp.where(hrows == hcols, jnp.exp(L_C), 0.0)   # diag(exp(L_C))
    k_tail = k * jnp.exp(L_C - L)                          # (C, hd)
    S_new = jax.lax.dot_general(
        decay, S, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + jax.lax.dot_general(
        k_tail, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = S_new

    @pl.when(ci == nc - 1)
    def _fin():
        s_out_ref[0] = S_new


def wkv6_fwd(r, k, v, logw, u, s0, *, chunk: int = 128,
             interpret: bool = False):
    """r/k/v/logw: (BH, S, hd); u: (BH, 1, hd); s0: (BH, hd, hd) fp32.
    Returns (y (BH, S, hd) fp32, S_final (BH, hd, hd) fp32)."""
    BH, S, hd = r.shape
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    kernel = functools.partial(_wkv6_kernel, chunk=chunk, hd=hd)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
        name="wkv6_fwd",
    )(r, k, v, logw, u, s0)
    return y, s_fin
