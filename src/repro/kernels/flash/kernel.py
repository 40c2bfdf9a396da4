"""Flash attention, forward and backward — Pallas TPU kernels.

Head-major operands: q is (B, H, S, hd) and k, v are (B, KV, T, hd), so a
(block, hd) tile of one head is contiguous.  The model splits heads with
an einsum, so the compiler lays the projections out head-major and the
transposes around the kernels are free.  GQA maps q-head h to kv-head
h // (H // KV) in the index maps: no kv head is copied per q head.

Three kernels, each a grid whose innermost axis runs sequentially on a
core, so its accumulators live in VMEM scratch across that axis:

- ``fwd``: grid (B, H, q-blocks, k-blocks), an online softmax over the
  k-blocks.  Writes o and the per-row logsumexp (f32), the residual of the
  backward;
- ``dkv``: grid (B, KV, k-blocks, group, q-blocks): dK and dV of one
  k-block summed over the q-heads of its group and every q-block;
- ``dq``: grid (B, H, q-blocks, k-blocks): dQ of one q-block.

D = rowsum(dO * O) comes in precomputed.  Causal kernels skip every block
wholly above the diagonal, and its index map repeats the neighbouring
needed block, so no DMA is issued for it.  Only blocks that cross the
diagonal or the padded end of the keys build a mask.

Numerics: q, k, v, dO and the probabilities enter the MXU in their own
dtype (bf16 in training) with f32 accumulation; scores, max, sum,
logsumexp and dS are f32; P and dS are rounded to the operand dtype only as
matmul operands.  The logsumexp and D travel as rows (B, H, 1, S): the
dQ kernel, which needs them as columns, transposes one tile per q-block.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.device import scope

NEG_INF = -1e30
LANES = 128
NT = (((1,), (1,)), ((), ()))        # a @ b.T
NN = (((1,), (0,)), ((), ()))        # a @ b


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Static shape of one call: ``seq_q`` and ``seq_k`` are padded to
    multiples of the blocks; ``keys`` is the unpadded key length."""
    heads: int
    kv_heads: int
    head_dim: int
    seq_q: int                       # padded
    seq_k: int                       # padded
    keys: int                        # unpadded key length
    block_q: int
    block_k: int
    causal: bool
    interpret: bool

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads

    @property
    def nq(self) -> int:
        return self.seq_q // self.block_q

    @property
    def nk(self) -> int:
        return self.seq_k // self.block_k

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)


def block_sizes(seq: int, head_dim: int) -> tuple:
    """(block_q, block_k) for a sequence of ``seq`` and heads of
    ``head_dim``: the sequence in 128-row steps, up to 1024 rows (512 above
    head_dim 128, where each row's tiles are larger).  Each grid step costs
    a fixed overhead next to its tile's matmuls, so at head_dim 128 on a
    TPU v5e few large blocks beat many small ones at S = 1024 and 2048
    (``benchmarks/attention_kernels.py``)."""
    cap = 1024 if head_dim <= 128 else 512
    blk = min(cap, -(-seq // LANES) * LANES)
    return blk, blk


# ---------------------------------------------------------------------------
# Which blocks run, and which need a mask
# ---------------------------------------------------------------------------

def _last_k_block(g: Geometry, i):
    """Last k-block that q-block ``i`` needs (causal)."""
    return jnp.minimum(((i + 1) * g.block_q - 1) // g.block_k, g.nk - 1)


def _first_q_block(g: Geometry, j):
    """First q-block that needs k-block ``j`` (causal)."""
    return (j * g.block_k) // g.block_q


def _runs(g: Geometry, q_start, k_start):
    """Some element of the (q, k) block is on or below the diagonal."""
    return jnp.logical_or(not g.causal, k_start <= q_start + g.block_q - 1)


def _needs_mask(g: Geometry, q_start, k_start):
    """Some element of the (q, k) block is above the diagonal or a padded
    key."""
    above = jnp.logical_and(g.causal, k_start + g.block_k - 1 > q_start)
    return jnp.logical_or(above, k_start + g.block_k > g.keys)


def _valid(g: Geometry, q_start, k_start, shape, q_axis: int):
    """Boolean (shape) of the allowed (query, key) pairs of one block; the
    query index runs along ``q_axis``."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    ok = kpos < g.keys
    if g.causal:
        ok = jnp.logical_and(ok, kpos <= qpos)
    return ok


def _when_runs(g: Geometry, q_start, k_start, body):
    """Run ``body(masked)`` if the grid step's (q, k) block has an element
    on or below the diagonal; the masked variant only if the block needs
    it."""
    runs, masked = _runs(g, q_start, k_start), _needs_mask(g, q_start, k_start)
    for m in (False, True):
        pl.when(jnp.logical_and(runs, masked == m))(
            functools.partial(body, m))


def _row_to_col(row, n: int):
    """(1, n) -> (n, 1), through a lane-aligned transpose."""
    return jnp.broadcast_to(row, (LANES, n)).T[:, :1]


def _col_to_row(col, n: int):
    """(n, 1) -> (1, n)."""
    return jnp.broadcast_to(col, (n, LANES)).T[:1, :]


def _q_major_specs(g: Geometry) -> tuple:
    """(q, k/v, row) BlockSpecs of a grid (B, H, q-blocks, k-blocks); a
    causal k-block past the diagonal repeats the last needed one."""
    def kv_map(b, h, i, j):
        if g.causal:
            j = jnp.minimum(j, _last_k_block(g, i))
        return b, h // g.group, j, 0

    return (pl.BlockSpec((None, None, g.block_q, g.head_dim),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, g.block_k, g.head_dim), kv_map),
            pl.BlockSpec((None, None, 1, g.block_q),
                         lambda b, h, i, j: (b, h, 0, i)))


def _cost(g: Geometry, matmuls: int, *arrays):
    """The kernel's cost for the compiler's scheduler: ``matmuls`` S x T x
    hd products per head (half of them where causal)."""
    pairs = g.heads * g.seq_q * g.seq_k // (2 if g.causal else 1)
    batch = arrays[0].shape[0]
    return pl.CostEstimate(
        flops=int(2 * matmuls * batch * pairs * g.head_dim),
        transcendentals=int(batch * pairs),
        bytes_accessed=int(sum(a.size * a.dtype.itemsize for a in arrays)))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, g: Geometry):
    i, j = pl.program_id(2), pl.program_id(3)
    q_start, k_start = i * g.block_q, j * g.block_k

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked: bool):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = jax.lax.dot_general(q, k, NT, preferred_element_type=jnp.float32)
        s = s * g.scale    # (block_q, block_k)
        if masked:
            s = jnp.where(_valid(g, q_start, k_start, s.shape, 0), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, NN, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _when_runs(g, q_start, k_start, body)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = _col_to_row(m_ref[...] + jnp.log(l), g.block_q)


def fwd(q, k, v, g: Geometry):
    """q (B, H, Sq, hd), k, v (B, KV, Sk, hd), padded to the blocks ->
    (o like q, logsumexp (B, H, 1, Sq) f32)."""
    B = q.shape[0]
    q_spec, kv_spec, row_spec = _q_major_specs(g)
    with scope("kernels.flash"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, g=g),
            grid=(B, g.heads, g.nq, g.nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, row_spec],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct((B, g.heads, 1, g.seq_q),
                                            jnp.float32)],
            scratch_shapes=[pltpu.VMEM((g.block_q, 1), jnp.float32),
                            pltpu.VMEM((g.block_q, 1), jnp.float32),
                            pltpu.VMEM((g.block_q, g.head_dim), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
            cost_estimate=_cost(g, 2, q, k, v, q),
            interpret=g.interpret,
            name="flash_fwd",
        )(q, k, v)


# ---------------------------------------------------------------------------
# Backward: dK, dV
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, g: Geometry):
    j, r, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    q_start, k_start = i * g.block_q, j * g.block_k

    @pl.when(jnp.logical_and(r == 0, i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked: bool):
        q, do = q_ref[...], do_ref[...]
        k, v = k_ref[...], v_ref[...]
        # the transposed block: keys down the sublanes, queries along the
        # lanes, so the logsumexp and D rows broadcast as they are stored
        st = jax.lax.dot_general(k, q, NT, preferred_element_type=jnp.float32)
        pt = jnp.exp(st * g.scale - lse_ref[...])    # (block_k, block_q)
        if masked:
            pt = jnp.where(_valid(g, q_start, k_start, pt.shape, 1), pt, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - d_ref[...])
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, NN, preferred_element_type=jnp.float32)

    _when_runs(g, q_start, k_start, body)

    @pl.when(jnp.logical_and(r == pl.num_programs(3) - 1,
                             i == pl.num_programs(4) - 1))
    def _finish():
        dk_ref[...] = (dk_acc[...] * g.scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def bwd_dkv(q, k, v, do, lse, d, g: Geometry):
    """dK, dV (like k, v) of ``fwd``, summed over each kv-head's group."""
    B, grp, hd = q.shape[0], g.group, g.head_dim

    def q_block(j, i):
        """A causal q-block before the diagonal repeats the first needed."""
        return jnp.maximum(i, _first_q_block(g, j)) if g.causal else i

    q_spec = pl.BlockSpec((None, None, g.block_q, hd), lambda b, h, j, r, i: (
        b, h * grp + r, q_block(j, i), 0))
    kv_spec = pl.BlockSpec((None, None, g.block_k, hd),
                           lambda b, h, j, r, i: (b, h, j, 0))
    row_spec = pl.BlockSpec((None, None, 1, g.block_q), lambda b, h, j, r, i: (
        b, h * grp + r, 0, q_block(j, i)))
    with scope("kernels.flash"):
        return pl.pallas_call(
            functools.partial(_dkv_kernel, g=g),
            grid=(B, g.kv_heads, g.nk, grp, g.nq),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[kv_spec, kv_spec],
            out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            scratch_shapes=[pltpu.VMEM((g.block_k, hd), jnp.float32),
                            pltpu.VMEM((g.block_k, hd), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary",
                "arbitrary")),
            cost_estimate=_cost(g, 4, q, k, v, do, k, v),
            interpret=g.interpret,
            name="flash_bwd_dkv",
        )(q, k, v, do, lse, d)


# ---------------------------------------------------------------------------
# Backward: dQ
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
               dq_acc, lse_col, d_col, *, g: Geometry):
    i, j = pl.program_id(2), pl.program_id(3)
    q_start, k_start = i * g.block_q, j * g.block_k

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        lse_col[...] = _row_to_col(lse_ref[...], g.block_q)
        d_col[...] = _row_to_col(d_ref[...], g.block_q)

    def body(masked: bool):
        q, do = q_ref[...], do_ref[...]
        k, v = k_ref[...], v_ref[...]
        s = jax.lax.dot_general(q, k, NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s * g.scale - lse_col[...])    # (block_q, block_k)
        if masked:
            p = jnp.where(_valid(g, q_start, k_start, p.shape, 0), p, 0.0)
        dp = jax.lax.dot_general(do, v, NT, preferred_element_type=jnp.float32)
        ds = p * (dp - d_col[...])
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, NN, preferred_element_type=jnp.float32)

    _when_runs(g, q_start, k_start, body)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[...] = (dq_acc[...] * g.scale).astype(dq_ref.dtype)


def bwd_dq(q, k, v, do, lse, d, g: Geometry):
    """dQ (like q) of ``fwd``."""
    B = q.shape[0]
    q_spec, kv_spec, row_spec = _q_major_specs(g)
    with scope("kernels.flash"):
        return pl.pallas_call(
            functools.partial(_dq_kernel, g=g),
            grid=(B, g.heads, g.nq, g.nk),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((g.block_q, g.head_dim), jnp.float32),
                            pltpu.VMEM((g.block_q, 1), jnp.float32),
                            pltpu.VMEM((g.block_q, 1), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
            cost_estimate=_cost(g, 3, q, k, v, do, q),
            interpret=g.interpret,
            name="flash_bwd_dq",
        )(q, k, v, do, lse, d)

