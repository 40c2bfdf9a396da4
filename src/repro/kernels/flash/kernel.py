"""Flash-attention forward — Pallas TPU kernel.

TPU-native tiling (not a CUDA port): the grid is (batch*heads, q-blocks,
k-blocks) with the *k-block axis innermost* — on TPU the innermost grid
dimension executes sequentially on a core, so the online-softmax
accumulators (m, l, acc) live in VMEM scratch and persist across k-steps.
Block shapes are (block_q, head_dim) / (block_k, head_dim) with
MXU-friendly 128-multiples; the (S, T) score matrix never exists — only a
(block_q, block_k) tile at a time, resident in VMEM.

GQA is handled at zero memory cost by the BlockSpec index_map: the kv-head
index is derived from the q-head index (h * KV) // H, so KV tensors are
never materialized per-q-head.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      block_q: int, block_k: int, causal: bool, scale: float,
                      seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def body():
        q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                       # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                 # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                              # (bq, bk)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    if causal:
        # skip blocks fully above the diagonal
        pl.when(k_start <= q_start + block_q - 1)(body)
    else:
        body()

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """q: (BH, Sq, hd) fp/bf16; k, v: (BKV, Sk, hd) where the kv-head of
    q-head h is resolved by the caller reshaping BH == B*H, BKV == B*KV and
    passing the per-head mapping via ``kv_map`` — see ops.flash_attention.

    This low-level entry expects BH == BKV (kv already head-aligned);
    ops.py does the GQA index mapping.
    """
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)

    grid = (BH, nq, nk)
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, seq_q=Sq, seq_k=Sk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            # m, l, acc accumulators in VMEM, persist across the k axis
            # (innermost grid dim is sequential on a TPU core)
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
