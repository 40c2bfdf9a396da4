"""jit'd public wrapper for the flash-attention kernel (GQA-aware)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_fwd


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, S, H, hd); k, v: (B, T, KV, hd), H % KV == 0.

    GQA: kv heads are broadcast to q heads *by index* (a reshape/broadcast
    of the (B, KV, T, hd) view — no per-q-head copy of K/V in HBM beyond
    the broadcast XLA will fuse).  Sequences are padded to block multiples;
    padded keys are masked inside the kernel via ``seq_k``.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    g = H // KV

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3)                    # (B, KV, T, hd)
    vf = v.transpose(0, 2, 1, 3)
    if g > 1:
        kf = jnp.broadcast_to(kf[:, :, None], (B, KV, g, T, hd))
        vf = jnp.broadcast_to(vf[:, :, None], (B, KV, g, T, hd))
    kf = kf.reshape(B * H, T, hd)
    vf = vf.reshape(B * H, T, hd)

    pad_q = (-S) % block_q
    pad_k = (-T) % block_k
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))

    out = flash_attention_fwd(qf, kf, vf, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    out = out[:, :S]
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
