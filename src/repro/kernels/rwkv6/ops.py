"""jit'd public wrapper: model-layout (B, S, H, hd) -> kernel layout."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import wkv6_fwd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, logw, u, s0, *, chunk: int = 128, interpret: bool = False):
    """r/k/v/logw: (B, S, H, hd); u: (H, hd); s0: (B, H, hd, hd).
    Returns (y (B, S, H, hd) fp32, S_final (B, H, hd, hd) fp32) —
    drop-in replacement for models.rwkv6.wkv_chunked."""
    B, S, H, hd = r.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    rf, kf, vf, lwf = map(fold, (r, k, v, logw))
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    s0f = s0.reshape(B * H, hd, hd).astype(jnp.float32)
    y, s_fin = wkv6_fwd(rf, kf, vf, lwf, uf, s0f, chunk=chunk,
                        interpret=interpret)
    y = y.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    return y, s_fin.reshape(B, H, hd, hd)
