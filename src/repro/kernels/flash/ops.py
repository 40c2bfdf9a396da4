"""Flash attention with its gradient: the kernels of ``kernel.py`` under a
``jax.custom_vjp`` (GQA-aware, causal or not)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import kernel
from .kernel import Geometry, block_sizes


def flash_attention(q, k, v, *, causal: bool = True, interpret: bool = False,
                    block_q: int | None = None, block_k: int | None = None):
    """q: (B, S, H, hd); k, v: (B, T, KV, hd), H % KV == 0 -> (B, S, H, hd).

    Differentiable in q, k and v.  The kernels take heads first; the
    transposes to and from (B, H, S, hd) cost nothing where the producer
    can write that layout (``block_fwd``'s einsum projections).  Sequences
    are padded to the blocks and padded keys are masked.
    ``block_q``/``block_k`` default to ``block_sizes(max(S, T), hd)``.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    bq, bk = block_sizes(max(S, T), hd)
    bq, bk = block_q or bq, block_k or bk
    sq, sk = -(-S // bq) * bq, -(-T // bk) * bk
    g = Geometry(heads=H, kv_heads=KV, head_dim=hd, seq_q=sq, seq_k=sk,
                 keys=T, block_q=bq, block_k=bk, causal=causal,
                 interpret=interpret)
    heads = lambda x, n: _pad(x.transpose(0, 2, 1, 3), n)
    out = _attention(heads(q, sq), heads(k, sk), heads(v, sk), g)
    return out[:, :, :S].transpose(0, 2, 1, 3)


def _fully_manual(fn):
    """``fn`` inside a ``shard_map`` over every axis of the current mesh,
    with its arguments and results whole on each device, where the mesh has
    an auto axis.  A Mosaic kernel cannot be partitioned automatically, and
    even auto axes of size 1 (the pipeline's "data" and "model" around its
    manual "stage") ask for that.  The map also names the axes an enclosing
    ``shard_map`` made manual: the kernel's lowering sees only the innermost
    map's axes.  ``flash_applies`` leaves no auto axis larger than 1, on
    which this would gather the operands whole.

    Only the custom VJP's rules call this, so autodiff never transposes
    the map: its transpose takes the operands as replicated over the axes
    it names, and would average each stage's gradients over "stage"."""
    mesh = jax.sharding.get_abstract_mesh()
    if all(t == jax.sharding.AxisType.Manual for t in mesh.axis_types):
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         axis_names=set(mesh.axis_names), check_vma=False)


def _pad(x, n: int):
    return x if x.shape[2] == n else jnp.pad(
        x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, g: Geometry):
    """o of ``kernel.fwd``; the backward runs ``kernel.bwd_dkv`` and
    ``kernel.bwd_dq`` on the saved logsumexp."""
    return _attention_fwd(q, k, v, g)[0]


def _attention_fwd(q, k, v, g: Geometry):
    o, lse = _fully_manual(functools.partial(kernel.fwd, g=g))(q, k, v)
    return o, (q, k, v, o, lse)


def _attention_bwd(g: Geometry, res, do):
    return _fully_manual(functools.partial(_grads, g=g))(*res, do)


def _grads(q, k, v, o, lse, do, g: Geometry):
    d = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                keepdims=True).transpose(0, 1, 3, 2)      # (B, H, 1, S)
    dk, dv = kernel.bwd_dkv(q, k, v, do, lse, d, g)
    dq = kernel.bwd_dq(q, k, v, do, lse, d, g)
    return dq, dk, dv


_attention.defvjp(_attention_fwd, _attention_bwd)
