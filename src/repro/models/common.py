"""Shared model building blocks (pure JAX, functional, scan-over-layers).

Conventions:
  - layer params are *stacked* on a leading ``L`` axis and consumed through
    ``jax.lax.scan`` so the HLO stays compact regardless of depth (critical
    for the 512-device dry-run compiles);
  - params live in ``param_dtype`` (fp32 for training masters, bf16 for
    serving) and are cast to ``compute_dtype`` at use;
  - attention supports GQA, optional qk-norm, optional QKV bias, RoPE
    on/off, sliding windows, and three execution paths in XLA: full (short
    sequences), *chunked* flash-style (long prefill — online softmax over
    query blocks, never materializing the S x S score matrix), and
    single-token decode against a fixed-size KV cache.  On a TPU a full
    sequence takes the Pallas flash kernels instead (``flash_applies``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One config object covers every assigned family via feature flags."""
    name: str
    family: str                   # dense | moe | hybrid | ssm | vlm | audio
    num_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    d_head: int = 0               # 0 -> d_model // n_heads
    qk_norm: bool = False
    qkv_bias: bool = False
    attn_out_bias: bool = False
    tie_embeddings: bool = False
    ffn_mult: int = 3             # 3 = SwiGLU, 2 = plain GELU MLP
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_every: int = 1            # layer i is MoE iff experts>0 and i%every==0
    capacity_factor: float = 1.25
    # hybrid (Jamba): within a period of ``attn_every`` layers, exactly one
    # attention layer, the rest Mamba.  0 disables (pure attention).
    attn_every: int = 0
    mamba_d_state: int = 16
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    # rwkv
    rwkv: bool = False
    rwkv_head_dim: int = 64
    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_frames: int = 1500
    # vlm
    moe_ff_chunks: int = 1        # scan expert matmuls over ff blocks:
    # bounds the live bytes of FSDP-gathered expert weights (jamba's
    # 8192x24576 experts otherwise hold ~GBs gathered per layer)
    patch_tokens: int = 0         # stub ViT patch embeddings, prepended
    # positional / numerics
    use_rope: bool = True
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    sliding_window: int = 0       # >0: attention window (for long contexts)
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # runtime knobs (hillclimbing handles)
    attn_chunk: int = 1024        # query-block size of chunked attention
    scan_chunk: int = 256         # time-chunk of SSM/RWKV linear scans
    remat: str = "layer"          # none | layer | dots
    train_microbatches: int = 0   # 0 = auto (launch/steps.py policy)
    use_pallas: bool = False      # route RWKV's WKV through its Pallas kernel
    seq_parallel_residual: bool = False
    # ^ Megatron-SP-style: keep the residual stream sequence-sharded over
    #   "model" between blocks, so XLA lowers the per-layer TP sync as
    #   all-gather + reduce-scatter (payload S*d bf16) instead of a full
    #   all-reduce (2x S*d) — §Perf iteration 3.

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv

    def layer_kind(self, i: int) -> str:
        """'attn' | 'mamba' for layer i of a hybrid stack."""
        if self.rwkv:
            return "rwkv"
        if self.attn_every <= 0:
            return "attn"
        return "attn" if (i % self.attn_every) == (self.attn_every - 1) else "mamba"

    def is_moe_layer(self, i: int) -> bool:
        return self.moe_experts > 0 and (i % self.moe_every) == (self.moe_every - 1)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis: int = -2):
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms / activations / rope
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x, w_up, b_up, w_down, b_down):
    h = jax.nn.gelu(x @ w_up + b_up, approximate=True)
    return h @ w_down + b_down


def rope_angles(positions, head_dim: int, theta: float):
    """positions: (...,) int32 -> (..., head_dim//2) angles."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    return positions.astype(jnp.float32)[..., None] * freqs


def apply_rope(x, positions, theta: float):
    """x: (..., S, n, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    ang = rope_angles(positions, hd, theta)          # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                 # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — three execution paths
# ---------------------------------------------------------------------------

def _repeat_kv(k, q_per_kv: int):
    """(B, T, KV, hd) -> (B, T, KV*G, hd)."""
    b, t, kv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, t, kv, q_per_kv, hd))
    return k.reshape(b, t, kv * q_per_kv, hd)


def _heads_spec():
    """Preferred layout of (B, S, H, hd) attention tensors: batch over the
    data axes, heads over "model".  Without the explicit constraint GSPMD
    keeps heads replicated whenever the kv-head count doesn't divide the
    model axis (the repeat-kv path), running attention 16x redundantly —
    measured in EXPERIMENTS.md §Perf iteration 0."""
    from jax.sharding import PartitionSpec as P
    return P(("pod", "data"), None, "model", None)


def _kv_seq_spec():
    """Fallback when the head count doesn't divide the model axis (e.g.
    whisper's 12 heads on a 16-wide mesh): shard the KEY sequence instead
    (sequence-parallel attention; XLA inserts the softmax psums)."""
    from jax.sharding import PartitionSpec as P
    return P(("pod", "data"), "model", None, None)


def _heads_divide_model(h: int) -> bool:
    """True unless an active mesh has a "model" axis that ``h`` does not
    fill evenly."""
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return True
    return h % mesh.shape["model"] == 0


def flash_applies(cfg: ArchConfig, mode: str) -> bool:
    """Whether a block's attention takes the Pallas flash kernels
    (``kernels/flash``): on a TPU, over a full sequence (train or prefill),
    with no window, at a lane-aligned head_dim, and where no auto mesh axis
    of size > 1 could shard the attention tensors.  A ``pallas_call`` is
    opaque to GSPMD, which would gather it whole; a manual axis (the
    pipeline's "stage", inside its ``shard_map``) shards nothing here."""
    if jax.default_backend() != "tpu" or mode not in ("train", "prefill"):
        return False
    if cfg.sliding_window > 0 or cfg.head_dim % 128:
        return False
    mesh = jax.sharding.get_abstract_mesh()
    return not any(n > 1 and t != jax.sharding.AxisType.Manual
                   for n, t in zip(mesh.axis_sizes, mesh.axis_types))


def full_attention(q, k, v, *, causal: bool = True, window: int = 0,
                   q_offset: int = 0):
    """q: (B, S, H, hd); k, v: (B, T, H, hd).  Returns (B, S, H, hd).

    Materializes (B, H, S, T) scores — use only when S*T is small/medium;
    ``chunked_attention`` covers the long-sequence path.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    if _heads_divide_model(h):
        q = maybe_constrain(q, _heads_spec())
        k = maybe_constrain(k, _heads_spec())
        v = maybe_constrain(v, _heads_spec())
    else:
        k = maybe_constrain(k, _kv_seq_spec())
        v = maybe_constrain(v, _kv_seq_spec())
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(s) + q_offset
    kpos = jnp.arange(t)
    mask = jnp.ones((s, t), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      chunk: int = 1024):
    """Flash-style attention in pure jnp: scan over query blocks with an
    online softmax, so peak memory is (B, H, chunk, T) instead of
    (B, H, S, T)."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    if _heads_divide_model(h):
        q = maybe_constrain(q, _heads_spec())
        k = maybe_constrain(k, _heads_spec())
        v = maybe_constrain(v, _heads_spec())
    else:
        k = maybe_constrain(k, _kv_seq_spec())
        v = maybe_constrain(v, _kv_seq_spec())
    if s % chunk != 0:
        pad = chunk - s % chunk
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s_pad = s + pad
    else:
        pad, s_pad = 0, s
    nq = s_pad // chunk
    qb = q.reshape(b, nq, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    from jax.sharding import PartitionSpec as P
    qb = maybe_constrain(qb, P(None, ("pod", "data"), None, "model", None))
    scale = 1.0 / math.sqrt(hd)
    kpos = jnp.arange(t)

    def do_block(i, q_blk):
        qpos = i * chunk + jnp.arange(chunk)
        scores = jnp.einsum("bshd,bthd->bhst", q_blk, k).astype(jnp.float32)
        scores = scores * scale
        mask = jnp.ones((chunk, t), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > (qpos[:, None] - window)
        scores = jnp.where(mask[None, None], scores, -1e30)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m)
        num = jnp.einsum("bhst,bthd->bshd", p.astype(q_blk.dtype), v)
        den = jnp.sum(p, axis=-1).transpose(0, 2, 1)[..., None]  # (b,s,h,1)
        return (num / jnp.maximum(den, 1e-30).astype(num.dtype))

    out = jax.lax.map(lambda args: do_block(*args),
                      (jnp.arange(nq), qb))
    out = maybe_constrain(out, P(None, ("pod", "data"), None, "model", None))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, s_pad, h, hd)
    return out[:, :s] if pad else out


def decode_attention(q, k_cache, v_cache, pos):
    """Single-token decode: q (B, 1, H, hd) against a fixed-size cache
    (B, T, KV, hd); only entries < pos+1 participate."""
    b, _, h, hd = q.shape
    t, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(hd)
    q = maybe_constrain(q, _heads_spec())
    qg = q.reshape(b, 1, kv, g, hd)
    scores = jnp.einsum("bqkgh,btkh->bkgqt", qg, k_cache).astype(jnp.float32)
    scores = scores * scale
    valid = jnp.arange(t)[None, None, None, None, :] <= pos
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqt,btkh->bqkgh", probs, v_cache)
    return out.reshape(b, 1, h, hd)


# ---------------------------------------------------------------------------
# Chunked linear recurrence  h_t = a_t * h_{t-1} + x_t   (SSM / RWKV carrier)
# ---------------------------------------------------------------------------

def chunked_linear_scan(a, x, h0, chunk: int = 256):
    """Solve h_t = a_t (*) h_{t-1} + x_t along axis 1 (time) in chunks.

    a, x: (B, S, ...) with matching trailing dims; h0: (B, ...).
    Sequential lax.scan over S/chunk chunks; inside a chunk, an associative
    scan — the standard memory/throughput trade used by chunked SSM kernels
    (keeps the transient state S_chunk x state instead of S x state).
    """
    b, s = x.shape[:2]
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    a_c = a.reshape((b, n, chunk) + a.shape[2:]).swapaxes(0, 1)
    x_c = x.reshape((b, n, chunk) + x.shape[2:]).swapaxes(0, 1)

    def combine(c1, c2):
        a1, u1 = c1
        a2, u2 = c2
        return a1 * a2, a2 * u1 + u2

    def step(h, ax):
        a_k, x_k = ax                                  # (B, chunk, ...)
        aa, uu = jax.lax.associative_scan(combine, (a_k, x_k), axis=1)
        h_all = aa * h[:, None] + uu                   # prefix-applied carry
        return h_all[:, -1], h_all

    h_last, ys = jax.lax.scan(step, h0, (a_c, x_c))
    ys = ys.swapaxes(0, 1).reshape((b, s) + x.shape[2:])
    return h_last, ys


# ---------------------------------------------------------------------------
# Cross entropy (computed in fp32, logits never stored beyond the microbatch)
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, ignore_id: int = -1):
    """logits (B, S, V) any float dtype; labels (B, S) int32.

    Logits stay in their compute dtype; only the *reductions* accumulate in
    fp32 (XLA fuses the convert into the reduce) — an fp32 copy of the
    vocab-sized logits never materializes in HBM.  Measured: -4.6 GiB/device
    on qwen3-0.6b train_4k (EXPERIMENTS.md §Perf iteration 0).
    """
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1, dtype=jnp.float32)
    gold = jnp.take_along_axis(shifted, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    nll = jnp.log(sumexp) - gold.astype(jnp.float32)
    mask = (labels != ignore_id)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def maybe_constrain(x, spec):
    """Sharding-constrain ``x`` when a named mesh is active (``jax.set_mesh``);
    with no mesh it returns ``x`` unchanged.  Axis entries absent from the
    mesh, or whose size does not divide the dim, are dropped from the spec.
    Lets model code state its preferred layout on any mesh, or none."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    fixed = []
    for i, ax in enumerate(spec):
        axes = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
        axes = tuple(a for a in axes if a in mesh.axis_names)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if axes and x.shape[i] % size == 0:
            fixed.append(axes if len(axes) > 1 else axes[0])
        else:
            fixed.append(None)
    from jax.sharding import PartitionSpec
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*fixed))


def remat_wrap(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "layer":
        return jax.checkpoint(fn)
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    raise ValueError(mode)
