"""Mixture-of-Experts FFN with per-row sort-based capacity dispatch.

TPU/GSPMD-friendly routing: top-k assignment, sorting, and capacity
dropping all happen *per batch row* (vmap over the batch axis, which is
sharded over "data") — so routing never induces a global cross-device
sort.  The dense (B, E, C, d) dispatch buffer is then sharding-constrained
to expert-parallel layout (E on "model") when E divides the axis, which
makes XLA lower the dispatch as the canonical token all-to-all; otherwise
(e.g. granite's 40 experts on a 16-wide axis) experts stay replicated over
"model" and the per-expert FFN hidden dim is sharded instead (tensor
parallelism inside each expert).

FLOP accounting matches 6*N_active*D: expert matmuls cost ~ k*N*d*ff
(+ router N*d*E); capacity overflow tokens are dropped (residual keeps
them alive) — standard capacity-factor semantics.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ArchConfig, dense_init


def init_moe_params(key, cfg: ArchConfig):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), cfg.param_dtype),
        "w_gate": dense_init(ks[1], (E, d, ff), cfg.param_dtype, in_axis=-2),
        "w_up": dense_init(ks[2], (E, d, ff), cfg.param_dtype, in_axis=-2),
        "w_down": dense_init(ks[3], (E, ff, d), cfg.param_dtype, in_axis=-2),
    }


def expert_capacity(tokens_per_row: int, cfg: ArchConfig) -> int:
    c = math.ceil(cfg.moe_top_k * tokens_per_row / cfg.moe_experts
                  * cfg.capacity_factor)
    return max(4, -(-c // 4) * 4)


# sharding helper shared with the executor: drops axis entries that are
# absent from the mesh or don't divide the dim (e.g. granite's 40 experts on
# a 16-wide model axis -> per-expert hidden dim carries the parallelism).
from .common import maybe_constrain as _maybe_constrain


def _experts_shardable(E: int) -> bool:
    """True unless an active mesh has a "model" axis that ``E`` experts do
    not fill evenly."""
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return True
    return E % mesh.shape["model"] == 0


def _route_row(x_row, logits_row, C: int, E: int, K: int):
    """Per-row dispatch: x_row (S, d), logits_row (S, E) ->
    (buf (E, C, d), combine info).

    Combine info is *slot-major*: tok_slot/w_slot are (E, C) arrays giving
    each capacity slot its source token (S = empty sentinel) and gate
    weight — so the combine can scatter per expert SHARD and psum token-
    sized partials, instead of gathering the whole (E*C, d) buffer across
    the expert axis (8 GiB/layer measured on qwen3-moe prefill)."""
    S, d = x_row.shape
    probs = jax.nn.softmax(logits_row, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)            # (S, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    flat_e = gate_idx.reshape(-1)                            # (S*K,)
    flat_t = jnp.repeat(jnp.arange(S), K)
    flat_w = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    first = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(S * K) - first[se]
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)              # E*C = trash

    buf = jnp.zeros((E * C + 1, d), x_row.dtype).at[slot].set(x_row[st])
    tok_slot = jnp.full((E * C + 1,), S, jnp.int32).at[slot].set(
        st.astype(jnp.int32))
    w_slot = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        sw.astype(jnp.float32))
    return (buf[:-1].reshape(E, C, d),
            (tok_slot[:-1].reshape(E, C), w_slot[:-1].reshape(E, C),
             keep, slot, st, sw))


def _combine_row_scatter(out, info, S: int, d: int):
    """out (E, C, d) [expert-sharded] -> y (S, d).

    Scatter per expert into (S+1, d) partials, then sum over E — under
    GSPMD the e-axis sum lowers as a token-sized psum (the inverse
    all-to-all), never an all-gather of the capacity buffer (−16%
    collective bytes on qwen3-moe train_4k).  The (E_loc, S+1, d) partials
    scale with S, so this path is for short sequences; the gather path
    covers long prefill (§Perf iteration B3)."""
    tok_slot, w_slot = info[0], info[1]                      # (E, C)
    weighted = out * w_slot[..., None].astype(out.dtype)

    def per_expert(o_e, t_e):
        return jnp.zeros((S + 1, d), out.dtype).at[t_e].add(o_e)

    partials = jax.vmap(per_expert)(weighted, tok_slot)      # (E, S+1, d)
    return partials.sum(axis=0)[:S]


def _combine_row_gather(out_flat, info, S: int, d: int):
    """Pair-indexed gather combine: O(S*K) memory regardless of S."""
    keep, slot, st, sw = info[2], info[3], info[4], info[5]
    gathered = jnp.where(
        keep[:, None], out_flat[jnp.minimum(slot, out_flat.shape[0] - 1)],
        jnp.zeros((1, d), out_flat.dtype))
    contrib = gathered * sw[:, None].astype(out_flat.dtype)
    return jnp.zeros((S, d), out_flat.dtype).at[st].add(contrib)


def moe_ffn(p, x, cfg: ArchConfig):
    """x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    C = expert_capacity(S, cfg)

    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
    buf, info = jax.vmap(
        lambda xr, lr: _route_row(xr, lr, C, E, K))(x, logits)
    # Pin the dispatch-buffer layout.  Leaving the batch dim unspecified
    # lets GSPMD pick a contraction-sharded einsum that ALL-GATHERS the
    # whole (B, E*C, d) buffer (60 GiB/device on granite prefill_32k —
    # EXPERIMENTS.md §Perf iteration 0).  Expert-parallel when E divides
    # the model axis (-> token all-to-all), else batch-only with the
    # per-expert hidden dim carrying "model".
    bd = ("pod", "data")
    e_par = _experts_shardable(E)
    buf = _maybe_constrain(
        buf, P(bd, "model", None, None) if e_par else P(bd, None, None, None))

    n = max(1, cfg.moe_ff_chunks)
    if n > 1 and cfg.d_ff % n == 0:
        # scan over ff blocks: weights become scan xs, so the FSDP
        # all-gather happens per-slice inside the loop — at most one
        # (E_local, d, ff/n) block is ever live in gathered form.
        ffc = cfg.d_ff // n
        wg = p["w_gate"].reshape(E, cfg.d_model, n, ffc).transpose(2, 0, 1, 3)
        wu = p["w_up"].reshape(E, cfg.d_model, n, ffc).transpose(2, 0, 1, 3)
        wd = p["w_down"].reshape(E, n, ffc, cfg.d_model).transpose(1, 0, 2, 3)

        def ff_step(acc, ws):
            g, u, dn = (w.astype(x.dtype) for w in ws)
            h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, g))
            h = h * jnp.einsum("becd,edf->becf", buf, u)
            return acc + jnp.einsum("becf,efd->becd", h, dn), None

        # NOTE: no remat on ff_step — the scan structure alone bounds the
        # live gathered-weight bytes, and rematting it re-gathers every
        # chunk in the backward (+50% FLOPs, 3x collective bytes, measured).
        out, _ = jax.lax.scan(ff_step, jnp.zeros_like(buf), (wg, wu, wd))
    else:
        h_spec = (P(bd, "model", None, None) if e_par
                  else P(bd, None, None, "model"))
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf,
                                   p["w_gate"].astype(x.dtype)))
        h = _maybe_constrain(h, h_spec)
        h = h * jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(x.dtype))
        out = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(x.dtype))
    out = _maybe_constrain(
        out, P(bd, "model", None, None) if e_par else P(bd, None, None, None))

    if S <= 8192:       # scatter+psum combine: token-sized collective
        y = jax.vmap(lambda o, i: _combine_row_scatter(o, i, S, d))(out, info)
    else:               # long prefill: S-sized partials would dominate HBM
        y = jax.vmap(lambda o, i: _combine_row_gather(
            o.reshape(E * C, d), i, S, d))(out, info)
    return y


def aux_load_balance_loss(logits, gate_idx, cfg: ArchConfig):
    """Switch-style auxiliary loss (optional; wired via --moe-aux)."""
    E = cfg.moe_experts
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = probs.mean(axis=tuple(range(probs.ndim - 1)))
    ce = jnp.zeros((E,)).at[gate_idx.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    return E * jnp.sum(me * ce)
