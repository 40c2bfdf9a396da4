"""Decoder-only transformer LM (dense or uniform-MoE FFN) — pure JAX.

Covers qwen3-0.6b / llama3-8b / qwen1.5-4b / command-r-35b (dense),
qwen3-moe-235b / granite-moe-3b (MoE every layer), and the internvl2-1b LM
backbone (patch embeddings prepended by the vlm wrapper).

Layers are stacked on a leading axis and executed with ``lax.scan`` so the
lowered HLO is depth-independent; each layer body is optionally ``remat``'d.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.flash import flash_attention
from repro.obs.device import scope
from .common import (ArchConfig, apply_rope, chunked_attention, cross_entropy,
                     decode_attention, dense_init, embed_init, flash_applies,
                     full_attention, remat_wrap, rms_norm)
from . import moe as moe_lib


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_layer_params(key, cfg: ArchConfig):
    d, hd = cfg.d_model, cfg.head_dim
    H, KV, ff = cfg.n_heads, cfg.n_kv, cfg.d_ff
    ks = jax.random.split(key, 12)
    p = {
        "ln1": jnp.ones((d,), cfg.param_dtype),
        "ln2": jnp.ones((d,), cfg.param_dtype),
        "wq": dense_init(ks[0], (d, H * hd), cfg.param_dtype),
        "wk": dense_init(ks[1], (d, KV * hd), cfg.param_dtype),
        "wv": dense_init(ks[2], (d, KV * hd), cfg.param_dtype),
        "wo": dense_init(ks[3], (H * hd, d), cfg.param_dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), cfg.param_dtype)
        p["bk"] = jnp.zeros((KV * hd,), cfg.param_dtype)
        p["bv"] = jnp.zeros((KV * hd,), cfg.param_dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.param_dtype)
        p["k_norm"] = jnp.ones((hd,), cfg.param_dtype)
    if cfg.moe_experts > 0:
        p["moe"] = moe_lib.init_moe_params(ks[4], cfg)
    elif cfg.ffn_mult == 3:
        p["w_gate"] = dense_init(ks[5], (d, ff), cfg.param_dtype)
        p["w_up"] = dense_init(ks[6], (d, ff), cfg.param_dtype)
        p["w_down"] = dense_init(ks[7], (ff, d), cfg.param_dtype)
    else:
        p["w_up"] = dense_init(ks[6], (d, ff), cfg.param_dtype)
        p["b_up"] = jnp.zeros((ff,), cfg.param_dtype)
        p["w_down"] = dense_init(ks[7], (ff, d), cfg.param_dtype)
        p["b_down"] = jnp.zeros((d,), cfg.param_dtype)
    return p


def init_params(rng, cfg: ArchConfig):
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    layers = jax.vmap(lambda k: init_layer_params(k, cfg))(layer_keys)
    params = {
        "embed": embed_init(k_emb, (cfg.vocab, cfg.d_model), cfg.param_dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab),
                                       cfg.param_dtype)
    return params


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------

def _project_qkv(p, x, cfg: ArchConfig, *, heads_in_dot: bool = False):
    """(B, S, d) -> q (B, S, H, hd), k, v (B, S, KV, hd).

    ``heads_in_dot`` (the flash path) makes each projection an einsum into
    the split heads, so the compiler lays q, k and v out head-major for the
    kernels with no copy between.  Otherwise a matmul and a reshape, whose
    sharding over a "model" axis GSPMD handles with fewer collectives."""
    B, S, d = x.shape
    hd = cfg.head_dim
    if heads_in_dot:
        def project(name, n):
            w = p["w" + name].astype(x.dtype).reshape(d, n, hd)
            y = jnp.einsum("bsd,dnk->bsnk", x, w)
            if cfg.qkv_bias:
                y = y + p["b" + name].astype(x.dtype).reshape(n, hd)
            return y

        q, k, v = (project("q", cfg.n_heads), project("k", cfg.n_kv),
                   project("v", cfg.n_kv))
    else:
        q = x @ p["wq"].astype(x.dtype)
        k = x @ p["wk"].astype(x.dtype)
        v = x @ p["wv"].astype(x.dtype)
        if cfg.qkv_bias:
            q = q + p["bq"].astype(x.dtype)
            k = k + p["bk"].astype(x.dtype)
            v = v + p["bv"].astype(x.dtype)
        q = q.reshape(B, S, cfg.n_heads, hd)
        k = k.reshape(B, S, cfg.n_kv, hd)
        v = v.reshape(B, S, cfg.n_kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _ffn(p, x, cfg: ArchConfig):
    if cfg.moe_experts > 0:
        return moe_lib.moe_ffn(p["moe"], x, cfg)
    if cfg.ffn_mult == 3:
        h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) * \
            (x @ p["w_up"].astype(x.dtype))
        return h @ p["w_down"].astype(x.dtype)
    h = jax.nn.gelu(x @ p["w_up"].astype(x.dtype) + p["b_up"].astype(x.dtype),
                    approximate=True)
    return h @ p["w_down"].astype(x.dtype) + p["b_down"].astype(x.dtype)


def block_fwd(p, x, cfg: ArchConfig, *, positions, mode: str = "train",
              cache=None, pos=None):
    """mode: 'train'/'prefill' (full sequence) or 'decode' (1 token).

    A full sequence takes the Pallas flash kernels where
    ``flash_applies``, else ``full_attention`` (S <= ``attn_chunk``) or
    ``chunked_attention``.

    Returns (y, new_cache_kv) — new_cache_kv is (k, v) to store when
    building or updating a cache, else None placeholders.
    """
    flash = flash_applies(cfg, mode)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(p, h, cfg, heads_in_dot=flash)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if mode == "decode":
        k_cache, v_cache = cache
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), pos, axis=1)
        attn = decode_attention(q, k_cache, v_cache, pos)
        new_cache = (k_cache, v_cache)
    elif flash:
        with scope("model.attention"):
            attn = flash_attention(q, k, v, causal=True)
        new_cache = (k, v)
    else:
        g = cfg.q_per_kv
        if g > 1:
            kf = jnp.repeat(k, g, axis=2)
            vf = jnp.repeat(v, g, axis=2)
        else:
            kf, vf = k, v
        S = x.shape[1]
        with scope("model.attention"):
            if S > cfg.attn_chunk:
                attn = chunked_attention(q, kf, vf, causal=True,
                                         window=cfg.sliding_window,
                                         chunk=cfg.attn_chunk)
            else:
                attn = full_attention(q, kf, vf, causal=True,
                                      window=cfg.sliding_window)
        new_cache = (k, v)
    if flash:
        wo = p["wo"].astype(x.dtype).reshape(cfg.n_heads, cfg.head_dim, -1)
        x = x + jnp.einsum("bsnk,nkd->bsd", attn, wo)
    else:
        B, S = x.shape[:2]
        attn = attn.reshape(B, S, cfg.n_heads * cfg.head_dim)
        x = x + attn @ p["wo"].astype(x.dtype)
    if cfg.seq_parallel_residual and mode != "decode":
        from jax.sharding import PartitionSpec as P
        from .common import maybe_constrain
        x = maybe_constrain(x, P(("pod", "data"), "model", None))
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + _ffn(p, h, cfg)
    if cfg.seq_parallel_residual and mode != "decode":
        x = maybe_constrain(x, P(("pod", "data"), "model", None))
    return x, new_cache


# ---------------------------------------------------------------------------
# Full-model passes
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg, extra_embeds=None):
    from jax.sharding import PartitionSpec as P
    from .common import maybe_constrain
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(cfg.compute_dtype), x],
                            axis=1)
    # keep the residual stream batch-sharded after the vocab-sharded gather
    return maybe_constrain(x, P(("pod", "data"), None, None))


def _unembed(params, x, cfg):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else
            params["lm_head"]).astype(x.dtype)
    return x @ head


def forward_hidden(params, tokens, cfg: ArchConfig, extra_embeds=None):
    """Token ids -> final hidden states, scanning stacked layers."""
    with scope("model.embed"):
        x = _embed(params, tokens, cfg, extra_embeds)
    S = x.shape[1]
    positions = jnp.arange(S)

    body = remat_wrap(
        lambda x, pl: block_fwd(pl, x, cfg, positions=positions,
                                mode="train")[0],
        cfg.remat)

    def scan_body(x, pl):
        return body(x, pl), None

    with scope("model.blocks"):
        x, _ = jax.lax.scan(scan_body, x, params["layers"])
    return x


def loss_fn(params, batch, cfg: ArchConfig):
    x = forward_hidden(params, batch["tokens"], cfg,
                       batch.get("patch_embeds"))
    P = 0 if "patch_embeds" not in batch else batch["patch_embeds"].shape[1]
    x = x[:, P:]
    with scope("model.head_loss"):
        logits = _unembed(params, x, cfg)
        return cross_entropy(logits, batch["labels"])


def make_cache(cfg: ArchConfig, batch: int, cache_len: int,
               dtype=None):
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.num_layers, batch, cache_len, cfg.n_kv, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(params, tokens, cfg: ArchConfig, cache_len: int,
            extra_embeds=None):
    """Run the full prompt, build the KV cache, return last-position logits."""
    x = _embed(params, tokens, cfg, extra_embeds)
    B, S = x.shape[:2]
    positions = jnp.arange(S)

    def body(x, pl):
        y, (k, v) = block_fwd(pl, x, cfg, positions=positions, mode="prefill")
        pad = cache_len - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cfg.compute_dtype)
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cfg.compute_dtype)
        return y, (k, v)

    body = remat_wrap(body, cfg.remat) if cfg.remat != "none" else body
    x, (ks, vs) = jax.lax.scan(lambda c, pl: body(c, pl), x, params["layers"])
    logits = _unembed(params, x[:, -1:], cfg)
    return logits, {"k": ks, "v": vs}


def decode_step(params, cache, token, pos, cfg: ArchConfig):
    """One token in, one token's logits out; cache updated in place.

    ``token``: (B, 1) int32; ``pos``: scalar int32 — current write position
    (the cache already holds ``pos`` valid entries).
    """
    x = _embed(params, token, cfg)
    positions = pos + jnp.zeros((1,), jnp.int32)

    def scan_body(x, layer):
        pl, kc, vc = layer
        y, (k2, v2) = block_fwd(pl, x, cfg, positions=positions,
                                mode="decode", cache=(kc, vc), pos=pos)
        return y, (k2, v2)

    x, (ks, vs) = jax.lax.scan(scan_body, x,
                               (params["layers"], cache["k"], cache["v"]))
    logits = _unembed(params, x, cfg)
    return logits, {"k": ks, "v": vs}
