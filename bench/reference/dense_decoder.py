"""Plain float32 reference of a dense decoder-only LM (Qwen2 / Qwen3).

Follows the Hugging Face ``Qwen2ForCausalLM`` / ``Qwen3ForCausalLM``
equations, from the published ``config.json`` keys alone:

    x = embed[tokens]
    per layer:  h = rms(x) ; q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
                Qwen3: q, k = rms(q) , rms(k)   (per head, before RoPE)
                q, k = rope(q), rope(k)         (rotate-half, rope_theta)
                a = softmax(q k^T / sqrt(head_dim), causal) v   (GQA: each
                    kv head serves num_attention_heads / kv heads queries)
                x = x + a Wo ; h = rms(x) ; x = x + (silu(h Wg) * h Wu) Wd
    logits = rms(x) head   (head = embed^T when tie_word_embeddings)
    loss = mean over tokens of -log softmax(logits)[label]

Every matmul is float32 at ``Precision.HIGHEST``. It imports nothing of the
program under test. ``init_params`` lays the weights out as the program
holds them (layers stacked on a leading axis, ``x @ W`` orientation), so one
seeded draw feeds both; the reference draws them itself from the seed.

``quant="fp8"`` is the lower-precision control, float8 training as it is
usually done: each matmul's operands are rounded to float8 e4m3 and, in the
backward pass, its incoming gradient to float8 e5m2, each under a
per-tensor scale, with float32 accumulation: the step a later change would
be tempted to take.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX, E5M2_MAX = 448.0, 57344.0
#: rows of a batch, and positions of a row, whose vocabulary-wide logits
#: exist at once
ROWS_PER_BLOCK, HEAD_CHUNK = 1, 512


# ---------------------------------------------------------------------------
# Shapes and weights
# ---------------------------------------------------------------------------

def dims(c: dict) -> dict:
    d = int(c["hidden_size"])
    h = int(c["num_attention_heads"])
    return {
        "d": d, "heads": h, "kv": int(c["num_key_value_heads"]),
        "hd": int(c.get("head_dim") or d // h),
        "ff": int(c["intermediate_size"]), "vocab": int(c["vocab_size"]),
        "layers": int(c["num_hidden_layers"]),
        "tied": bool(c["tie_word_embeddings"]),
        "bias": bool(c.get("attention_bias", c["model_type"] == "qwen2")),
        "qk_norm": c["model_type"] == "qwen3",
        "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
    }


def param_shapes(c: dict) -> dict:
    """Leaf name -> shape, in the program's layout."""
    m = dims(c)
    d, L, hq, hk = m["d"], m["layers"], m["heads"] * m["hd"], m["kv"] * m["hd"]
    layer = {"ln1": (d,), "ln2": (d,), "wq": (d, hq), "wk": (d, hk),
             "wv": (d, hk), "wo": (hq, d), "w_gate": (d, m["ff"]),
             "w_up": (d, m["ff"]), "w_down": (m["ff"], d)}
    if m["bias"]:
        layer.update(bq=(hq,), bk=(hk,), bv=(hk,))
    if m["qk_norm"]:
        layer.update(q_norm=(m["hd"],), k_norm=(m["hd"],))
    out = {"embed": (m["vocab"], d), "final_norm": (d,),
           "layers": {k: (L,) + s for k, s in layer.items()}}
    if not m["tied"]:
        out["lm_head"] = (d, m["vocab"])
    return out


def init_params(key, c: dict) -> dict:
    """Seeded float32 weights: normal(0, initializer_range) for matrices,
    zeros for biases, ones for norm scales (the published init)."""
    std = float(c["initializer_range"])
    shapes = param_shapes(c)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = getattr(path[-1], "key", "")
        if "norm" in name or name in ("ln1", "ln2"):
            leaves.append(jnp.ones(shape, jnp.float32))
        elif name in ("bq", "bk", "bv"):
            leaves.append(jnp.zeros(shape, jnp.float32))
        else:
            leaves.append(std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, leaves)


# ---------------------------------------------------------------------------
# Forward pass and loss
# ---------------------------------------------------------------------------

def _to_fp8(x, dtype, largest):
    """``x`` rounded to float8 ``dtype`` under a per-tensor scale."""
    scale = jnp.max(jnp.abs(x)) / largest
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(eq, a, b):
    return _mm_fp8_fwd(eq, a, b)[0]


def _mm_fp8_fwd(eq, a, b):
    qa = _to_fp8(a, jnp.float8_e4m3fn, E4M3_MAX)
    qb = _to_fp8(b, jnp.float8_e4m3fn, E4M3_MAX)
    return _einsum(eq, qa, qb), (qa, qb)


def _mm_fp8_bwd(eq, res, g):
    _, vjp = jax.vjp(functools.partial(_einsum, eq), *res)
    return vjp(_to_fp8(g, jnp.float8_e5m2, E5M2_MAX))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(eq, a, b, quant):
    if quant == "fp8":
        return _mm_fp8(eq, a, b)
    if quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return _einsum(eq, a, b)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (b, s, h, hd); rotate-half convention."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _layer(m, quant):
    def layer(x, p):
        b, s, _ = x.shape
        h = _rms(x, p["ln1"], m["eps"])
        q = _mm("bsd,df->bsf", h, p["wq"], quant)
        k = _mm("bsd,df->bsf", h, p["wk"], quant)
        v = _mm("bsd,df->bsf", h, p["wv"], quant)
        if m["bias"]:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(b, s, m["heads"], m["hd"])
        k = k.reshape(b, s, m["kv"], m["hd"])
        v = v.reshape(b, s, m["kv"], m["hd"])
        if m["qk_norm"]:
            q = _rms(q, p["q_norm"], m["eps"])
            k = _rms(k, p["k_norm"], m["eps"])
        q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
        group = m["heads"] // m["kv"]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        scores = _mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(m["hd"])
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        a = _mm("bhqk,bkhd->bqhd", probs, v, quant)
        x = x + _mm("bsf,fd->bsd", a.reshape(b, s, -1), p["wo"], quant)
        h = _rms(x, p["ln2"], m["eps"])
        g = _mm("bsd,df->bsf", h, p["w_gate"], quant)
        u = _mm("bsd,df->bsf", h, p["w_up"], quant)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"], quant)
        return x, None
    return layer


def _head_nll(x, labels, head, quant):
    logits = _mm("bsd,dv->bsv", x, head, quant)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)


def nll_sum(params, tokens, labels, c: dict, quant=None):
    """Summed next-token negative log-likelihood of a block of rows; the
    vocabulary-wide logits exist ``HEAD_CHUNK`` positions at a time."""
    m = dims(c)
    x = params["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(_layer(m, quant)), x,
                        params["layers"])
    x = _rms(x, params["final_norm"], m["eps"])
    head = params["embed"].T if m["tied"] else params["lm_head"]
    b, s, d = x.shape
    chunk = min(HEAD_CHUNK, s)
    n = s // chunk
    xs = x.reshape(b, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(b, n, chunk).swapaxes(0, 1)
    head_nll = jax.checkpoint(lambda xc, lc: _head_nll(xc, lc, head, quant))

    def body(total, xl):
        return total + head_nll(*xl), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, ls))
    return total


def loss_and_grads(params, tokens, labels, c: dict, *, quant=None):
    """Mean loss over all tokens and its gradient, computed
    ``ROWS_PER_BLOCK`` rows at a time so that one block's activations fit."""
    B, S = tokens.shape
    nb = B // ROWS_PER_BLOCK
    tb = tokens.reshape(nb, ROWS_PER_BLOCK, S)
    lb = labels.reshape(nb, ROWS_PER_BLOCK, S)
    grad_fn = jax.value_and_grad(nll_sum)

    def body(acc, blk):
        loss, grads = acc
        l, g = grad_fn(params, blk[0], blk[1], c, quant)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros), (tb, lb))
    n = float(B * S)
    return loss / n, jax.tree.map(lambda g: g / n, grads)
