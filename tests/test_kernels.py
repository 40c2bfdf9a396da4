"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs the jnp oracle
(explicit interpret mode executes the kernel body on CPU; the TPU compile
is covered by tests/test_tpu_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash import attention_ref, flash_attention
from repro.kernels.rwkv6 import wkv6, wkv6_ref
from repro.models.rwkv6 import wkv_chunked


FLASH_SWEEP = [
    # (B, S, T, H, KV, hd, causal, block or (block_q, block_k)); None:
    # ``block_sizes``
    (1, 64, 64, 2, 2, 32, True, 32),                # MHA
    (2, 128, 128, 4, 2, 64, True, 64),              # GQA 2:1
    (1, 200, 200, 4, 4, 64, True, 64),              # non-multiple of block
    (2, 128, 256, 8, 2, 128, False, 64),            # cross lengths, GQA 4:1
    (1, 96, 96, 8, 1, 64, True, 32),                # MQA
    (1, 200, 200, 4, 2, 128, True, None),           # GQA 2:1, chosen blocks
    (2, 160, 160, 4, 2, 64, True, (64, 32)),        # block_q > block_k
    (1, 160, 160, 2, 2, 64, True, (32, 64)),        # block_q < block_k
    (1, 1024, 1024, 2, 1, 128, True, None),         # one 1024 block
    (1, 1100, 1100, 2, 1, 128, True, None),         # two, the last padded
]


def _flash_refs(causal, g):
    """The two plain references, each as f(q, k, v) on (B, S, H, hd) q and
    (B, T, KV, hd) k, v."""
    from repro.models.common import _repeat_kv, full_attention

    def full(q, k, v):
        return full_attention(q, _repeat_kv(k, g), _repeat_kv(v, g),
                              causal=causal)
    return {"attention_ref": lambda q, k, v: attention_ref(q, k, v,
                                                           causal=causal),
            "full_attention": full}


@pytest.mark.parametrize("B,S,T,H,KV,hd,causal,blk", FLASH_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_oracle(B, S, T, H, KV, hd, causal, blk, dtype):
    """Output and dQ, dK, dV of the kernels (interpret mode) against
    ``attention_ref`` and ``full_attention`` taken in float32 on the same
    inputs: the kernels round P and dS to the input dtype as matmul
    operands only."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(42), 4)
    q = jax.random.normal(k1, (B, S, H, hd), dtype)
    k = jax.random.normal(k2, (B, T, KV, hd), dtype)
    v = jax.random.normal(k3, (B, T, KV, hd), dtype)
    do = jax.random.normal(k4, (B, S, H, hd), dtype)
    bq, bk = blk if isinstance(blk, tuple) else (blk, blk)
    out, back = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True),
        q, k, v)
    got = (out,) + back(do)
    assert [x.dtype for x in got] == [dtype] * 4
    f32 = lambda x: x.astype(jnp.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for name, ref in _flash_refs(causal, H // KV).items():
        r_out, r_back = jax.vjp(ref, f32(q), f32(k), f32(v))
        want = (r_out,) + r_back(f32(do))
        for what, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            b = np.asarray(b)
            # gradients are checked against their own scale
            atol = tol if what == "o" else tol * float(np.abs(b).max())
            np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                       rtol=tol, atol=atol,
                                       err_msg=f"{name} {what}")


WKV_SWEEP = [
    # (B, S, H, hd, chunk)
    (1, 64, 1, 16, 16),
    (2, 128, 2, 32, 32),
    (1, 256, 4, 64, 64),
    (2, 96, 2, 8, 32),
    (1, 128, 2, 64, 128),                   # single chunk == full seq
]


@pytest.mark.parametrize("B,S,H,hd,chunk", WKV_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_kernel_matches_oracle(B, S, H, hd, chunk, dtype):
    rng = jax.random.PRNGKey(7)
    ks = jax.random.split(rng, 6)
    r = (jax.random.normal(ks[0], (B, S, H, hd)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, S, H, hd)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, S, H, hd)) * 0.5).astype(dtype)
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.2
    y_ref, s_ref = wkv6_ref(r, k, v, logw, u, s0)
    y, s = wkv6(r, k, v, logw, u, s0, chunk=chunk, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,S,H,hd,chunk", WKV_SWEEP[:3])
def test_wkv6_jnp_chunked_matches_oracle(B, S, H, hd, chunk):
    """The model's default (non-Pallas) chunked path is the same math."""
    rng = jax.random.PRNGKey(11)
    ks = jax.random.split(rng, 6)
    r = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, hd)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.2
    y_ref, s_ref = wkv6_ref(r, k, v, logw, u, s0)
    y, s = wkv_chunked(r, k, v, logw, u, s0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-4)


def test_wkv6_state_threading():
    """Chunked with carried state == one long sequence split in two."""
    rng = jax.random.PRNGKey(3)
    ks = jax.random.split(rng, 6)
    B, S, H, hd = 1, 128, 2, 32
    r = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, hd)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jnp.zeros((B, H, hd, hd))
    y_full, s_full = wkv6_ref(r, k, v, logw, u, s0)
    h = S // 2
    y1, s_mid = wkv6(r[:, :h], k[:, :h], v[:, :h], logw[:, :h], u, s0,
                     chunk=32, interpret=True)
    y2, s_end = wkv6(r[:, h:], k[:, h:], v[:, h:], logw[:, h:], u, s_mid,
                     chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_end), np.asarray(s_full),
                               atol=1e-4)
