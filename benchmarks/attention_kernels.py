"""Attention kernels on one TPU chip: forward-plus-backward time of the
causal core at the benchmark cells' shapes.

Compares, per shape (micro-batch rows, S, q heads, kv heads, head_dim):

- ``xla``: ``models.common.full_attention``, the path a block takes off
  the TPU (dense S x S scores, GQA by repeating kv heads);
- ``flash``: ``kernels/flash`` at each candidate (block_q, block_k), and
  at what ``block_sizes`` picks (``chosen``);
- ``splash``: ``jax.experimental.pallas.ops.tpu.splash_attention`` with a
  causal mask (blocks of 512 and 1024, kv compute blocks of up to 512), on
  the head-major layout it takes, with and without the (B, S, H, hd) ->
  (B, H, S, hd) transposes (which the flash timings include).

Each timing is one jitted ``vjp`` (forward with residuals, then backward)
per call, ``--iters`` calls back to back, then ``block_until_ready``; the
median of ``--repeats`` such loops, per call.  ``tflops`` counts the causal
core's needed work as ``bench/flops`` does: three times the forward's two
matmuls over the lower triangle.  ``err`` is the largest |flash - xla| of
the output and the three gradients over the largest |xla| value, on the
chip.  Needs a TPU: exits non-zero without one.

    python benchmarks/attention_kernels.py [--iters 20] [--repeats 5]

Writes one JSON line per timing to stdout and appends it to
``results/bench/attention_kernels.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.kernels.flash import flash_attention  # noqa: E402
from repro.kernels.flash.kernel import block_sizes  # noqa: E402
from repro.models.common import _repeat_kv, full_attention  # noqa: E402

#: (name, micro-batch rows, S, q heads, kv heads, head_dim)
SHAPES = [
    ("qwen3-0.6b.train-s1k", 2, 1024, 16, 8, 128),
    ("qwen1.5-4b.pipe4-s1k", 2, 1024, 20, 20, 128),
    ("qwen3-0.6b.s2k", 1, 2048, 16, 8, 128),
]
#: (block_q, block_k) candidates
BLOCKS = [(256, 256), (512, 512), (1024, 1024)]


def _inputs(B, S, H, KV, hd, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, S, H, hd), jnp.bfloat16)
    return q, k, v, do


def _vjp(attn):
    def run(q, k, v, do):
        o, back = jax.vjp(attn, q, k, v)
        return (o,) + back(do)
    return jax.jit(run)


def _time(fn, args, iters, repeats) -> float:
    jax.block_until_ready(fn(*args))
    per_call = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        per_call.append((time.perf_counter() - t0) / iters)
    return statistics.median(per_call) * 1e3


def _xla(q, k, v):
    g = q.shape[2] // k.shape[2]
    return full_attention(q, _repeat_kv(k, g), _repeat_kv(v, g), causal=True)


def _splash(H, KV, S, blk, transpose: bool):
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    mask = sa.MultiHeadMask([sa.CausalMask((S, S))] * H)
    compute = min(blk, 512)
    sizes = sa.BlockSizes(block_q=blk, block_kv=blk, block_kv_compute=compute,
                          block_q_dkv=blk, block_kv_dkv=blk,
                          block_kv_dkv_compute=compute, block_q_dq=blk,
                          block_kv_dq=blk)
    kern = jax.vmap(sa.make_splash_mha_single_device(mask, block_sizes=sizes))

    def attn(q, k, v):                    # head-major (B, H, S, hd)
        scale = q.shape[-1] ** -0.5
        return kern(q * jnp.asarray(scale, q.dtype), k, v)

    if not transpose:
        return attn
    t = lambda x: x.transpose(0, 2, 1, 3)
    return lambda q, k, v: t(attn(t(q), t(k), t(v)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 1
    out_dir = os.path.join(ROOT, "results", "bench")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "attention_kernels.jsonl"), "a") as out:
        def emit(**row):
            row["device"] = dev.device_kind
            line = json.dumps(row)
            print(line, flush=True)
            out.write(line + "\n")

        for shape in SHAPES:
            _measure(shape, args, emit)
    return 0


def _measure(shape, args, emit):
    name, B, S, H, KV, hd = shape
    q, k, v, do = _inputs(B, S, H, KV, hd)
    work = 3 * 4 * B * H * hd * S * S / 2
    ref = _vjp(_xla)
    ref_out = ref(q, k, v, do)
    scale = [float(jnp.max(jnp.abs(r.astype(jnp.float32))))
             for r in ref_out]

    def report(path, fn, **extra):
        ms = _time(fn, (q, k, v, do), args.iters, args.repeats)
        emit(shape=name, path=path, ms=ms,
             tflops=work / ms / 1e9, **extra)

    report("xla", ref)
    chosen = block_sizes(S, hd)
    for bq, bk in dict.fromkeys(BLOCKS + [chosen]):
        if bq > S or bk > S:
            continue
        fn = _vjp(lambda q, k, v, bq=bq, bk=bk: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk))
        got = fn(q, k, v, do)
        err = max(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))) / s
            for a, b, s in zip(got, ref_out, scale))
        report("flash", fn, block_q=bq, block_k=bk, err=err,
               chosen=(bq, bk) == chosen)
    for blk in (512, 1024):
        if blk > S:
            continue
        report("splash", _vjp(_splash(H, KV, S, blk, True)),
               block=blk, layout="model (transposes included)")
        t = lambda x: x.transpose(0, 2, 1, 3)
        fn = _vjp(_splash(H, KV, S, blk, False))
        ms = _time(fn, (t(q), t(k), t(v), t(do)), args.iters,
                   args.repeats)
        emit(shape=name, path="splash", ms=ms,
             tflops=work / ms / 1e9, block=blk, layout="head-major")


if __name__ == "__main__":
    sys.exit(main())
