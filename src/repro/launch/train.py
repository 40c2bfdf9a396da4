"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
        --steps 50 --batch 32 --seq 128 --ckpt /tmp/ckpt

Runs the real loop: synthetic LM data -> micro-batched train_step (Q is
the ``microbatches`` argument / --microbatches; ``chip_smoke.py`` takes it
from ``core.planner.plan_stages``) -> optimizer -> periodic async
checkpoints -> restart-from-latest on relaunch.  The step is compiled
ahead of the loop (its time printed on its own line) and each step is
timed to ``block_until_ready``.  On CPU use --reduced; ``--full`` runs the
published widths (one v5e chip: see ``chip_smoke.py`` for a size that fits).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointStore
from repro.configs import get_config
from repro.data import token_lm_batches
from repro.launch.cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import get_model
from repro.optim import get_optimizer


def train(arch: str, *, reduced: bool = True, steps: int = 50,
          batch: int = 32, seq: int = 128, microbatches: int = 4,
          optimizer: str = "adamw", lr: float = 1e-3,
          ckpt_dir: str | None = None, ckpt_every: int = 20,
          log_every: int = 10, seed: int = 0) -> list:
    cfg = get_config(arch, reduced=reduced)
    api = get_model(cfg)
    opt = get_optimizer(optimizer, lr=lr)
    rng = jax.random.PRNGKey(seed)

    params = api.init(rng)
    opt_state = opt.init(params)
    step0 = 0
    store = CheckpointStore(ckpt_dir) if ckpt_dir else None
    if store is not None:
        restored, meta = store.restore_latest((params, opt_state))
        if restored is not None:
            params, opt_state = restored
            step0 = meta["step"] + 1
            print(f"restored checkpoint at step {meta['step']}")

    step_fn = jax.jit(make_train_step(cfg, opt, microbatches))
    data = token_lm_batches(batch=batch, seq_len=seq, vocab=cfg.vocab,
                            seed=seed)
    losses = []
    compiled = None
    for step in range(step0, steps):
        b = next(data)
        extra = {}
        if cfg.family == "vlm":
            extra["patch_embeds"] = np.zeros(
                (batch, cfg.patch_tokens, cfg.d_model), np.float32)
        if cfg.family == "audio":
            extra["frames"] = np.random.default_rng(step).normal(
                0, 1, (batch, cfg.encoder_frames, cfg.d_model)
            ).astype(np.float32)
        batch_dev = {k: jnp.asarray(v) for k, v in {**b, **extra}.items()}
        if compiled is None:
            t0 = time.perf_counter()
            compiled = step_fn.lower(params, opt_state, batch_dev).compile()
            print(f"compiled train step in {time.perf_counter() - t0:.2f}s",
                  flush=True)
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, batch_dev)
        jax.block_until_ready((params, opt_state, loss))
        seconds = time.perf_counter() - t0
        losses.append(float(loss))
        if step % log_every == 0:
            print(f"step {step:5d}  loss {float(loss):.4f}  "
                  f"{seconds * 1e3:.1f} ms", flush=True)
        if store is not None and step % ckpt_every == 0 and step > step0:
            store.save(step, (params, opt_state), blocking=False)
    if store is not None:
        store.save(steps - 1, (params, opt_state), blocking=True)
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    losses = train(args.arch, reduced=args.reduced, steps=args.steps,
                   batch=args.batch, seq=args.seq,
                   microbatches=args.microbatches, optimizer=args.optimizer,
                   lr=args.lr, ckpt_dir=args.ckpt)
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
