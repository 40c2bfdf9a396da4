"""Batched serving driver: continuous-batching decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --requests 8 --prompt-len 16 --gen 24

Implements a small production-shaped server core: a request queue, batched
prefill (padded to the batch), then a decode loop that retires finished
sequences and admits new ones into freed KV-cache slots (continuous
batching).  Greedy sampling; the decode-shape dry-run cells lower exactly
this decode_step at 32k/500k cache lengths.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import get_model


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (P,) int32
    max_new: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


class BatchedServer:
    def __init__(self, arch: str, *, reduced: bool = True, batch: int = 4,
                 cache_len: int = 128, seed: int = 0):
        self.cfg = get_config(arch, reduced=reduced)
        self.api = get_model(self.cfg)
        self.batch = batch
        self.cache_len = cache_len
        rng = jax.random.PRNGKey(seed)
        self.params = self.api.init(rng)
        self.decode = jax.jit(self.api.decode)
        self.queue: list = []
        self.slots: list = [None] * batch

    def submit(self, req: Request):
        self.queue.append(req)

    def _prefill_one(self, req: Request):
        """Prefill a single request into a fresh single-row cache."""
        batch = {"tokens": jnp.asarray(req.prompt[None, :])}
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (1, self.cfg.patch_tokens, self.cfg.d_model),
                self.cfg.compute_dtype)
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (1, self.cfg.encoder_frames, self.cfg.d_model),
                self.cfg.compute_dtype)
        logits, cache = self.api.prefill(self.params, batch, self.cache_len)
        tok = int(jnp.argmax(logits[0, -1]))
        return tok, cache, len(req.prompt)

    def run(self, *, max_ticks: int = 1000) -> dict:
        """Continuous batching: admit from queue, decode, retire."""
        stats = {"ticks": 0, "completed": [], "tokens": 0}
        t0 = time.time()
        for _ in range(max_ticks):
            # admit
            for i in range(self.batch):
                if self.slots[i] is None and self.queue:
                    req = self.queue.pop(0)
                    tok, cache, pos = self._prefill_one(req)
                    req.generated.append(tok)
                    self.slots[i] = {"req": req, "cache": cache, "pos": pos,
                                     "last": tok}
            live = [s for s in self.slots if s is not None]
            if not live:
                break
            # decode each live slot (row-batched per slot: caches are per
            # slot so heterogeneous positions are exact)
            for s in live:
                logits, s["cache"] = self.decode(
                    self.params, s["cache"],
                    jnp.asarray([[s["last"]]], jnp.int32),
                    jnp.int32(s["pos"]))
                s["last"] = int(jnp.argmax(logits[0, -1]))
                s["pos"] += 1
                s["req"].generated.append(s["last"])
                stats["tokens"] += 1
            # retire
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                req = s["req"]
                if (len(req.generated) >= req.max_new
                        or s["pos"] >= self.cache_len - 1):
                    req.done = True
                    stats["completed"].append(req)
                    self.slots[i] = None
            stats["ticks"] += 1
        stats["seconds"] = time.time() - t0
        stats["tok_per_s"] = stats["tokens"] / max(stats["seconds"], 1e-9)
        return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--cache-len", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()
    srv = BatchedServer(args.arch, reduced=args.reduced, batch=args.batch,
                        cache_len=args.cache_len)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        srv.submit(Request(rid, rng.integers(
            0, srv.cfg.vocab, size=args.prompt_len).astype(np.int32),
            max_new=args.gen))
    stats = srv.run()
    print(f"served {len(stats['completed'])} requests, "
          f"{stats['tokens']} tokens in {stats['seconds']:.1f}s "
          f"({stats['tok_per_s']:.1f} tok/s)")


if __name__ == "__main__":
    main()
