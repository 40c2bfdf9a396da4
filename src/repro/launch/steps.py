"""Step-function factories shared by the trainer, server, and dry-run.

``train_step`` does micro-batched gradient accumulation (lax.scan) — the
single-mesh counterpart of the paper's micro-batching (Theorem 1 picks Q)
— followed by the optimizer update.  ``prefill_step``/``decode_step`` are
the serving entries the decode-shape cells lower.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import get_model
from repro.models.common import ArchConfig
from repro.obs.device import scope
from repro.optim import Optimizer, get_optimizer
from repro.pipeline.executor import microbatch_grads


# Optimizer policy: AdamW by default; factored second moments once fp32
# moments stop fitting (>= ~100B params on a 256-chip pod) — DESIGN.md §2.
BIG_MODEL_OPTIMIZER_THRESHOLD = 100e9


def default_optimizer_name(cfg: ArchConfig) -> str:
    from repro.configs.base import count_params
    return ("adafactor" if count_params(cfg) >= BIG_MODEL_OPTIMIZER_THRESHOLD
            else "adamw")


def default_microbatches(cfg: ArchConfig, global_batch: int) -> int:
    """Gradient-accumulation depth Q for the train shape.  The planner
    (Theorem 1) refines this; the default keeps per-microbatch activations
    bounded for the largest configs.  Configs can pin Q (§Perf winners)."""
    q = cfg.train_microbatches
    if q <= 0:
        q = 8
        if cfg.d_model >= 8192 or cfg.num_layers >= 64:
            q = 16
    while global_batch % q:
        q //= 2
    return max(q, 1)


def make_train_step(cfg: ArchConfig, optimizer: Optimizer,
                    num_microbatches: int) -> Callable:
    api = get_model(cfg)

    def train_step(params, opt_state, batch):
        loss, grads = microbatch_grads(api.loss, params, batch,
                                       num_microbatches)
        with scope("step.optimizer"):
            params, opt_state = optimizer.update(params, grads, opt_state)
        return params, opt_state, loss

    return train_step


def make_prefill_step(cfg: ArchConfig, cache_len: int) -> Callable:
    api = get_model(cfg)

    def prefill_step(params, batch):
        return api.prefill(params, batch, cache_len)

    return prefill_step


def make_decode_step(cfg: ArchConfig) -> Callable:
    api = get_model(cfg)

    def decode_step(params, cache, token, pos):
        return api.decode(params, cache, token, pos)

    return decode_step
