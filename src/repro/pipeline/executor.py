"""Single-process pipelined-SL executors.

1. ``microbatch_grads`` — gradient accumulation over micro-batches via
   ``lax.scan``; *numerically equivalent* to the full-batch gradient (the
   paper's synchronous-SGD guarantee: pipelining changes latency, not the
   update — Fig. 4's "same converged accuracy").  Tests assert allclose.

2. ``SplitLearningExecutor`` — the paper's multi-hop SL semantics made
   runnable on one host: submodels (from a core.Plan) execute as separate
   stages with explicit activation/grad hand-offs, per-link compression
   hooks, and a latency ledger driven by the core latency model, so
   training curves can be plotted against *simulated wall-clock* (Fig. 4).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.obs.device import scope
from repro.core import Plan, breakdown
from repro.core.latency import SplitSolution
from repro.models import vgg as vgg_lib
from .stage import split_vgg_params, vgg_stages_from_cuts


def split_batch(batch, num_microbatches: int):
    """(B, ...) -> (Q, B/Q, ...), keeping the per-microbatch batch dim
    sharded over the data axes (the reshape otherwise loses the input's
    batch sharding and every activation replicates — measured +8 GiB/device
    on qwen3-0.6b train_4k; EXPERIMENTS.md §Perf iteration 0)."""
    from repro.models.common import maybe_constrain
    from jax.sharding import PartitionSpec as P

    def resh(x):
        B = x.shape[0]
        assert B % num_microbatches == 0, (B, num_microbatches)
        y = x.reshape((num_microbatches, B // num_microbatches)
                      + x.shape[1:])
        return maybe_constrain(
            y, P(None, ("pod", "data"), *([None] * (y.ndim - 2))))

    return jax.tree.map(resh, batch)


def microbatch_grads(loss_fn: Callable, params, batch, num_microbatches: int):
    """Mean loss + grads accumulated over micro-batches (== full batch)."""
    mb = split_batch(batch, num_microbatches)
    gfn = jax.value_and_grad(loss_fn)

    def step(acc, mbatch):
        loss_acc, grad_acc = acc
        loss, grads = gfn(params, mbatch)
        with scope("step.accumulate"):
            grad_acc = jax.tree.map(jnp.add, grad_acc, grads)
        return (loss_acc + loss, grad_acc), None

    with scope("step.accumulate"):
        zeros = jax.tree.map(jnp.zeros_like, params)
    (loss_sum, grad_sum), _ = jax.lax.scan(step, (0.0, zeros), mb)
    scale = 1.0 / num_microbatches
    with scope("step.accumulate"):
        grads = jax.tree.map(lambda g: g * scale, grad_sum)
    return loss_sum * scale, grads


# ---------------------------------------------------------------------------
# Split-learning executor (paper semantics, VGG workload)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LinkHooks:
    """Per-link transforms for activations / gradients (compression/...)."""
    fwd: Callable = lambda x: x
    bwd: Callable = lambda g: g


class SplitLearningExecutor:
    """Runs one training round of pipelined SL per the paper's Plan.

    The compute graph is *identical* to centralized training (stages chain
    to the full model; autodiff crosses the cut via VJPs — the
    activation-gradient hand-off of Eq. (9)), while the latency ledger
    accounts T_f + ceil((B-b)/b)*T_i per round from the analytical model.
    """

    def __init__(self, plan: Plan, profile, net, *, hooks: LinkHooks = None,
                 seed: int = 0):
        self.plan = plan
        self.profile = profile
        self.net = net
        self.hooks = hooks or LinkHooks()
        self.stages = vgg_stages_from_cuts(plan.solution.cuts)
        rng = jax.random.PRNGKey(seed)
        self.full_params = vgg_lib.init_params(rng)
        self.round_latency = plan.L_t
        self.simulated_time = 0.0
        self._jitted_grads = {}      # q -> compiled microbatch_grads

    def stage_params(self):
        return split_vgg_params(self.full_params, self.plan.solution.cuts)

    def _forward_chain(self, params_list, x):
        """Client -> servers with link hooks at every cut (Eqs. 5/6)."""
        acts = [x]
        for stage, sp in zip(self.stages, params_list):
            x = stage.forward(sp, x)
            x = self.hooks.fwd(x)
            acts.append(x)
        return x, acts

    def loss(self, params_list, batch):
        logits, _ = self._forward_chain(params_list, batch["images"])
        from repro.models.common import cross_entropy
        return cross_entropy(logits[:, None, :], batch["labels"][:, None])

    def train_round(self, batch, lr: float = 0.05, momentum: float = 0.0):
        """One mini-batch: micro-batched grads + SGD (optionally with heavy
        -ball ``momentum``); advances the simulated clock.  Momentum keeps
        the update rule client-computable (one extra buffer per stage) and
        tames plain SGD's oscillation on the norm-free VGG stack."""
        params_list = self.stage_params()
        q = self.plan.num_microbatches
        B = batch["images"].shape[0]
        q = max(1, min(q, B))
        while B % q:
            q -= 1
        # cache the compiled step per q: a fresh jit(lambda) every round
        # would recompile the whole fwd+bwd scan each call
        step = self._jitted_grads.get(q)
        if step is None:
            obs.inc("executor.jit_compile")
            step = jax.jit(lambda p, b: microbatch_grads(self.loss, p, b, q))
            self._jitted_grads[q] = step
        else:
            obs.inc("executor.jit_cache_hit")
        obs.inc("executor.train_rounds")
        loss, grads = step(params_list, batch)
        if momentum:
            vel = getattr(self, "_velocity", None)
            # a replan can change the cuts (different stage grouping/leaf
            # shapes) — a stale velocity tree would crash the tree.map, so
            # restart the buffer whenever the gradient tree changed shape
            if vel is None or (jax.tree.map(jnp.shape, vel)
                               != jax.tree.map(jnp.shape, grads)):
                vel = jax.tree.map(jnp.zeros_like, grads)
            vel = jax.tree.map(lambda v, g: momentum * v + g, vel, grads)
            self._velocity = vel
            grads = vel
        params_list = jax.tree.map(lambda p, g: p - lr * g, params_list,
                                   grads)
        # write back into the flat param list
        flat = [p for sp in params_list for p in sp]
        self.full_params = flat
        self.simulated_time += self.round_latency
        return float(loss)

    def evaluate(self, batch) -> float:
        logits = vgg_lib.forward(self.full_params, batch["images"])
        pred = jnp.argmax(logits, -1)
        return float((pred == batch["labels"]).mean())
