"""Submodel (stage) construction from cut layers.

Two param layouts are supported:
  - *list-per-layer* (VGG and other heterogeneous nets): a stage is just
    ``forward(params, x, lo, hi)`` over the python list;
  - *stacked-scan* (all LM families): layer params are stacked on a leading
    axis, which the spmd pipeline shards across the "stage" mesh axis; a
    stage scans one block of its share at a time.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.models import vgg as vgg_lib
from repro.obs.device import scope
from repro.models.common import ArchConfig, remat_wrap
from repro.models import transformer as tf_lib


# ---------------------------------------------------------------------------
# VGG (list-per-layer) stages — the paper's edge-SL submodels
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class VGGStage:
    lo: int
    hi: int

    def init(self, rng):
        return [p for i, p in enumerate(vgg_lib.init_params(rng))
                if self.lo <= i < self.hi]

    def forward(self, stage_params, x):
        for off, i in enumerate(range(self.lo, self.hi)):
            x = vgg_lib.layer_fwd(i, stage_params[off], x)
        return x


def vgg_stages_from_cuts(cuts: Sequence[int]) -> list:
    """cuts: 1-based last layer per submodel (SplitSolution.cuts)."""
    stages, lo = [], 0
    for hi in cuts:
        if hi > lo:
            stages.append(VGGStage(lo, hi))
            lo = hi
    return stages


def split_vgg_params(params: list, cuts: Sequence[int]) -> list:
    out, lo = [], 0
    for hi in cuts:
        if hi > lo:
            out.append(params[lo:hi])
            lo = hi
    return out


# ---------------------------------------------------------------------------
# Stacked-scan transformer stages
# ---------------------------------------------------------------------------

def transformer_stage_fn(cfg: ArchConfig):
    """Returns f(stack, block, x): ``x`` through block ``block`` of
    ``stack``, a (blocks, layers, ...) stack of layer params, one layer
    after another.

    Each layer's params are read from the stack inside its remat region, so
    what the layer keeps for its backward is the whole (loop-invariant)
    stack and two indices, not a slice of it: a slice taken outside would be
    saved once per pipeline tick."""
    def body(x, stack, block, i):
        pl = jax.tree.map(lambda p: p[block, i], stack)
        positions = jnp.arange(x.shape[1])
        y, _ = tf_lib.block_fwd(pl, x, cfg, positions=positions, mode="train")
        return y

    body = remat_wrap(body, cfg.remat)

    def stage_fn(stack, block, x):
        depth = jax.tree.leaves(stack)[0].shape[1]
        with scope("model.blocks"):
            x, _ = jax.lax.scan(lambda c, i: (body(c, stack, block, i), None),
                                x, jnp.arange(depth))
        return x

    return stage_fn
