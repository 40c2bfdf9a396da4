"""Submodel (stage) construction from cut layers.

Two param layouts are supported:
  - *list-per-layer* (VGG and other heterogeneous nets): a stage is just
    ``forward(params, x, lo, hi)`` over the python list;
  - *stacked-scan* (all LM families): layer params are stacked on a leading
    axis, so a stage slices ``[lo:hi]`` and scans its own block — this is
    what the spmd pipeline shards across the "stage" mesh axis.
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

def stack_stage_params(layer_params, num_stages: int):
    """(L, ...) stacked layers -> (S, L/S, ...) per-stage stacking."""
    def resh(x):
        L = x.shape[0]
        assert L % num_stages == 0, (L, num_stages)
        return x.reshape((num_stages, L // num_stages) + x.shape[1:])
    return jax.tree.map(resh, layer_params)


def unstack_stage_params(stage_params):
    def resh(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
    return jax.tree.map(resh, stage_params)


def transformer_stage_fn(cfg: ArchConfig):
    """Returns f(stage_layer_params, x) scanning one stage's layer block."""
    def body(x, pl):
        positions = jnp.arange(x.shape[1])
        y, _ = tf_lib.block_fwd(pl, x, cfg, positions=positions, mode="train")
        return y

    body = remat_wrap(body, cfg.remat)

    def stage_fn(stage_layers, x):
        with scope("model.blocks"):
            x, _ = jax.lax.scan(lambda c, pl: (body(c, pl), None), x,
                                stage_layers)
        return x

    return stage_fn
