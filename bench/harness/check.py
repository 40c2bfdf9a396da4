"""The comparison that decides ``correct``.

Set-up drives the compiled step, with its state, through the first
``CHECK_STEPS`` batches by the window's own call and feed. From that run it
reads each step's loss, the first gradient as the optimizer holds it (AdamW's
first moment after one step over ``1 - b1``) and, after the last check step,
the change of the parameters from their seeded start. Once the window has
closed and the program's state is freed, the plain float32 reference
(``bench/reference/<family>.py``) follows the same steps from the same seeded
weights with a plain AdamW, and three numbers are compared:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: over the parameter leaves, the largest gap between the
  program's and the reference's first-gradient norm, over the larger of the
  reference's norm of that leaf and the median leaf's;
- ``change_gap``: the same for the parameters' change after the check steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (they move under Adam by round-off alone).

Each number has its own limit in the workload file; the readings each was
set from are in ``PERF.md``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's is left out of ``change_gap``
STILL_LEAF = 1e-3
NAMES = ("loss_gap", "grad_gap", "change_gap")


def leaf_norms(tree) -> jax.Array:
    """Float32 L2 norm of every leaf, in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def reference_sharding(devices: list, shapes):
    """How the reference lays out its float32 state: whole on one device;
    over several, each leaf split along its last axis where that divides."""
    if len(devices) == 1:
        return jax.tree.map(
            lambda _: jax.sharding.SingleDeviceSharding(devices[0]), shapes)
    mesh = Mesh(np.array(devices), ("x",))
    n = len(devices)

    def spec(s):
        if len(s.shape) >= 2 and s.shape[-1] % n == 0:
            return NamedSharding(mesh, P(*([None] * (len(s.shape) - 1)), "x"))
        return NamedSharding(mesh, P())
    return jax.tree.map(spec, shapes)


def reference_programs(ref, c: dict, hp: dict, devices, *,
                       quant=None) -> tuple:
    """(init, zeros, step, change): the reference's jitted programs. ``step``
    is one float32 AdamW step: (p, m, v, t, tokens, labels) -> (loss,
    gradient leaf norms, p, m, v), donating p, m and v."""
    shapes = jax.eval_shape(lambda k: ref.init_params(k, c),
                            jax.random.key(0))
    shard = reference_sharding(devices, shapes)
    init = jax.jit(lambda k: ref.init_params(k, c), out_shardings=shard)
    zeros = jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes), out_shardings=shard)
    b1, b2 = float(hp["b1"]), float(hp["b2"])
    lr, eps, wd = float(hp["lr"]), float(hp["eps"]), float(hp["weight_decay"])

    def step(p, m, v, t, tokens, labels):
        loss, g = ref.loss_and_grads(p, tokens, labels, c, quant=quant)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                      + wd * p), p, m, v)
        return loss, leaf_norms(g), p, m, v

    step = jax.jit(step, donate_argnums=(0, 1, 2),
                   out_shardings=(None, None, shard, shard, shard))
    change = jax.jit(lambda p, k: leaf_norms(
        jax.tree.map(jnp.subtract, p, ref.init_params(k, c))))
    return init, zeros, step, change


def reference_readings(ref, c: dict, hp: dict, key, batches: list, devices,
                       *, quant=None, rows: int | None = None) -> dict:
    """The reference's losses, first-gradient norms and change norms over
    ``batches``. ``quant`` computes it in lower precision (the control);
    ``rows`` keeps only the first ``rows`` rows of each batch (a fault)."""
    init, zeros, step, change = reference_programs(ref, c, hp, devices,
                                                   quant=quant)
    p, m, v = init(key), zeros(), zeros()
    losses, grads = [], None
    for t, b in enumerate(batches, start=1):
        tok = np.asarray(b["tokens"])[:rows]
        lab = np.asarray(b["labels"])[:rows]
        loss, g, p, m, v = step(p, m, v, jnp.float32(t), tok, lab)
        losses.append(float(loss))
        if grads is None:
            grads = np.asarray(g, np.float64)
    del m, v
    moved = np.asarray(change(p, key), np.float64)
    return {"losses": losses, "grads": grads, "change": moved}


def _leaf_gap(prog, ref, keep) -> float:
    prog, ref = np.asarray(prog)[keep], np.asarray(ref)[keep]
    floor = np.maximum(ref, np.median(ref))
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(prog - ref) / floor
    gap = np.where(np.isfinite(gap), gap, np.inf)
    return float(np.max(gap))


def gaps(prog: dict, ref: dict) -> dict:
    """The three compared numbers (``NAMES``) of ``prog`` against ``ref``."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    with np.errstate(invalid="ignore", divide="ignore"):
        loss = np.abs(lp - lr) / np.abs(lr)
    loss = np.where(np.isfinite(loss), loss, np.inf)
    g = np.asarray(ref["grads"])
    all_leaves = np.ones(g.shape, bool)
    moving = g >= STILL_LEAF * np.median(g)
    return {"loss_gap": float(np.max(loss)),
            "grad_gap": _leaf_gap(prog["grads"], g, all_leaves),
            "change_gap": _leaf_gap(prog["change"], ref["change"], moving)}


def judge(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number within its limit;
    a number that is not finite fails."""
    out = {n: {"value": values[n], "limit": float(limits[n])} for n in NAMES}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values())
    return ok, out
