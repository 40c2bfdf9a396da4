"""The system under test: the program's step, built for one cell.

A workload file names the entry the window drives:

- ``"train_step"``: ``jax.jit(launch.steps.make_train_step(cfg, adamw, Q))``
  on one device, micro-batched gradient accumulation;
- ``"pipelined_train_step"``: ``jax.jit(pipeline.spmd.make_pipelined_train_step(
  cfg, mesh, PipelineConfig(stages, Q), adamw))`` on
  ``launch.mesh.make_pipeline_mesh(num_stages=stages)``, one stage per chip.

``microbatches`` is a number or ``"planner"``: then Q comes from
``core.planner.plan_stages`` on a profile of the cell's own sequence length.
The program's ``ArchConfig`` and the planner's profile come from the
family's ``bench/arch/<family>.py`` (``cell.arch``), which maps every size
from the configuration file, so the program runs as the configuration
states. What holds for every family is here: the entries, Q, and that the
program computes in the configuration's ``torch_dtype``.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def arch_config(cell):
    """The program's ``ArchConfig`` for the cell's published config."""
    cfg = cell.arch.arch_config(cell.config)
    dtype = cell.config["torch_dtype"]
    if jnp.dtype(cfg.compute_dtype) != jnp.dtype(dtype):
        raise ValueError(f"program computes in {cfg.compute_dtype}, the "
                         f"configuration states {dtype}")
    return cfg


def stages(workload: dict) -> int:
    return int(workload.get("stages", 1))


def microbatches(cell) -> int:
    """Q: pinned by the workload file, or the planner's at the cell's S."""
    q = cell.workload["microbatches"]
    if q != "planner":
        return int(q)
    from repro.core import plan_stages
    from repro.pipeline import plan_to_pipeline_config

    n = stages(cell.workload)
    sp = plan_stages(cell.arch.planner_profile(cell.config, cell.seq),
                     total_chips=cell.chips, stage_candidates=(n,),
                     global_batch=cell.batch)
    return plan_to_pipeline_config(sp, cell.batch).num_microbatches


def optimizer(workload: dict):
    from repro.optim import get_optimizer

    hp = dict(workload["optimizer"])
    return get_optimizer(hp.pop("name"), **hp)


@dataclasses.dataclass
class Program:
    step: Callable                 # jitted (params, opt_state, batch) -> ...
    init_state: Callable           # jitted params -> opt_state
    put: Callable                  # host batch -> device batch
    param_sharding: Any            # pytree of shardings, or one sharding
    mesh_context: Callable         # () -> context manager for every call
    devices: list

    def loss(self, out) -> jax.Array:
        """The loss of one step's output."""
        metrics = out[2]
        return metrics["loss"] if isinstance(metrics, dict) else metrics


def build(cell, param_shapes: dict, devices: list) -> Program:
    """The cell's entry on ``devices``; ``param_shapes`` is the param pytree
    of ``jax.ShapeDtypeStruct`` (for shardings)."""
    cfg = arch_config(cell)
    opt = optimizer(cell.workload)
    q = microbatches(cell)
    entry = cell.workload["entry"]
    if entry == "train_step":
        from repro.launch.steps import make_train_step

        dev = devices[0]
        one = jax.sharding.SingleDeviceSharding(dev)
        return Program(
            step=jax.jit(make_train_step(cfg, opt, q)),
            init_state=jax.jit(opt.init),
            put=lambda b: jax.device_put(b, one),
            param_sharding=one, mesh_context=contextlib.nullcontext,
            devices=[dev])
    if entry == "pipelined_train_step":
        from repro.launch.mesh import make_pipeline_mesh
        from repro.pipeline import (PipelineConfig, make_pipelined_train_step,
                                    stage_shardings)

        n = stages(cell.workload)
        mesh = make_pipeline_mesh(num_devices=n, num_stages=n,
                                  devices=devices[:n])
        shardings = stage_shardings(mesh, param_shapes)
        state_shapes = jax.eval_shape(opt.init, param_shapes)
        step = make_pipelined_train_step(cfg, mesh, PipelineConfig(n, q), opt)
        replicated = NamedSharding(mesh, P())
        return Program(
            step=jax.jit(step),
            init_state=jax.jit(opt.init, out_shardings=stage_shardings(
                mesh, state_shapes)),
            put=lambda b: jax.device_put(b, replicated),
            param_sharding=shardings,
            mesh_context=lambda: jax.set_mesh(mesh),
            devices=list(mesh.devices.flat))
    raise ValueError(f"unknown entry {entry!r}")
