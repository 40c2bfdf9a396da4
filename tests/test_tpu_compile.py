"""Compiles for a described TPU v5e, at real widths, with no chip attached.

The TPU compiler is installed with jax; ``topologies.get_topology_desc``
describes a v5e:2x2 host so programs lower and compile for it from shapes
alone.  Nothing runs: these tests say that Mosaic and XLA:TPU accept the
main-path kernels and the pipelined step, not how fast they are.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.  All such compiles stay in this one file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache entirely
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_fwd_compiles_at_qwen3_width(one_chip):
    """qwen3-0.6b attention: 16 q heads, 8 kv heads, hd=128, S=4096."""
    from repro.kernels.flash import flash_attention
    q = _shape(one_chip, (1, 4096, 16, 128))
    kv = _shape(one_chip, (1, 4096, 8, 128))
    hlo = jax.jit(flash_attention).lower(q, kv, kv).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_flash_block_gradient_compiles_at_qwen3_width(one_chip, monkeypatch):
    """The gradient of one qwen3-0.6b block under its layer remat, at the
    train-s1k micro-batch (2 rows of S=1024, hd=128), with the backend
    taken as the TPU so that ``block_fwd`` dispatches to the flash kernels:
    the forward (run again by the remat) and both backward kernels are
    custom calls, under ``model.attention``, and no (2, 16, 1024, 1024)
    score buffer is left."""
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.models.common import remat_wrap
    from repro.obs.device import op_names, scopes_of

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("qwen3-0.6b")
    layer = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                         jax.eval_shape(lambda k: tf.init_layer_params(k, cfg),
                                        jax.random.key(0)))
    x = _shape(one_chip, (2, 1024, cfg.d_model))
    body = remat_wrap(lambda x, p: tf.block_fwd(
        p, x, cfg, positions=jnp.arange(1024))[0], cfg.remat)
    loss = lambda p, x: jnp.sum(body(x, p).astype(jnp.float32))
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile().as_text()
    names = op_names(hlo)
    kernels = {n: names[n] for n in names if n.startswith("flash_")}
    assert {n.split(".")[0] for n in kernels} == {
        "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"}
    assert all(scopes_of(op)[-2:] == ("model.attention", "kernels.flash")
               for op in kernels.values())
    assert "16,1024,1024" not in hlo
    # q, k, v, o and their gradients reach and leave the kernels as the
    # einsum projections lay them out: no standalone relayout of them
    heads = (r"(2,1024,16,128|2,16,1024,128|2,1024,8,128|2,8,1024,128"
             r"|2,1024,2048)")
    relayout = rf"= \w+\[{heads}\]\S* (copy|transpose|reshape)\("
    assert not re.findall(relayout, "\n".join(_unfused(hlo)))


def _unfused(hlo: str) -> list:
    """The instruction lines of a compiled module outside its fusions."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    out, comp = [], None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*{$", line)
        if m:
            comp = m.group(1)
        elif comp not in fused:
            out.append(line)
    return out


def test_wkv6_compiles_at_rwkv6_width(one_chip):
    """rwkv6-1.6b time mix: d=2048 as 32 heads of hd=64, S=4096."""
    from repro.kernels.rwkv6 import wkv6
    B, S, H, hd = 1, 4096, 32, 64
    act = _shape(one_chip, (B, S, H, hd))
    f32 = lambda *s: _shape(one_chip, s, jnp.float32)
    hlo = jax.jit(wkv6).lower(act, act, act, f32(B, S, H, hd), f32(H, hd),
                              f32(B, H, hd, hd)).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_pipelined_loss_compiles_on_2x2(topo):
    """The 4-stage pipelined loss and its gradient, qwen3-0.6b widths at 4
    layers (one per stage), on a (data=1, stage=4, model=1) mesh."""
    import dataclasses
    from repro.configs import get_config, param_specs
    from repro.launch.mesh import make_pipeline_mesh
    from repro.pipeline import (PipelineConfig, make_pipelined_loss,
                                stage_shardings)

    cfg = dataclasses.replace(get_config("qwen3-0.6b"), num_layers=4)
    mesh = make_pipeline_mesh(devices=topo.devices, num_stages=4)
    assert dict(mesh.shape) == {"data": 1, "stage": 4, "model": 1}
    pspecs = param_specs(cfg)
    pshapes = jax.tree.map(lambda s, sh: _shape(sh, s.shape, s.dtype),
                           pspecs, stage_shardings(mesh, pspecs))
    tok = _shape(NamedSharding(mesh, P()), (8, 1024), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    pcfg = PipelineConfig(num_stages=4, num_microbatches=4)
    with jax.set_mesh(mesh):
        loss = make_pipelined_loss(cfg, mesh, pcfg)
        hlo = jax.jit(jax.value_and_grad(loss)).lower(
            pshapes, batch).compile().as_text()
    assert "collective-permute" in hlo        # stage -> stage hand-offs
    assert "all-reduce" in hlo                # last-stage combine (psum)
    # one layer a stage runs GPipe: Q + S - 1 = 7 ticks, no ring wrap
    assert "[7,2,1024,1024]" in hlo
    pairs = {p for _, _, _, ps in _collectives(hlo) for p in ps}
    assert pairs and not pairs & {(3, 0), (0, 3)}, pairs


def _collectives(hlo: str) -> list:
    """(instruction, result shape, kind, source-target pairs) of every
    collective in a compiled module's text."""
    out = []
    for m in re.finditer(
            r"^\s+(?:ROOT )?%([\w.\-]+) = (\([^=]*?\)|\S+) (all-reduce|"
            r"all-gather|all-to-all|reduce-scatter|collective-permute)"
            r"(?:-start)?\((.*)$", hlo, re.M):
        pairs = re.search(r"source_target_pairs=\{(.*?)\}\}", m.group(4))
        out.append((m.group(1), m.group(2), m.group(3), {
            tuple(map(int, p.split(","))) for p in
            re.findall(r"\{(\d+,\d+)", pairs.group(1) + "}")}
            if pairs else set()))
    return out


def test_pipelined_train_step_carries_scopes_on_2x2(topo):
    """The 4-stage pipelined train step (loss, gradient and AdamW update)
    compiled for the chip: the TPU compiler's fusions keep the program's
    device scopes in their metadata, as a trace reader needs."""
    import dataclasses
    from repro.configs import get_config, param_specs
    from repro.launch.mesh import make_pipeline_mesh
    from repro.optim import get_optimizer
    from repro.pipeline import (PipelineConfig, make_pipelined_train_step,
                                stage_shardings)
    from test_device_scopes import scope_census

    cfg = dataclasses.replace(get_config("qwen3-0.6b"), num_layers=4)
    mesh = make_pipeline_mesh(devices=topo.devices, num_stages=4)
    opt = get_optimizer("adamw", lr=1e-3)
    pspecs = param_specs(cfg)
    sspecs = jax.eval_shape(opt.init, pspecs)
    placed = lambda specs: jax.tree.map(
        lambda s, sh: _shape(sh, s.shape, s.dtype), specs,
        stage_shardings(mesh, specs))
    tok = _shape(NamedSharding(mesh, P()), (8, 1024), jnp.int32)
    with jax.set_mesh(mesh):
        step = make_pipelined_train_step(
            cfg, mesh, PipelineConfig(num_stages=4, num_microbatches=4), opt)
        hlo = jax.jit(step).lower(placed(pspecs), placed(sspecs),
                                  {"tokens": tok, "labels": tok}
                                  ).compile().as_text()
    share, seen = scope_census(hlo)
    assert share >= 0.9, share
    assert {"model.attention", "model.head_loss", "step.optimizer",
            "pipe.ticks", "pipe.combine"} <= seen


def test_pipelined_train_step_with_flash_compiles_on_2x2(topo, monkeypatch):
    """The 4-stage pipelined train step at qwen1.5-4b's attention width (20
    heads of 128, S=1024), one layer per stage, with the backend taken as
    the TPU: inside the pipeline's manual "stage" region every block takes
    the flash kernels, which the size-1 auto axes around it do not refuse,
    and no (2, 20, 1024, 1024) score buffer is left.  No all-reduce
    carries ``model.attention``: each stage's attention gradients stay its
    own (``test_spmd.py`` checks their values on the CPU)."""
    import dataclasses
    from repro.configs import get_config, param_specs
    from repro.launch.mesh import make_pipeline_mesh
    from repro.obs.device import op_names, scopes_of
    from repro.optim import get_optimizer
    from repro.pipeline import (PipelineConfig, make_pipelined_train_step,
                                stage_shardings)
    from test_device_scopes import scope_census

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("qwen1.5-4b"), num_layers=4,
                              vocab=4096)
    mesh = make_pipeline_mesh(devices=topo.devices, num_stages=4)
    opt = get_optimizer("adamw", lr=1e-3)
    pspecs = param_specs(cfg)
    sspecs = jax.eval_shape(opt.init, pspecs)
    placed = lambda specs: jax.tree.map(
        lambda s, sh: _shape(sh, s.shape, s.dtype), specs,
        stage_shardings(mesh, specs))
    tok = _shape(NamedSharding(mesh, P()), (8, 1024), jnp.int32)
    with jax.set_mesh(mesh):
        step = make_pipelined_train_step(
            cfg, mesh, PipelineConfig(num_stages=4, num_microbatches=4), opt)
        hlo = jax.jit(step).lower(placed(pspecs), placed(sspecs),
                                  {"tokens": tok, "labels": tok}
                                  ).compile().as_text()
    share, seen = scope_census(hlo)
    assert share >= 0.9, share
    assert {"model.attention", "kernels.flash", "pipe.ticks"} <= seen
    assert "20,1024,1024" not in hlo
    names = op_names(hlo)
    reduces = re.findall(r"%([\w.\-]+) = .* all-reduce(?:-start)?\(", hlo)
    assert reduces
    assert not [r for r in reduces
                if "model.attention" in scopes_of(names.get(r, ""))]


def test_interleaved_pipeline_compiles_on_2x2(topo):
    """The 4-stage pipelined train step at qwen1.5-4b widths with 8 layers
    (2 a stage) and Q=4 runs the interleaved schedule, v = 2: Q v + S - 1 =
    11 one-layer ticks.  The layers reach the stages that run them by
    collective-permutes (or all-to-alls) under ``pipe.relayout``; no
    all-gather result holds more than a stage's share of a layer weight;
    and the ticks' micro-batch hand-off includes the ring's wrap, 3 -> 0."""
    import dataclasses
    from repro.configs import get_config, param_specs
    from repro.launch.mesh import make_pipeline_mesh
    from repro.obs.device import op_names, scopes_of
    from repro.optim import get_optimizer
    from repro.pipeline import (PipelineConfig, make_pipelined_train_step,
                                stage_shardings)

    cfg = dataclasses.replace(get_config("qwen1.5-4b"), num_layers=8,
                              vocab=4096)
    d, ff = cfg.d_model, cfg.d_ff
    mesh = make_pipeline_mesh(devices=topo.devices, num_stages=4)
    opt = get_optimizer("adamw", lr=1e-3)
    pspecs = param_specs(cfg)
    sspecs = jax.eval_shape(opt.init, pspecs)
    placed = lambda specs: jax.tree.map(
        lambda s, sh: _shape(sh, s.shape, s.dtype), specs,
        stage_shardings(mesh, specs))
    tok = _shape(NamedSharding(mesh, P()), (8, 1024), jnp.int32)
    with jax.set_mesh(mesh):
        step = make_pipelined_train_step(
            cfg, mesh, PipelineConfig(num_stages=4, num_microbatches=4), opt)
        hlo = jax.jit(step).lower(placed(pspecs), placed(sspecs),
                                  {"tokens": tok, "labels": tok}
                                  ).compile().as_text()
    assert f"[11,2,1024,{d}]" in hlo
    names = op_names(hlo)
    found = _collectives(hlo)
    relayout = {kind for name, _, kind, _ in found
                if "pipe.relayout" in scopes_of(names.get(name, ""))}
    assert relayout and relayout <= {"collective-permute", "all-to-all"}
    for _, shape, kind, _ in found:
        if kind != "all-gather":
            continue
        for dims in re.findall(r"\[([\d,]+)\]", shape):
            dims = [int(x) for x in dims.split(",")]
            if dims[-2:] in ([d, ff], [ff, d], [d, d]):
                assert math.prod(dims[:-2]) <= 2, shape
    handoffs = [pairs for name, shape, kind, pairs in found
                if kind == "collective-permute" and f"[2,1024,{d}]" in shape
                and "pipe.ticks" in scopes_of(names.get(name, ""))]
    assert any((3, 0) in pairs for pairs in handoffs), handoffs
