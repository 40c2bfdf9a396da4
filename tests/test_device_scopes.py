"""The training step's device scopes (``repro.obs.device``).

The program names each part of the step with a ``jax.named_scope`` from one
vocabulary; a reader of a device trace maps an operation back to those
names through the ``op_name`` metadata of the compiled program.  Checked
here: the vocabulary refuses other names, the path parser, the map from a
compiled module's text, and that the compiled steps (one device, and the
stage pipeline on four virtual CPU devices) put their work under scopes.
"""

import glob
import os
import re
import subprocess
import sys
import textwrap

import pytest

from repro import obs
from repro.obs import device


def test_scope_refuses_an_unknown_name():
    with pytest.raises(ValueError, match="model.mlp"):
        device.scope("model.mlp")
    assert len(set(device.SCOPES)) == len(device.SCOPES)
    for name in device.SCOPES:
        with device.scope(name):
            pass


@pytest.mark.parametrize("op_name, scopes", [
    ("jit(train_step)/jvp(model.blocks)/while/body/closed_call/"
     "model.attention/dot_general", ("model.blocks", "model.attention")),
    ("jit(train_step)/transpose(jvp(pipe.ticks))/while/body/closed_call/"
     "transpose(jvp(model.blocks))/while", ("pipe.ticks", "model.blocks")),
    ("jit(train_step)/step.optimizer/mul", ("step.optimizer",)),
    ("jit(train_step)/while/body/closed_call/cos", ()),
    ("jit(f)/jvp(model.mlp)/dot_general", ()),
    ("", ()),
])
def test_scopes_of_reads_names_inside_transformations(op_name, scopes):
    assert device.scopes_of(op_name) == scopes


HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %sine.1 = f32[4]{0} sine(%param_0), metadata={op_name="jit(step)/jvp(model.blocks)/while/body/sin"}
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%p), index=0
  %gte.1 = f32[4]{0} get-tuple-element(%p), index=1
  %fusion.1 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(model.blocks)/while/body/sin"}
  %add.2 = s32[] add(%gte.0, %gte.0)
  ROOT %tuple.3 = (s32[], f32[4]{0}) tuple(%add.2, %fusion.1)
}

%cond (c: (s32[], f32[4])) -> pred[] {
  %c = (s32[], f32[4]{0}) parameter(0)
  %gte.4 = s32[] get-tuple-element(%c), index=0
  ROOT %compare.5 = pred[] compare(%gte.4, %gte.4), direction=LT
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %copy.6 = f32[4]{0} copy(%x)
  %while.7 = (s32[], f32[4]{0}) while(%copy.6), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(model.blocks)/while"}
  ROOT %multiply.8 = f32[4]{0} multiply(%copy.6, %copy.6), metadata={op_name="jit(step)/step.optimizer/mul"}
}
"""


def test_op_names_read_each_instruction_and_inherit_the_callers():
    names = device.op_names(HLO)
    assert names["fusion.1"] == \
        "jit(step)/jvp(model.blocks)/while/body/sin"
    # a loop's counter and carry: the op_name of the while that runs them
    assert names["add.2"] == names["compare.5"] == \
        "jit(step)/jvp(model.blocks)/while"
    assert names["multiply.8"] == "jit(step)/step.optimizer/mul"
    assert "copy.6" not in names and "x" not in names
    assert device.scopes_of(names["add.2"]) == ("model.blocks",)


def scope_census(hlo_text: str) -> tuple:
    """(share of the fusion, dot and convolution instructions outside fused
    computations that carry a scope, set of scopes any instruction there
    carries) of a compiled module's text."""
    names = device.op_names(hlo_text)
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo_text))
    comp, total, scoped, seen = None, 0, 0, set()
    for line in hlo_text.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*{$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$", line)
        if not m or comp in fused:
            continue
        scopes = device.scopes_of(names.get(m.group(1), ""))
        seen.update(scopes)
        if re.search(r" (fusion|dot|convolution)\(", m.group(2)):
            total += 1
            scoped += bool(scopes)
    return scoped / total, seen


def _compiled_text(entry: str) -> str:
    """``entry`` compiled at tiny widths for four virtual CPU devices, in a
    child process (the device count is fixed when JAX starts)."""
    code = textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import get_model
        from repro.optim import get_optimizer

        cfg = dataclasses.replace(get_config("qwen3-0.6b", reduced=True),
                                  num_layers=4, compute_dtype=jnp.float32)
        params = get_model(cfg).init(jax.random.key(0))
        opt = get_optimizer("adamw", lr=1e-3)
        state = opt.init(params)
        tok = jnp.zeros((8, 32), jnp.int32)
        batch = {{"tokens": tok, "labels": tok}}
        if {entry!r} == "train_step":
            from repro.launch.steps import make_train_step
            text = jax.jit(make_train_step(cfg, opt, 2)).lower(
                params, state, batch).compile().as_text()
        else:
            from repro.launch.mesh import make_pipeline_mesh
            from repro.pipeline import (PipelineConfig,
                                        make_pipelined_train_step)
            mesh = make_pipeline_mesh(num_stages=4)
            with jax.set_mesh(mesh):
                step = make_pipelined_train_step(
                    cfg, mesh, PipelineConfig(4, 4), opt)
                text = jax.jit(step).lower(params, state,
                                           batch).compile().as_text()
        sys.stdout.write(text)
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=root, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.parametrize("entry, expected", [
    ("train_step", {"model.embed", "model.blocks", "model.attention",
                    "model.head_loss", "step.accumulate", "step.optimizer"}),
    ("pipelined_train_step", {"model.embed", "model.blocks",
                              "model.attention", "model.head_loss",
                              "step.optimizer", "pipe.ticks",
                              "pipe.combine"}),
])
def test_compiled_steps_put_their_work_under_scopes(entry, expected):
    share, seen = scope_census(_compiled_text(entry))
    assert share >= 0.9, share
    assert seen == expected


def test_enabled_span_lands_in_a_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    obs.reset()                 # spans other tests left in the registry
    with obs.enabled_scope():
        with jax.profiler.trace(str(tmp_path)):
            with obs.span("bcd.solve", b=2):
                jax.block_until_ready(jax.numpy.ones(3) + 1)
        assert [s.name for s in obs.wall_spans()] == ["bcd.solve"]
    obs.reset()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]
    assert "bcd.solve" in host
