"""Multi-device tests (subprocess: the main pytest process keeps 1 device;
children run on fake CPU devices, never on a chip).

Covers: the shard_map stage pipeline's numerics on a real (fake-device)
mesh, checkpoint reshard-on-restore across meshes, and a small-mesh
train_step lowering with the production sharding rules.
"""

import subprocess
import sys
import textwrap

import pytest


def _run(code: str, devices: int = 4):
    prelude = textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, "src")
    """)
    r = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(code)],
                       capture_output=True, text=True, cwd=".",
                       timeout=900)
    assert r.returncode == 0 and "PASS" in r.stdout, \
        (r.stdout[-2000:], r.stderr[-3000:])


def test_pipeline_loss_and_grads_match_plain():
    _run("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config
        from repro.models import get_model
        from repro.pipeline import PipelineConfig, make_pipelined_loss
        cfg = dataclasses.replace(get_config("llama3-8b", reduced=True),
                                  num_layers=4, remat="none",
                                  compute_dtype=jnp.float32)
        api = get_model(cfg)
        rng = jax.random.key(0)
        params = api.init(rng)
        batch = {"tokens": jax.random.randint(rng, (8, 16), 0, cfg.vocab),
                 "labels": jax.random.randint(rng, (8, 16), 0, cfg.vocab)}
        mesh = jax.make_mesh((2, 2), ("data", "stage"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pcfg = PipelineConfig(num_stages=2, num_microbatches=4)
        with jax.set_mesh(mesh):
            ploss = make_pipelined_loss(cfg, mesh, pcfg)
            lp = float(jax.jit(ploss)(params, batch))
            gp = jax.jit(jax.grad(ploss))(params, batch)
        l0 = float(jax.jit(api.loss)(params, batch))
        g0 = jax.jit(jax.grad(api.loss))(params, batch)
        assert abs(lp - l0) < 1e-5, (lp, l0)
        err = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), gp, g0)))
        assert err < 1e-4, err
        print("PASS")
    """)



@pytest.mark.parametrize("layers", [4, 8], ids=["gpipe", "interleaved"])
def test_pipeline_flash_grads_match_plain(layers):
    """The flash kernels (interpret mode) inside the pipeline's manual
    "stage" region, as a TPU would take them on the four-chip mesh: loss
    and every stage's parameter gradients match the plain loss through
    ``full_attention``.  Each stage's attention gradients are its own, not
    shared across stages by the kernels' inner map, one layer a stage
    (GPipe) or two rounds of one layer (interleaved)."""
    _run(f"""
        import dataclasses, functools
        import jax, jax.numpy as jnp
        from repro import obs
        from repro.configs import get_config
        from repro.kernels.flash import flash_attention
        from repro.launch.mesh import make_pipeline_mesh
        from repro.models import get_model, transformer as tf
        from repro.pipeline import PipelineConfig, make_pipelined_loss
        cfg = dataclasses.replace(get_config("qwen3-0.6b", reduced=True),
                                  num_layers={layers}, d_model=256,
                                  n_heads=2, n_kv=1, d_head=128, vocab=512,
                                  compute_dtype=jnp.float32)
        api = get_model(cfg)
        params = api.init(jax.random.key(0))
        k1, k2 = jax.random.split(jax.random.key(1))
        batch = {{"tokens": jax.random.randint(k1, (8, 128), 0, cfg.vocab),
                  "labels": jax.random.randint(k2, (8, 128), 0, cfg.vocab)}}
        l0, g0 = jax.jit(jax.value_and_grad(api.loss))(params, batch)
        calls = []
        def flash(*a, **kw):
            calls.append(kw)
            return flash_attention(*a, interpret=True, **kw)
        jax.default_backend = lambda: "tpu"
        tf.flash_attention = flash
        mesh = make_pipeline_mesh(num_stages=4)
        with jax.set_mesh(mesh), obs.enabled_scope():
            ploss = make_pipelined_loss(cfg, mesh, PipelineConfig(4, 4))
            lp, gp = jax.jit(jax.value_and_grad(ploss))(params, batch)
        assert obs.counter("pipe.virtual_stages") == {layers} // 4
        assert calls
        assert abs(float(lp) - float(l0)) < 1e-5, (lp, l0)
        err = jax.tree.map(lambda a, b: float(
            jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))), gp, g0)
        assert max(jax.tree.leaves(err)) < 1e-4, err
        print("PASS")
    """)


@pytest.mark.parametrize("stages, layers, q, rounds", [
    (2, 4, 2, 2),     # interleaved, Q = S: the hand-off lands just in time
    (2, 4, 4, 2),     # interleaved, Q > S: it waits Q - S ticks on stage 0
    (4, 4, 4, 1),     # one layer a stage: GPipe
    (4, 8, 2, 1),     # Q < S: the hand-off would come late, GPipe
])
def test_interleaved_pipeline_matches_plain(stages, layers, q, rounds):
    """The pipelined loss and every gradient leaf match the plain loss
    under each branch of the schedule (layer remat on, as trained), and
    ``pipe.virtual_stages`` says how many rounds of the ring it ran."""
    _run(f"""
        import dataclasses
        import jax, jax.numpy as jnp
        from repro import obs
        from repro.configs import get_config
        from repro.launch.mesh import make_pipeline_mesh
        from repro.models import get_model
        from repro.pipeline import PipelineConfig, make_pipelined_loss
        cfg = dataclasses.replace(get_config("llama3-8b", reduced=True),
                                  num_layers={layers}, remat="layer",
                                  compute_dtype=jnp.float32)
        api = get_model(cfg)
        params = api.init(jax.random.key(0))
        k1, k2 = jax.random.split(jax.random.key(1))
        batch = {{"tokens": jax.random.randint(k1, (8, 16), 0, cfg.vocab),
                  "labels": jax.random.randint(k2, (8, 16), 0, cfg.vocab)}}
        mesh = make_pipeline_mesh(num_stages={stages})
        with jax.set_mesh(mesh), obs.enabled_scope():
            ploss = make_pipelined_loss(cfg, mesh,
                                        PipelineConfig({stages}, {q}))
            lp, gp = jax.jit(jax.value_and_grad(ploss))(params, batch)
        assert obs.counter("pipe.virtual_stages") == {rounds}
        l0, g0 = jax.jit(jax.value_and_grad(api.loss))(params, batch)
        assert abs(float(lp) - float(l0)) < 1e-5, (lp, l0)
        err = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), gp, g0)))
        assert err < 1e-4, err
        print("PASS")
    """)


@pytest.mark.parametrize("arch, vocab, shards", [
    ("qwen1.5-4b", 384, 4),     # untied, QKV bias: split 4 ways by vocab
    ("qwen3-0.6b", None, 1),    # tied embeddings: replicated
    ("qwen1.5-4b", 386, 1),     # V not divisible by the stages: replicated
])
def test_pipeline_head_split_matches_plain(arch, vocab, shards):
    """The four-stage pipelined loss and every gradient leaf match the
    plain loss whether the LM head is split over the stages by vocabulary
    or replicated, and ``pipe.head_vocab_shards`` says which it was."""
    _run(f"""
        import dataclasses
        import jax, jax.numpy as jnp
        from repro import obs
        from repro.configs import get_config
        from repro.launch.mesh import make_pipeline_mesh
        from repro.models import get_model
        from repro.pipeline import PipelineConfig, make_pipelined_loss
        cfg = dataclasses.replace(get_config({arch!r}, reduced=True),
                                  num_layers=4, remat="none",
                                  compute_dtype=jnp.float32)
        if {vocab!r}:
            cfg = dataclasses.replace(cfg, vocab={vocab!r})
        api = get_model(cfg)
        params = api.init(jax.random.key(0))
        assert ("lm_head" in params) == (not cfg.tie_embeddings)
        k1, k2 = jax.random.split(jax.random.key(1))
        batch = {{"tokens": jax.random.randint(k1, (8, 16), 0, cfg.vocab),
                  "labels": jax.random.randint(k2, (8, 16), 0, cfg.vocab)}}
        mesh = make_pipeline_mesh(num_stages=4)
        with jax.set_mesh(mesh), obs.enabled_scope():
            ploss = make_pipelined_loss(cfg, mesh, PipelineConfig(4, 4))
            lp, gp = jax.jit(jax.value_and_grad(ploss))(params, batch)
        assert obs.counter("pipe.head_vocab_shards") == {shards}
        l0, g0 = jax.jit(jax.value_and_grad(api.loss))(params, batch)
        assert abs(float(lp) - float(l0)) < 1e-5, (lp, l0)
        err = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), gp, g0)))
        assert err < 1e-4, err
        print("PASS")
    """)


def test_split_head_stays_split_in_the_compiled_step():
    """The compiled four-stage train step keeps a vocab-split head split:
    ``lm_head`` and its AdamW moments come out (d, V/4) on every device,
    and no collective moves a tensor with a dimension of V or V/4 — only
    per-token statistics (mb, S) and input gradients (mb, S, d) cross the
    stages, so a head gathered or replicated again fails here."""
    _run(r"""
        import dataclasses, re
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.launch.mesh import make_pipeline_mesh
        from repro.models import get_model
        from repro.optim import get_optimizer
        from repro.pipeline import (PipelineConfig, make_pipelined_train_step,
                                    stage_shardings)
        cfg = dataclasses.replace(get_config("qwen1.5-4b", reduced=True),
                                  num_layers=4)
        d, V = cfg.d_model, cfg.vocab
        assert V % 4 == 0
        params = get_model(cfg).init(jax.random.key(0))
        opt = get_optimizer("adamw", lr=1e-3)
        state = opt.init(params)
        mesh = make_pipeline_mesh(num_stages=4)
        params = jax.device_put(params, stage_shardings(mesh, params))
        state = jax.device_put(state, stage_shardings(mesh, state))
        for leaf in (params["lm_head"], state["m"]["lm_head"],
                     state["v"]["lm_head"]):
            assert {s.data.shape for s in leaf.addressable_shards} == {
                (d, V // 4)}
        tok = jax.device_put(jnp.zeros((8, 32), jnp.int32),
                             NamedSharding(mesh, P()))
        with jax.set_mesh(mesh):
            step = make_pipelined_train_step(
                cfg, mesh, PipelineConfig(4, 4), opt)
            compiled = jax.jit(step).lower(
                params, state, {"tokens": tok, "labels": tok}).compile()
        p_out, s_out, _ = compiled.output_shardings
        for sh in (p_out["lm_head"], s_out["m"]["lm_head"],
                   s_out["v"]["lm_head"]):
            assert sh.shard_shape((d, V)) == (d, V // 4), sh
        # the compiled text names operands without their shapes: look each
        # up by its defining instruction
        hlo = compiled.as_text()
        shape_of = dict(re.findall(
            r"^\s+(?:ROOT )?%([\w.\-]+) = (\([^=]*?\)|\S+) ", hlo, re.M))
        coll = re.compile(
            r"^\s+(?:ROOT )?%([\w.\-]+) = .* (all-reduce|all-gather|"
            r"all-to-all|reduce-scatter|collective-permute)(?:-start)?"
            r"\(([^)]*)\)", re.M)
        found = coll.findall(hlo)
        assert found
        for name, kind, args in found:
            shapes = [shape_of[name]] + [
                shape_of[a] for a in re.findall(r"%([\w.\-]+)", args)]
            dims = {int(x) for sh in shapes
                    for dd in re.findall(r"\[([\d,]*)\]", sh)
                    for x in dd.split(",") if x}
            assert not dims & {V, V // 4}, (kind, shapes)
        print("PASS")
    """)


def test_planner_drives_pipeline_config():
    _run("""
        from repro.configs import get_config, arch_profile
        from repro.core import plan_stages
        from repro.pipeline import plan_to_pipeline_config
        prof = arch_profile(get_config("llama3-8b"))
        sp = plan_stages(prof, total_chips=256, stage_candidates=(2, 4, 8),
                         global_batch=256)
        assert sp.num_stages in (2, 4, 8)
        assert 1 <= sp.microbatch <= 256
        pcfg = plan_to_pipeline_config(sp, 256)
        assert 256 % pcfg.num_microbatches == 0
        assert sp.T_i > 0 and sp.L_t >= sp.T_f
        print("PASS")
    """, devices=1)


def test_checkpoint_reshards_across_meshes():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        import tempfile, os
        d = tempfile.mkdtemp()
        Auto = jax.sharding.AxisType.Auto
        mesh4 = jax.make_mesh((4,), ("model",), axis_types=(Auto,))
        x = jax.device_put(jnp.arange(32.0).reshape(8, 4),
                           NamedSharding(mesh4, P("model", None)))
        save_checkpoint(d, 0, {"x": x})
        mesh2 = jax.make_mesh((2, 2), ("data", "model"),
                              axis_types=(Auto,) * 2)
        sh = {"x": NamedSharding(mesh2, P(None, "model"))}
        restored, _ = restore_checkpoint(
            d, 0, jax.eval_shape(lambda: {"x": jnp.zeros((8, 4))}),
            shardings=sh)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(x))
        assert restored["x"].sharding.spec == P(None, "model")
        print("PASS")
    """)


def test_small_mesh_train_step_lowers_with_production_rules():
    """8-device (2 data x 4 model) lowering of the full train_step using
    the same sharding rules as the 512-device dry-run."""
    _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, input_specs, param_specs
        from repro.launch import (ShardingPolicy, batch_sharding,
                                  opt_sharding_tree, param_sharding_tree,
                                  make_train_step)
        from repro.optim import get_optimizer
        import dataclasses
        cfg = get_config("qwen3-0.6b", reduced=True)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        policy = ShardingPolicy()
        pshapes = param_specs(cfg)
        psh = param_sharding_tree(cfg, mesh, pshapes, policy)
        opt = get_optimizer("adamw")
        oshapes = jax.eval_shape(opt.init, pshapes)
        osh = opt_sharding_tree(mesh, "adamw", psh, pshapes)
        bshapes = {
            "tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        bsh = batch_sharding(cfg, mesh, bshapes, policy)
        step = make_train_step(cfg, opt, 2)
        jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None))
        with jax.set_mesh(mesh):
            compiled = jitted.lower(pshapes, oshapes, bshapes).compile()
        assert compiled.memory_analysis().temp_size_in_bytes > 0
        print("PASS")
    """, devices=8)


def test_elastic_restart_resharded():
    """Train on 4 devices, checkpoint, restore into a 2-device mesh and
    continue — elastic scaling across 'pod' counts."""
    _run("""
        import jax, jax.numpy as jnp, tempfile
        from repro.launch.train import train
        d = tempfile.mkdtemp()
        l1 = train("qwen3-0.6b", reduced=True, steps=4, batch=8, seq=16,
                   microbatches=2, ckpt_dir=d, ckpt_every=2, log_every=100)
        l2 = train("qwen3-0.6b", reduced=True, steps=6, batch=8, seq=16,
                   microbatches=2, ckpt_dir=d, ckpt_every=2, log_every=100)
        assert len(l2) == 2
        print("PASS")
    """, devices=2)
