"""Bring-up check of the main path on TPU: profile -> planner -> Q -> step.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the 4-stage pipelined step, 2x2 host

One chip runs three phases, in order:

1. planner: ``plan_stages`` on the qwen3-0.6b layer profile for one chip;
   its Q, T_i, L_t and bubble fraction are the cost model's predictions;
2. trainer: ``launch.train.train`` at qwen3-0.6b's published widths (28
   layers, d=1024, vocab 151936, random weights from a seed) for a few
   AdamW steps with the planner's Q;
3. paper workload: a ``core.ours`` plan for VGG-16 on the paper's edge
   network, run through ``SplitLearningExecutor.train_round``.

``--four-chips`` runs only the paper's pipelined split across devices: a
4-stage ``pipeline/spmd.py`` train step of qwen3-0.6b (7 layers per stage),
compared with the plain loss and gradient on the same parameters and batch.

Everything runs in this one process; it starts no other.  Without a TPU it
exits non-zero before any phase and prints no result.  Any failed phase or
check raises, so the exit code is non-zero and the result line is never
printed.  Otherwise the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Times printed
along the way are one-off wall-clock readings, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "qwen3-0.6b"
#: one-chip trainer shape.  With the planner's Q=8 the micro-batches are
#: 2 x 1024 tokens; compiled for v5e that step needs 13.81 GiB of the
#: chip's 16 GiB (6.66 arguments + 6.66 outputs + 0.48 temporaries).
#: 2 x 2048-token micro-batches need 16.10 GiB and do not fit.
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 16, 1024, 5
MAX_MICROBATCH_TOKENS = 2 * 1024
#: four-chip pipelined step: 8 x 1024 tokens, Q from the planner
PIPE_BATCH, PIPE_SEQ, PIPE_STEPS = 8, 1024, 3
#: bf16 tolerance for pipelined vs plain: loss within 2 bf16 ulps
#: (relative 2^-7), gradient within 2^-4 in relative L2 norm
LOSS_RTOL, GRAD_RTOL = 2.0 ** -7, 2.0 ** -4


def check(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)


def device_check(need: int):
    """Versions and devices; exits non-zero without ``need`` TPU chips."""
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {libtpu}")
    devs = jax.devices()
    print(f"devices: {devs}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: jax found {devs[0].platform} devices")
    if len(devs) < need:
        raise SystemExit(f"need {need} TPU chips, jax found {len(devs)}")
    return devs


def planner_phase(total_chips: int, global_batch: int):
    """The StagePlan for ``total_chips`` chips as one pipeline of that
    many stages, and the runtime PipelineConfig it yields."""
    from repro.configs import arch_profile, get_config
    from repro.core import plan_stages
    from repro.pipeline import plan_to_pipeline_config

    prof = arch_profile(get_config(ARCH))
    sp = plan_stages(prof, total_chips=total_chips,
                     stage_candidates=(total_chips,),
                     global_batch=global_batch)
    pcfg = plan_to_pipeline_config(sp, global_batch)
    print(f"planner prediction ({ARCH} train_4k profile, {total_chips} "
          f"chip(s), B={global_batch}): stages={sp.num_stages} "
          f"layer_ranges={sp.layer_ranges} b={sp.microbatch} "
          f"Q={pcfg.num_microbatches} T_i={float(sp.T_i)!r}s "
          f"L_t={float(sp.L_t)!r}s "
          f"bubble_fraction={float(sp.bubble_fraction)!r}", flush=True)
    return sp, pcfg


def trainer_phase(q: int, *, reduced: bool = False, batch: int = TRAIN_BATCH,
                  seq: int = TRAIN_SEQ, steps: int = TRAIN_STEPS):
    """A few full-width AdamW steps through ``launch.train.train``."""
    from repro.configs import get_config
    from repro.launch.train import train

    check(batch % q == 0 and batch // q * seq <= MAX_MICROBATCH_TOKENS,
          f"Q={q} gives {batch // q} x {seq}-token micro-batches, more than "
          f"the {MAX_MICROBATCH_TOKENS} tokens rehearsed to fit one chip")
    vocab = get_config(ARCH, reduced=reduced).vocab
    losses = train(ARCH, reduced=reduced, steps=steps, batch=batch, seq=seq,
                   microbatches=q, log_every=1, seed=0)
    print(f"trainer losses: {losses}", flush=True)
    check(all(math.isfinite(x) for x in losses), "non-finite loss")
    check(abs(losses[0] - math.log(vocab)) < 1.0,
          f"step-0 loss {losses[0]} is not near ln(vocab) = "
          f"{math.log(vocab)}")
    check(losses[-1] < losses[0], "loss did not fall")
    return losses


def paper_phase(rounds: int = 6):
    """The paper's workload: a BCD plan for VGG-16 on the edge network,
    executed as split-learning rounds (heavy-ball SGD, one batch)."""
    import jax
    import jax.numpy as jnp

    from repro.core import make_edge_network, ours, vgg16_profile
    from repro.data import classification_batches
    from repro.models import vgg as vgg_lib
    from repro.pipeline import SplitLearningExecutor

    prof = vgg16_profile(work_units="bytes")
    net = make_edge_network(num_servers=6, num_clients=4, seed=1,
                            kappa=1 / 32.0)
    plan = ours(prof, net, B=16, b0=4)
    print(f"paper plan prediction (VGG-16, B=16): cuts={plan.solution.cuts} "
          f"placement={plan.solution.placement} b={plan.b} "
          f"Q={plan.num_microbatches} L_t={float(plan.L_t)!r}s", flush=True)

    ex = SplitLearningExecutor(plan, prof, net, seed=0)
    batch = {k: jnp.asarray(v) for k, v in
             next(classification_batches(batch=16, seed=0)).items()}
    ref = float(jax.jit(vgg_lib.loss_fn)(ex.full_params, batch))
    losses = []
    for r in range(rounds):
        t0 = time.perf_counter()
        losses.append(ex.train_round(batch, lr=0.01, momentum=0.9))
        ms = (time.perf_counter() - t0) * 1e3
        print(f"sl round {r}  loss {losses[-1]:.4f}  {ms:.1f} ms"
              + ("  (includes compile)" if r == 0 else ""), flush=True)
    print(f"sl losses: {losses}  (unsplit reference at round 0: {ref})")
    check(all(math.isfinite(x) for x in losses), "non-finite SL loss")
    check(abs(losses[0] - ref) <= 1e-2 * abs(ref),
          f"split round-0 loss {losses[0]} != unsplit loss {ref}")
    check(losses[-1] < losses[0], "SL loss did not fall")
    return losses


def pipeline_programs(cfg, mesh, pcfg, opt):
    """The three programs of the four-chip phase, jitted: the plain
    (unpipelined) loss and gradient, the pipelined loss and gradient, and
    the pipelined AdamW train step."""
    import jax

    from repro.models import get_model
    from repro.pipeline import (make_pipelined_loss,
                                make_pipelined_train_step, microbatch_grads)

    api = get_model(cfg)
    q = pcfg.num_microbatches
    plain = jax.jit(lambda p, b: microbatch_grads(api.loss, p, b, q))
    piped = jax.jit(jax.value_and_grad(make_pipelined_loss(cfg, mesh, pcfg)))
    step = jax.jit(make_pipelined_train_step(cfg, mesh, pcfg, opt),
                   donate_argnums=(0, 1))
    return plain, piped, step


def _compile(name, jitted, *args):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    print(f"compiled {name} in {time.perf_counter() - t0:.2f}s", flush=True)
    return compiled


def four_chip_phase(cfg=None, *, batch: int = PIPE_BATCH,
                    seq: int = PIPE_SEQ, steps: int = PIPE_STEPS):
    """4-stage pipelined qwen3-0.6b (``cfg``, default the published one)
    vs the plain loss, on a (data=1, stage=4, model=1) mesh over
    ``jax.devices()``."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data import token_lm_batches
    from repro.launch.mesh import make_pipeline_mesh
    from repro.models import get_model
    from repro.optim import get_optimizer
    from repro.pipeline import stage_shardings

    _, pcfg = planner_phase(4, batch)
    cfg = cfg or get_config(ARCH)
    mesh = make_pipeline_mesh(num_stages=4)
    print(f"mesh: {dict(mesh.shape)}  {cfg.num_layers} layers, "
          f"{cfg.num_layers // 4} per stage", flush=True)
    opt = get_optimizer("adamw", lr=1e-3)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    params = jax.device_put(params, stage_shardings(mesh, params))
    opt_state = opt.init(params)
    opt_state = jax.device_put(opt_state, stage_shardings(mesh, opt_state))
    data = token_lm_batches(batch=batch, seq_len=seq, vocab=cfg.vocab, seed=0)
    tokens = {k: jnp.asarray(v) for k, v in next(data).items()}

    plain, piped, step = pipeline_programs(cfg, mesh, pcfg, opt)
    with jax.set_mesh(mesh):
        plain = _compile("plain loss+grad", plain, params, tokens)
        piped = _compile("pipelined loss+grad", piped, params, tokens)
        step = _compile("pipelined train step", step, params, opt_state,
                        tokens)
        l0, g0 = plain(params, tokens)
        lp, gp = piped(params, tokens)
    l0, lp = float(l0), float(lp)
    sq = lambda t: sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
                       for x in jax.tree.leaves(t))
    diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, gp, g0)
    grad_rel = math.sqrt(sq(diff) / sq(g0))
    print(f"pipelined loss {lp!r}  plain loss {l0!r}  "
          f"rel diff {abs(lp - l0) / abs(l0)!r}  "
          f"grad rel L2 diff {grad_rel!r}", flush=True)
    check(abs(lp - l0) <= LOSS_RTOL * abs(l0),
          f"pipelined loss {lp} != plain loss {l0}")
    check(grad_rel <= GRAD_RTOL, f"gradient differs: rel L2 {grad_rel}")
    del g0, gp, diff                  # free HBM for the train steps

    leaf = jax.tree.leaves(params["layers"])[0]
    owners = sorted((s.device.id, s.data.shape[0])
                    for s in leaf.addressable_shards)
    print(f"layer blocks per device (device id, layers): {owners}")
    check(len({d for d, _ in owners}) == 4
          and all(n == cfg.num_layers // 4 for _, n in owners),
          "the four stages do not sit on four devices")

    losses = []                       # AdamW steps on the one batch
    with jax.set_mesh(mesh):
        for i in range(steps):
            t0 = time.perf_counter()
            params, opt_state, metrics = step(params, opt_state, tokens)
            jax.block_until_ready((params, opt_state, metrics))
            ms = (time.perf_counter() - t0) * 1e3
            losses.append(float(metrics["loss"]))
            print(f"pipelined step {i}  loss {losses[-1]:.4f}  {ms:.1f} ms",
                  flush=True)
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"device {d.id}: bytes_in_use={stats.get('bytes_in_use')} "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    check(all(math.isfinite(x) for x in losses), "non-finite loss")
    check(abs(losses[0] - lp) <= LOSS_RTOL * abs(lp),
          f"train-step loss {losses[0]} != pipelined loss {lp}")
    check(losses[-1] < losses[0], "pipelined loss did not fall")
    return {"plain_loss": l0, "pipelined_loss": lp, "grad_rel": grad_rel,
            "losses": losses}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-stage pipelined step and its "
                         "comparison with the plain loss (needs 4 chips)")
    args = ap.parse_args(argv)
    devs = device_check(4 if args.four_chips else 1)
    sys.path.insert(0, SRC)
    from repro.launch.cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.four_chips:
        four_chip_phase()
    else:
        _, pcfg = planner_phase(1, TRAIN_BATCH)
        trainer_phase(pcfg.num_microbatches)
        paper_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
