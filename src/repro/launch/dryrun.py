import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay the first statements in this module — jax
locks the device count on first init, and the production meshes need 512
placeholder host devices.  Nothing here allocates device memory: inputs are
ShapeDtypeStructs, params come from jax.eval_shape, and the only artifacts
are the compiled executable's memory_analysis / cost_analysis plus the HLO
collective-traffic stats, persisted to results/dryrun/*.json for the
roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun                      # all cells, both meshes
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --mode pipeline ...  # paper-mode train cells
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_IDS, SHAPES, get_config, input_specs,
                           cache_specs, param_specs, supports_shape)
from repro.launch import sharding as shlib
from repro.launch.mesh import make_production_mesh, make_pipeline_mesh, mesh_tag
from repro.launch.steps import (default_microbatches, default_optimizer_name,
                                make_decode_step, make_prefill_step,
                                make_train_step)
from repro.optim import get_optimizer
from repro.utils.hlo import (collective_bytes, cpu_f32_promotion_bytes,
                             hlo_cost, op_histogram)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _mem_dict(mem) -> dict:
    return {k: getattr(mem, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}


def _lower_cell(arch: str, shape: str, mesh, *, policy=None, q_override=None,
                donate: bool = True):
    """Build + lower + compile one cell; returns the record dict."""
    cfg = get_config(arch)
    if os.environ.get("REPRO_SEQ_PARALLEL"):
        cfg = dataclasses.replace(cfg, seq_parallel_residual=True)
    if os.environ.get("REPRO_REMAT"):
        cfg = dataclasses.replace(cfg, remat=os.environ["REPRO_REMAT"])
    if os.environ.get("REPRO_FF_CHUNKS"):
        cfg = dataclasses.replace(cfg, moe_ff_chunks=int(os.environ["REPRO_FF_CHUNKS"]))
    if os.environ.get("REPRO_CF"):
        cfg = dataclasses.replace(cfg, capacity_factor=float(os.environ["REPRO_CF"]))
    sp = SHAPES[shape]
    policy = policy or shlib.ShardingPolicy()
    t0 = time.time()

    pshapes = param_specs(cfg)
    psh = shlib.param_sharding_tree(cfg, mesh, pshapes, policy)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_tag(mesh),
           "kind": sp.kind, "policy": dataclasses.asdict(policy)}

    if sp.kind == "train":
        opt_name = default_optimizer_name(cfg)
        q = q_override or default_microbatches(cfg, sp.global_batch)
        opt = get_optimizer(opt_name)
        oshapes = jax.eval_shape(opt.init, pshapes)
        osh = shlib.opt_sharding_tree(mesh, opt_name, psh, pshapes)
        bshapes = input_specs(cfg, shape)
        bsh = shlib.batch_sharding(cfg, mesh, bshapes, policy)
        step = make_train_step(cfg, opt, q)
        rec.update(optimizer=opt_name, microbatches=q)
        jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None),
                         donate_argnums=(0, 1) if donate else ())
        with jax.set_mesh(mesh):
            lowered = jitted.lower(pshapes, oshapes, bshapes)
    elif sp.kind == "prefill":
        bshapes = input_specs(cfg, shape)
        bsh = shlib.batch_sharding(cfg, mesh, bshapes, policy)
        cshapes = cache_specs(cfg, shape)
        csh = shlib.cache_sharding(cfg, mesh, cshapes, policy)
        # serving runs bf16 params
        cfg_srv = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
        pshapes = param_specs(cfg_srv)
        psh = shlib.param_sharding_tree(cfg_srv, mesh, pshapes, policy)
        # the cache covers the full prompt incl. prepended patch tokens
        step = make_prefill_step(cfg_srv, sp.seq_len + cfg.patch_tokens)
        jitted = jax.jit(step, in_shardings=(psh, bsh),
                         out_shardings=(None, csh))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(pshapes, bshapes)
    else:  # decode
        cfg_srv = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
        pshapes = param_specs(cfg_srv)
        psh = shlib.param_sharding_tree(cfg_srv, mesh, pshapes, policy)
        cshapes = cache_specs(cfg_srv, shape)
        csh = shlib.cache_sharding(cfg_srv, mesh, cshapes, policy)
        tok = jax.ShapeDtypeStruct((sp.global_batch, 1), jnp.int32)
        toksh = shlib.batch_sharding(cfg_srv, mesh, {"t": tok}, policy)["t"]
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        step = make_decode_step(cfg_srv)
        jitted = jax.jit(step, in_shardings=(psh, csh, toksh, None),
                         out_shardings=(None, csh),
                         donate_argnums=(1,) if donate else ())
        with jax.set_mesh(mesh):
            lowered = jitted.lower(pshapes, cshapes, tok, pos)

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    hc = hlo_cost(hlo)      # trip-count-aware (XLA counts loop bodies once)
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))

    rec.update(
        lower_compile_seconds=round(time.time() - t0, 2),
        devices=n_dev,
        memory=_mem_dict(mem),
        # raw XLA numbers (loop bodies once) — kept for reference
        xla_flops_per_device=float(cost.get("flops", 0.0)),
        xla_bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        # trip-count-corrected per-device numbers (used by the roofline)
        flops_per_device=hc.flops,
        bytes_per_device=hc.traffic_bytes,
        collective_bytes_per_device=hc.collective_bytes,
        collective_breakdown=hc.collective_by_kind,
        while_trip_counts=hc.while_trip_counts,
        unresolved_loops=hc.unresolved_loops,
        op_histogram=op_histogram(hlo, top=12),
    )
    hbm = float(mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    promo = cpu_f32_promotion_bytes(hlo)
    rec.update(
        hbm_per_device=hbm,
        cpu_f32_promotion_bytes=promo,
        hbm_per_device_tpu_adjusted=hbm - promo,
        fits_16gb=bool(hbm - promo < 16 * 2**30),
    )
    return rec


def _lower_pipeline_cell(arch: str, mesh, *, num_stages: int = 4,
                         q: int = 16):
    """Paper-mode train cell: the shard_map stage pipeline (spmd.py)."""
    from repro.pipeline import PipelineConfig, make_pipelined_train_step
    cfg = get_config(arch)
    if os.environ.get("REPRO_REMAT"):
        cfg = dataclasses.replace(cfg, remat=os.environ["REPRO_REMAT"])
    sp = SHAPES["train_4k"]
    t0 = time.time()
    if cfg.num_layers % num_stages:
        raise ValueError(f"{arch}: L={cfg.num_layers} % stages={num_stages}")
    policy = shlib.ShardingPolicy(batch_axes=("pod", "data"))
    pshapes = param_specs(cfg)
    psh = shlib.param_sharding_tree(cfg, mesh, pshapes, policy)
    opt_name = default_optimizer_name(cfg)
    opt = get_optimizer(opt_name)
    oshapes = jax.eval_shape(opt.init, pshapes)
    osh = shlib.opt_sharding_tree(mesh, opt_name, psh, pshapes)
    bshapes = input_specs(cfg, "train_4k")
    bsh = shlib.batch_sharding(cfg, mesh, bshapes, policy)
    pcfg = PipelineConfig(num_stages=num_stages, num_microbatches=q)
    step = make_pipelined_train_step(cfg, mesh, pcfg, opt)
    jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                     out_shardings=(psh, osh, None), donate_argnums=(0, 1))
    with jax.set_mesh(mesh):
        lowered = jitted.lower(pshapes, oshapes, bshapes)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    hc = hlo_cost(hlo)
    return {
        "arch": arch, "shape": "train_4k", "mesh": mesh_tag(mesh),
        "kind": "train-pipeline", "num_stages": num_stages,
        "microbatches": q, "optimizer": opt_name,
        "lower_compile_seconds": round(time.time() - t0, 2),
        "memory": _mem_dict(mem),
        "flops_per_device": hc.flops,
        "bytes_per_device": hc.traffic_bytes,
        "collective_bytes_per_device": hc.collective_bytes,
        "collective_breakdown": hc.collective_by_kind,
        "unresolved_loops": hc.unresolved_loops,
        "hbm_per_device": float(mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
    }


def run_cells(archs, shapes, meshes, *, mode="baseline", out_dir=RESULTS_DIR,
              force=False, policy=None, q_override=None, tag=""):
    os.makedirs(out_dir, exist_ok=True)
    failures, done = [], 0
    for mesh_name in meshes:
        multi = mesh_name == "multi"
        mesh = (make_production_mesh(512 if multi else 256, multi_pod=multi)
                if mode == "baseline" else
                make_pipeline_mesh(512 if multi else 256, multi_pod=multi))
        for arch in archs:
            cfg = get_config(arch)
            for shape in shapes:
                if not supports_shape(cfg, shape):
                    print(f"SKIP {arch} x {shape} (N/A: full attention "
                          f"at 500k) ")
                    continue
                suffix = f"_{tag}" if tag else ""
                fname = os.path.join(
                    out_dir, f"{arch}__{shape}__{mesh_name}"
                             f"{'_pipe' if mode == 'pipeline' else ''}"
                             f"{suffix}.json")
                if os.path.exists(fname) and not force:
                    print(f"CACHED {arch} x {shape} x {mesh_name}")
                    done += 1
                    continue
                try:
                    if mode == "pipeline":
                        if shape != "train_4k":
                            continue
                        rec = _lower_pipeline_cell(arch, mesh)
                    else:
                        rec = _lower_cell(arch, shape, mesh, policy=policy,
                                          q_override=q_override)
                    with open(fname, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"OK {arch} x {shape} x {mesh_name}: "
                          f"hbm/dev={rec['hbm_per_device']/2**30:.2f}GiB "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"coll/dev={rec['collective_bytes_per_device']/2**20:.1f}MiB "
                          f"({rec['lower_compile_seconds']}s)", flush=True)
                    done += 1
                except Exception as e:
                    failures.append((arch, shape, mesh_name, repr(e)))
                    print(f"FAIL {arch} x {shape} x {mesh_name}: {e!r}",
                          flush=True)
                    traceback.print_exc()
    print(f"\n{done} cells OK, {len(failures)} failures")
    for f in failures:
        print("  FAIL:", *f)
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS) + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--mode", default="baseline",
                    choices=["baseline", "pipeline"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for result files "
                    "(perf-iteration variants)")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    policy = shlib.ShardingPolicy(fsdp=not args.no_fsdp)
    failures = run_cells(archs, shapes, meshes, mode=args.mode,
                         out_dir=args.out, force=args.force, policy=policy,
                         q_override=args.microbatches, tag=args.tag)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
