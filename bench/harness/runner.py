"""One run of one cell: set-up, the measured window, the check, the result.

Set-up (counted in ``setup_s``) turns on the compile cache, draws the
traffic and the weights from the seed (the weights on the device, in one
jitted call, each device making its own shard), builds the cell's entry and
drives it through the check batches, which compiles every program the
window calls. The window then runs one step per batch, each ending in
``block_until_ready``, until ``seconds`` have passed; only the batch's copy
to the device happens inside it besides the step. After the window the
peak memory is read, the program's state is freed, and the plain reference
follows the check steps (``check.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import sys
import time

import numpy as np

from . import check, spec, trace as trace_lib, traffic

#: compile events counted inside the window (each is a failure there)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Record:
    """What the per-layer metric readers (``bench/metrics``) read."""
    cell: spec.Cell
    chips: int
    stages: int
    q: int
    batch: int
    seq: int
    flops_per_token: float
    peaks: dict
    planner_profile: object
    steps_ms: list
    memory_peak_bytes: int
    trace: list = dataclasses.field(default_factory=list)  # [DeviceTime]
    trace_window_s: float = 0.0

    @property
    def steps_traced(self) -> int:
        return len(self.steps_ms) if self.trace else 0

    @property
    def tokens_traced(self) -> int:
        return self.steps_traced * self.batch * self.seq


class _CompileCounter:
    """Counts the compilations (and compile-cache loads) JAX reports."""

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, _secs, **_):
        if event in COMPILE_EVENTS:
            self.count += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.count += 1


def devices_for(cell, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:cell.chips]


def _step_loop(prog, params, state, batches, annotate):
    """Drive ``prog`` over ``batches`` as the window does; yields
    (seconds, params, state, loss) per step."""
    import jax

    for b in batches:
        t0 = time.perf_counter()
        with annotate("bench.h2d"):
            dev_b = prog.put(b)
        with annotate("bench.dispatch"):
            out = prog.step(params, state, dev_b)
        with annotate("bench.wait"):
            jax.block_until_ready(out)
        params, state = out[0], out[1]
        yield time.perf_counter() - t0, params, state, prog.loss(out)


def _cycle(pool):
    while True:
        yield from pool


class Harness:
    """A cell's program, weights and check, built once per process."""

    def __init__(self, name: str, *, root: str = spec.ROOT,
                 require_tpu: bool = True, log=print):
        self.cell = cell = spec.load_cell(name, root)
        import jax
        import jax.numpy as jnp

        self.devices = devices_for(cell, require_tpu)
        self.peaks = spec.peaks(self.devices[0].device_kind, root)
        sys.path.insert(0, os.path.join(spec.ROOT, "src"))
        from repro.launch.cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_threefry_partitionable", True)
        from . import programs

        self.programs = programs
        c = self.c = cell.config
        self.hp = cell.workload["optimizer"]
        self.ref = ref = spec.family_module("reference", cell.family, root)
        self.flops = spec.family_module("flops", cell.family, root)
        log(f"cell {name}: {cell.chips} chip(s) "
            f"{self.devices[0].device_kind}, B={cell.batch} S={cell.seq}, "
            f"compile cache {cache_dir}")
        shapes = jax.eval_shape(lambda k: ref.init_params(k, c),
                                jax.random.key(0))
        self.prog = programs.build(cell, shapes, self.devices)
        self.q = programs.microbatches(cell)
        self.init = jax.jit(lambda k: ref.init_params(k, c),
                            out_shardings=self.prog.param_sharding)
        self.norms = jax.jit(check.leaf_norms)
        self.change = jax.jit(lambda p, k: check.leaf_norms(
            jax.tree.map(jnp.subtract, p, ref.init_params(k, c))))
        self.counter = _CompileCounter()
        self.annotate = jax.profiler.TraceAnnotation

    def batches(self, seed: int) -> tuple:
        return traffic.make_batches(self.cell.traffic,
                                    int(self.c["vocab_size"]), seed)

    def drive_check(self, key, check_batches, prog=None) -> tuple:
        """Seeded weights through the check batches by the window's call and
        feed: (params, state, the program's readings). Call it inside
        ``prog.mesh_context()``."""
        prog = prog or self.prog
        params = self.init(key)
        state = prog.init_state(params)
        losses, grads = [], None
        for i, (_, params, state, loss) in enumerate(_step_loop(
                prog, params, state, check_batches, self.annotate)):
            losses.append(float(loss))
            if i == 0:
                grads = np.asarray(self.norms(state["m"]), np.float64) / (
                    1 - float(self.hp["b1"]))
        moved = np.asarray(self.change(params, key), np.float64)
        return params, state, {"losses": losses, "grads": grads,
                               "change": moved}

    def reference(self, key, check_batches, **kw) -> dict:
        return check.reference_readings(self.ref, self.c, self.hp, key,
                                        check_batches, self.devices, **kw)


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str = spec.ROOT, require_tpu: bool = True,
        trace_dir: str | None = None, log=print) -> dict:
    """One run of cell ``name``; returns the result object (the last line's
    content). Raises ``NoChip`` without the chips the cell asks for."""
    import jax

    h = Harness(name, root=root, require_tpu=require_tpu, log=log)
    cell, prog, c = h.cell, h.prog, h.c
    check_batches, pool = h.batches(seed)
    key = jax.random.key(seed)
    with prog.mesh_context():
        params, state, program_readings = h.drive_check(key, check_batches)

        # the window
        if trace:
            jax.profiler.start_trace(trace_dir)
        steps_s, window_losses = [], []
        compiles_before = h.counter.count
        gc.collect()
        gc.disable()          # no collector pauses inside the window
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        deadline = t0 + seconds
        for dt, params, state, loss in _step_loop(
                prog, params, state, _cycle(pool), h.annotate):
            steps_s.append(dt)
            window_losses.append(loss)
            if time.perf_counter() >= deadline:
                break
        window_s = time.perf_counter() - t0
        gc.enable()
        compiles = h.counter.count - compiles_before
        if trace:
            jax.profiler.stop_trace()
    nonfinite = sum(not math.isfinite(float(x)) for x in window_losses)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in prog.devices]
    memory_peak = int(max(mem))
    log(f"window: {len(steps_s)} steps in {window_s:.3f} s, {compiles} "
        f"compile(s), {nonfinite} non-finite loss(es); peak bytes {mem}; "
        f"step ms {[round(s * 1e3, 1) for s in steps_s]}")
    del params, state, loss, window_losses
    gc.collect()

    peaks, devices = h.peaks, h.devices
    rec = Record(cell=cell, chips=cell.chips,
                 stages=h.programs.stages(cell.workload), q=h.q,
                 batch=cell.batch, seq=cell.seq,
                 flops_per_token=h.flops.flops_per_token(c, cell.seq),
                 peaks=peaks,
                 planner_profile=cell.arch.planner_profile(c, cell.seq),
                 steps_ms=[s * 1e3 for s in steps_s],
                 memory_peak_bytes=memory_peak)
    result = {"correct": False, "attempted": len(steps_s),
              "failed": nonfinite + compiles, "metrics": {},
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": memory_peak}}
    if trace:
        t_reduce = time.perf_counter()
        _reduce_trace(rec, result, trace_dir)
        log(f"trace reduced in {time.perf_counter() - t_reduce:.1f} s")
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"], root)(rec)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
    else:
        tokens_per_s = len(steps_s) * cell.batch * cell.seq / window_s
        values = {
            "tokens_per_s": tokens_per_s,
            "mfu": 100.0 * rec.flops_per_token * tokens_per_s
            / (cell.chips * peaks["bf16_flops_per_s"]),
            "step_ms_p95": float(np.percentile(rec.steps_ms, 95)),
            "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    # the check, once the program's state is freed
    ref_readings = h.reference(key, check_batches)
    correct, checks = check.judge(check.gaps(program_readings, ref_readings),
                                  cell.workload["limits"])
    result["correct"] = correct
    result["checks"] = checks
    return result


def _reduce_trace(rec: Record, result: dict, trace_dir: str):
    path = trace_lib.find_xplane(trace_dir)
    devices, spans = trace_lib.load(path)
    lo, hi = trace_lib.window(spans)
    rec.trace = [trace_lib.device_time(n, ops, lo, hi)
                 for n, ops in sorted(devices.items())]
    if not rec.trace:
        raise RuntimeError("the trace holds no device operations")
    rec.trace_window_s = (hi - lo) / 1e9
    result["device"]["busy_s"] = (sum(d.busy_s for d in rec.trace)
                                  / len(rec.trace))
    result["device"]["window_s"] = rec.trace_window_s
    result["breakdown"] = {
        "device_ops": trace_lib.top_ops(devices, lo, hi),
        "idle_gaps": trace_lib.label_gaps(devices, spans, lo, hi)}
