"""repro.obs — zero-overhead-when-disabled telemetry (ISSUE 6).

Three pillars:

* **Idle/bubble accounting** (``utilization``): per-resource
  busy/blocked/fill/bubble/drain interval decomposition from either
  engine's output, surfaced as ``SimReport.utilization()`` and checked
  against the Eq. (12)-(14) closed form — the paper's "resource
  idleness" motivation turned into a measured quantity.
* **Span tracing** (``spans``): ``with obs.span("planner.solve"): ...``
  wall-clock instrumentation through the planner, BCD loop, cost models,
  simulator dispatch, and replanning coordinator, exportable to one
  Perfetto file next to the simulated-time pipeline tracks.
* **Counters** (``registry``): DP-cache and solve-memo hit rates,
  engine-dispatch tallies, fixpoint sweep counts, memoized-cost-model
  hit rates — dumped by the benchmark drivers alongside their CSVs.

Everything is off until :func:`enable` (or ``enabled_scope``); while
disabled the instrumentation costs a global load plus a branch per call
site and allocates nothing (``benchmarks/bench_obs.py`` enforces < 5%
overhead even *enabled* on the 10k-micro-batch chain).  Where JAX is
already imported, an enabled span also enters a
``jax.profiler.TraceAnnotation`` of its name, so planner and coordinator
spans (``bcd.solve``, ``ft.apply``, ...) land in a profiler trace on the
same clock as the device's operations.

On the device side, ``device`` names the parts of the training step:
``device.scope(name)`` is a ``jax.named_scope`` from the fixed vocabulary
``device.SCOPES`` (``model.attention``, ``step.optimizer``, ``pipe.ticks``,
...), which the compiled program carries in each instruction's
``op_name`` and a trace reader maps back with ``device.op_names`` and
``device.scopes_of``.  Scopes are compile-time metadata and are always
on.
"""

from . import device
from .registry import (Registry, counter, disable, dump, enable, enabled,
                       enabled_scope, gauge, get_registry, inc, reset)
from .spans import SpanRecord, span, span_summary, wall_spans
from .trace import (SIM_PID, SOLVER_PID, microbatch_flow_events,
                    solver_span_events, utilization_counter_events,
                    validate_chrome_trace)
from .utilization import (ResourceUtilization, UtilizationReport,
                          accumulate_service, busy_fractions,
                          resource_sort_key, resource_traces,
                          service_from_records, utilization_from_records,
                          utilization_from_timeline)

__all__ = [
    "Registry", "counter", "disable", "dump", "enable", "enabled",
    "enabled_scope", "gauge", "get_registry", "inc", "reset",
    "SpanRecord", "span", "span_summary", "wall_spans",
    "SIM_PID", "SOLVER_PID", "microbatch_flow_events", "solver_span_events",
    "utilization_counter_events", "validate_chrome_trace",
    "ResourceUtilization", "UtilizationReport", "accumulate_service",
    "busy_fractions", "resource_sort_key", "resource_traces",
    "service_from_records", "utilization_from_records",
    "utilization_from_timeline",
]
