"""Wall-clock span tracing: ``with span("planner.solve", b=4): ...``.

Spans record ``time.perf_counter()`` intervals into the process registry.
While telemetry is disabled :func:`span` returns one shared no-op context
manager, so instrumented call sites cost a global load plus a branch and
allocate nothing — the zero-overhead-when-disabled contract.

Finished spans export to a Perfetto/Chrome trace through
``repro.sim.events.write_chrome_trace(..., wall_spans=...)``, which puts
the wall-clock solver tracks on their own process id next to the
simulated-time pipeline tracks.  Where JAX is already imported, an enabled
span also enters a ``jax.profiler.TraceAnnotation`` of its name, so that a
profiler trace shows it on the host's clock beside the device's
operations.
"""

from __future__ import annotations

import dataclasses
import sys
import time

from . import registry as _registry


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One finished wall-clock span (``perf_counter`` seconds)."""
    name: str
    start: float
    end: float
    args: tuple          # ((key, value), ...) — kwargs at the call site

    @property
    def duration(self) -> float:
        return self.end - self.start


class _NullSpan:
    """Shared do-nothing context manager returned while disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "start", "annotation")

    def __init__(self, name, args):
        self.name = name
        self.args = args
        self.start = 0.0
        self.annotation = None

    def __enter__(self):
        jax = sys.modules.get("jax")      # never the one to import it
        if jax is not None:
            self.annotation = jax.profiler.TraceAnnotation(self.name)
            self.annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        _registry.get_registry().spans.append(
            SpanRecord(self.name, self.start, end, self.args))
        return False


def span(name: str, **args):
    """Context manager timing one named operation (no-op when disabled).

    Spans nest naturally — ``bcd.solve`` wraps per-iterate spans wraps
    ``planner.solve`` spans — and the Chrome-trace exporter renders the
    nesting as stacked slices on the solver track.
    """
    if not _registry.enabled():
        return _NULL
    return _Span(name, tuple(args.items()))


def wall_spans() -> list:
    """Finished spans recorded so far (in completion order)."""
    return list(_registry.get_registry().spans)


def span_summary() -> dict:
    """Per-name ``{count, total_s}`` rollup of the finished spans."""
    out: dict = {}
    for s in _registry.get_registry().spans:
        agg = out.setdefault(s.name, {"count": 0, "total_s": 0.0})
        agg["count"] += 1
        agg["total_s"] += s.duration
    return out
