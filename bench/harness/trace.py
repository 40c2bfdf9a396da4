"""Reduction of a profiler trace to per-device times.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
records: for each device plane, its operations as ``Op(start_ns, end_ns,
name, category, asynchronous)``, and the host spans that the harness writes
around the batch transfer, dispatch and wait (``jax.profiler.
TraceAnnotation`` named ``bench.*``). On a TPU an operation's event name is
its HLO instruction text (``%fusion.5 = bf16[..] fusion(..), kind=kOutput,
..``): ``name`` is the instruction's name and ``category`` its opcode, with
the fusion kind for a fusion (``fusion:kOutput``). The synchronous ops are
on the plane's ``XLA Ops`` line; ``Async XLA Ops`` holds the asynchronous
parts of copies and collectives. Everything after ``load`` is arithmetic on
intervals, so the tests check it on excerpts of traces recorded on the chip.

- busy: the union of a device's synchronous operations inside the window,
  leaving out control flow (``while``, ``conditional``, ``call``), whose
  events span the operations they run; idle is the window less busy;
- matmul time: the union of matmul-class operations: ``convolution`` and
  ``dot``, and output fusions (``fusion:kOutput``), which the TPU compiler
  forms around a convolution;
- exposed collective time: the union of collective operations' intervals
  (either line) less the union of every other synchronous operation's.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
HOST_SPAN_PREFIX = "bench."
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "collective-permute",
                    "reduce-scatter", "all-to-all", "collective-broadcast")
MATMUL_CATEGORIES = ("convolution", "dot", "fusion:kOutput")
CONTROL_FLOW = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Op:
    start_ns: float
    end_ns: float
    name: str
    category: str
    asynchronous: bool = False


@dataclasses.dataclass
class DeviceTime:
    """What one device did in the traced window, in seconds."""
    device: str
    busy_s: float
    matmul_s: float
    collective_s: float
    collective_exposed_s: float


def is_collective(op: Op) -> bool:
    text = (op.category + " " + op.name).lower()
    return any(w in text for w in COLLECTIVE_WORDS)


def is_matmul(op: Op) -> bool:
    return op.category in MATMUL_CATEGORIES


def is_leaf(op: Op) -> bool:
    """A synchronous operation that is not control flow around others."""
    return not op.asynchronous and op.category not in CONTROL_FLOW


_KIND = re.compile(r"kind=(k\w+)")


def parse_hlo(text: str) -> tuple:
    """(instruction name, category) of an HLO instruction's text; a text
    that is no instruction is its own name, with no category."""
    name, sep, rhs = text.partition(" = ")
    if not sep:
        return text, ""
    if rhs.startswith("("):                     # a tuple shape
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rhs = rhs[i + 1:].lstrip()
                break
    else:
        rhs = rhs.partition(" ")[2]
    opcode = rhs.partition("(")[0]
    kind = _KIND.search(rhs) if opcode == "fusion" else None
    return name.lstrip("%"), opcode + (":" + kind.group(1) if kind else "")


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} xplane files under {trace_dir}")
    return files[0]


def load(path: str) -> tuple:
    """({device plane name: [Op]}, [(start_ns, end_ns, host span name)])."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [Op(e.start_ns, e.start_ns + e.duration_ns,
                      *parse_hlo(e.name), line.name == ASYNC_LINE)
                   for line in plane.lines
                   if line.name in (OPS_LINE, ASYNC_LINE)
                   for e in line.events]
            if ops:
                devices[plane.name] = sorted(ops, key=lambda o: o.start_ns)
        elif plane.name.startswith("/host:"):
            spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(HOST_SPAN_PREFIX)]
    return devices, sorted(spans)


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted, non-overlapping [start, end) intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b) -> list:
    """The parts of merged intervals ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of the window [lo, hi) around merged ``busy``."""
    return subtract([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# Per-device reduction
# ---------------------------------------------------------------------------

def _intervals(ops, lo, hi) -> list:
    return union(clip([(o.start_ns, o.end_ns) for o in ops], lo, hi))


def device_time(name: str, ops, lo: float, hi: float) -> DeviceTime:
    ops = [o for o in ops if o.end_ns > lo and o.start_ns < hi]
    leaves = [o for o in ops if is_leaf(o)]
    coll = _intervals([o for o in ops if is_collective(o)], lo, hi)
    other = _intervals([o for o in leaves if not is_collective(o)], lo, hi)
    return DeviceTime(
        device=name,
        busy_s=length(_intervals(leaves, lo, hi)) / 1e9,
        matmul_s=length(_intervals([o for o in leaves if is_matmul(o)],
                                   lo, hi)) / 1e9,
        collective_s=length(coll) / 1e9,
        collective_exposed_s=length(subtract(coll, other)) / 1e9)


def top_ops(devices: dict, lo: float, hi: float, n: int = 10) -> list:
    """[["name (category)", seconds], ...]: the synchronous operations that
    took most device time, summed over devices."""
    tot = collections.Counter()
    for ops in devices.values():
        for o in ops:
            s, e = max(o.start_ns, lo), min(o.end_ns, hi)
            if e > s and is_leaf(o):
                tot[f"{o.name} ({o.category})"] += (e - s) / 1e9
    return [[k, v] for k, v in tot.most_common(n)]


def label_gaps(devices: dict, spans: list, lo: float, hi: float,
               n: int = 10) -> list:
    """[[what the host was doing, seconds], ...]: the longest idle gaps of
    every device, each labelled by the host span that covers most of it."""
    longest = []
    for ops in devices.values():
        longest += gaps(_intervals([o for o in ops if is_leaf(o)], lo, hi),
                        lo, hi)
    longest = sorted(longest, key=lambda g: g[1] - g[0], reverse=True)[:n]
    out = []
    for s, e in longest:
        best, cover = "no host span", 0.0
        for hs, he, span in spans:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = span, c
        out.append([best, (e - s) / 1e9])
    return out


def window(spans: list) -> tuple:
    """[first span start, last span end) of the harness's host spans."""
    if not spans:
        raise ValueError("the trace holds none of the harness's host spans")
    return min(s for s, _, _ in spans), max(e for _, e, _ in spans)
