"""Device time by the program's scopes, read from the traced window.

The program names the parts of its step on the device with the scopes of
``repro.obs.device`` (``model.attention``, ``step.optimizer``, ...); each
compiled instruction carries them in its ``op_name`` metadata. A trace
names each device operation by its instruction alone, but the xplane's
metadata plane also holds the HLO of every program the process compiled
(one ``Hlo Proto`` stat per program), and with it each instruction's
``op_name``. So the window's trace is enough:

- ``op_names`` reads {instruction: op_name} from the xplane's bytes: an
  instruction the compiler made without metadata (a loop's counter, a copy
  of its carry) takes the op_name of the instruction that calls its
  computation, so a scan's bookkeeping falls under the scope around the
  scan. Where two programs share an instruction name, the larger
  program's (the step's) is kept;
- ``scope_time`` is, for each scope, the union of one device's synchronous
  operations (``trace.is_leaf``, as the busy time) that the scope holds,
  clipped to the window;
- ``seconds()`` gives [{scope: seconds}] per device of the run being
  read, computed once per trace; a program without ``repro.obs.device``
  gives [].

The harness hands a metric reader only the run's ``Record``, which names no
trace; the xplane lies in the directory the running ``runner.run`` was
given (``trace_dir``), which ``window_xplane`` reads from that call's
frame. Nothing here changes what the harness reads or reports.
"""

from __future__ import annotations

import collections
import sys
import time

from . import trace as trace_lib

HLO_PROTO_STAT = "Hlo Proto"
GAP_NS = 50e6          # in-step gaps logged with the ops on either side

_cache: dict = {}


# ---------------------------------------------------------------------------
# The protobuf wire format, as far as the xplane and HLO messages need it
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: an int for a varint, bytes
    (a memoryview) for a length-delimited field; fixed-width fields are
    skipped."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


# XSpace.planes 1; XPlane: event_metadata 4, stat_metadata 5 (map entries:
# key 1, value 2); XEventMetadata.stats 5; XStatMetadata: id 1, name 2;
# XStat: metadata_id 1, bytes 6.

def hlo_protos(xspace) -> list:
    """The ``Hlo Proto`` stats of an xplane (one serialized ``HloProto``
    per compiled program)."""
    out = []
    for f, plane in fields(xspace):
        if f != 1:
            continue
        ids, stats = set(), []
        for g, entry in fields(plane):
            if g not in (4, 5):
                continue
            value = dict(fields(entry)).get(2, b"")
            if g == 5:
                meta = dict(fields(value))
                if _text(meta.get(2, b"")) == HLO_PROTO_STAT:
                    ids.add(meta.get(1, 0))
            else:
                stats += [dict(fields(v)) for h, v in fields(value) if h == 5]
        out += [s[6] for s in stats if s.get(1) in ids and 6 in s]
    return out


# HloProto.hlo_module 1; HloModuleProto: computations 3, entry id 6;
# HloComputationProto: instructions 2, id 5; HloInstructionProto: name 1,
# metadata 7 (OpMetadata.op_name 2), called_computation_ids 38.

def _module_op_names(hlo_proto) -> dict:
    """{instruction name: op_name} of one ``HloProto``: the computations
    walked from the entry, an instruction without an op_name taking that
    of the instruction that calls its computation."""
    module = dict(fields(hlo_proto)).get(1, b"")
    computations, entry = {}, None
    for f, v in fields(module):
        if f == 6:
            entry = v
        elif f == 3:
            cid, instructions = None, []
            for g, w in fields(v):
                if g == 5:
                    cid = w
                elif g == 2:
                    instructions.append(_instruction(w))
            computations[cid] = instructions
    out, caller_op, todo = {}, {entry: ""}, [entry]
    while todo:
        cid = todo.pop()
        for name, own, called in computations.get(cid, ()):
            op = own or caller_op[cid]
            if op:
                out[name] = op
            for c in called:
                if c not in caller_op:
                    caller_op[c] = op
                    todo.append(c)
    return out


def _instruction(buf) -> tuple:
    """(name, op_name, called computation ids) of one instruction."""
    name, op, called = "", "", []
    for f, v in fields(buf):
        if f == 1:
            name = _text(v)
        elif f == 7:
            op = _text(dict(fields(v)).get(2, b""))
        elif f == 38:                  # packed, or one id per field
            if isinstance(v, int):
                called.append(v)
            else:
                i = 0
                while i < len(v):
                    c, i = _varint(v, i)
                    called.append(c)
    return name, op, called


def op_names(xspace: bytes) -> dict:
    """{instruction name: op_name} of every program an xplane holds; a name
    two programs share keeps the larger program's."""
    out = {}
    for names in sorted(map(_module_op_names, hlo_protos(xspace)), key=len):
        out.update(names)
    return out


# ---------------------------------------------------------------------------
# Device time by scope
# ---------------------------------------------------------------------------

def scope_time(ops, scopes_of, lo: float, hi: float) -> dict:
    """{scope: seconds} of one device: for each scope that ``scopes_of``
    (instruction name -> scopes) gives a synchronous op, the union of those
    ops' intervals inside the window."""
    by_scope = collections.defaultdict(list)
    for o in ops:
        if trace_lib.is_leaf(o) and o.end_ns > lo and o.start_ns < hi:
            for name in scopes_of(o.name):
                by_scope[name].append((o.start_ns, o.end_ns))
    return {name: trace_lib.length(trace_lib.union(
        trace_lib.clip(iv, lo, hi))) / 1e9 for name, iv in by_scope.items()}


def partition(devices: dict, scopes_of, lo: float, hi: float) -> dict:
    """{innermost scope, or "unscoped": seconds}, the mean over devices: a
    partition of the busy time where scopes nest (each op counted under
    its innermost scope)."""
    out = collections.Counter()
    for ops in devices.values():
        inner = {}
        for o in ops:
            if trace_lib.is_leaf(o) and o.end_ns > lo and o.start_ns < hi:
                s = scopes_of(o.name)
                inner.setdefault(s[-1] if s else "unscoped", []).append(
                    (o.start_ns, o.end_ns))
        out.update({k: trace_lib.length(trace_lib.union(trace_lib.clip(
            iv, lo, hi))) / 1e9 / len(devices) for k, iv in inner.items()})
    return dict(out)


def window_xplane() -> str | None:
    """The xplane of the traced window that the running ``runner.run`` is
    reading; None outside such a call."""
    f = sys._getframe(1)
    while f is not None:
        if (f.f_code.co_name == "run"
                and f.f_globals.get("__name__", "").endswith("runner")):
            trace_dir = f.f_locals.get("trace_dir")
            return trace_lib.find_xplane(trace_dir) if trace_dir else None
        f = f.f_back
    return None


def _program_scopes():
    try:
        from repro.obs.device import scopes_of
    except ImportError:           # a program that names no device scopes
        return None
    return scopes_of


def reduce_xplane(path: str, log=None) -> list:
    """[{scope: seconds}] per device (in device-plane order) of one traced
    window; [] for a program without device scopes or a trace without
    op_name metadata."""
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    scopes_of = _program_scopes()
    if scopes_of is None:
        return []
    t0 = time.perf_counter()
    with open(path, "rb") as f:
        names = op_names(f.read())
    log(f"scopes: op_name of {len(names)} instructions read from the "
        f"trace in {time.perf_counter() - t0:.1f} s")
    if not names:
        return []
    memo = {}

    def of(name):
        if name not in memo:
            memo[name] = scopes_of(names.get(name, ""))
        return memo[name]

    devices, spans = trace_lib.load(path)
    lo, hi = trace_lib.window(spans)
    out = [scope_time(ops, of, lo, hi) for _, ops in sorted(devices.items())]
    _log_partition(devices, spans, of, lo, hi, log)
    log(f"scopes: window reduced by scope in "
        f"{time.perf_counter() - t0:.1f} s")
    return out


def _log_partition(devices, spans, of, lo, hi, log):
    """Where the device time went by scope, for the records: the time under
    each innermost scope, the scoped share of busy, the top unscoped ops,
    and the ops on either side of every idle gap over ``GAP_NS``."""
    parts = partition(devices, of, lo, hi)
    busy = sum(parts.values())
    log(f"scopes: device s by innermost scope (mean over devices) "
        f"{dict(sorted(parts.items()))}; busy {busy:.4f} s, scoped share "
        f"{1 - parts.get('unscoped', 0.0) / busy:.4%}" if busy else
        "scopes: no busy time")
    log("scopes: top unscoped ops " + str(trace_lib.top_ops(
        {k: [o for o in ops if not of(o.name)] for k, ops in devices.items()},
        lo, hi, 5)))
    for name, ops in sorted(devices.items()):
        leaves = [o for o in ops if trace_lib.is_leaf(o)]
        busy_iv = trace_lib.union([(o.start_ns, o.end_ns) for o in leaves])
        for s, e in trace_lib.gaps(busy_iv, lo, hi):
            if e - s < GAP_NS:
                continue
            before = max((o for o in leaves if o.end_ns <= s),
                         key=lambda o: o.end_ns, default=None)
            after = min((o for o in leaves if o.start_ns >= e),
                        key=lambda o: o.start_ns, default=None)
            host = max(spans, key=lambda h: min(e, h[1]) - max(s, h[0]))
            log(f"scopes: gap {name} {(e - s) / 1e6:.1f} ms ({host[2]}) "
                f"after {before and (before.name, of(before.name))}, "
                f"before {after and (after.name, of(after.name))}")


def seconds() -> list:
    """[{scope: seconds}] per device of the traced window being read."""
    path = window_xplane()
    if path is None:
        return []
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce_xplane(path)
    return _cache[path]


def ms_per_step(rec, scope: str):
    """Device time per traced step under ``scope``, the mean over devices,
    in ms; None where no op of the window is under it."""
    per_device = [by_scope.get(scope, 0.0) for by_scope in seconds()]
    if not any(per_device) or rec.steps_traced == 0:
        return None
    return sum(per_device) / len(per_device) / rec.steps_traced * 1e3
