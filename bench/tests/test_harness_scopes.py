"""Device time by the program's scopes (``harness/scopes.py``), and the
metrics that read it.

Checked here: the op_name map read from an xplane's bytes (a CPU trace of a
scoped program, and HLO messages made by hand for the inheritance rules),
``scope_time`` and ``partition`` on intervals made by hand with nested and
overlapping scopes, the lookup of the running harness's trace, and the
readers on made-up reductions. On the recorded chip excerpt with scopes
(``data/``), each scope's time lies within the busy time the harness's
own reduction gives, and the partition adds up to it.
"""

import glob
import json
import os
import sys
import types

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TESTS))

from harness import scopes, spec, trace  # noqa: E402
from harness.trace import Op  # noqa: E402

SCOPE_READERS = ("model.attention_ms", "model.head_loss_ms",
                 "step.optimizer_ms")


# --- the protobuf messages -------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A message of (field number, int | str | bytes) pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _instruction(name, op_name=None, called=()):
    fields = [(1, name)]
    if op_name is not None:
        fields.append((7, _msg((2, op_name))))
    if called:
        fields.append((38, b"".join(_varint(c) for c in called)))
    return _msg(*fields)


def _hlo_proto(computations, entry):
    """``computations``: {id: [instruction message]}."""
    module = _msg(*[(3, _msg((5, cid), *[(2, i) for i in insts]))
                    for cid, insts in computations.items()], (6, entry))
    return _msg((1, module))


def _xspace(*planes):
    """``planes``: (name, [(event name, [(stat name, kind, value)])])."""
    out = b""
    for name, events in planes:
        stat_ids, fields = {}, [(2, name)]
        for i, (event, stats) in enumerate(events, 1):
            msg_stats = []
            for stat, kind, value in stats:
                sid = stat_ids.setdefault(stat, len(stat_ids) + 1)
                msg_stats.append((5, _msg((1, sid), (kind, value))))
            fields.append((4, _msg((1, i), (2, _msg((1, i), (2, event),
                                                     *msg_stats)))))
        fields += [(5, _msg((1, sid), (2, _msg((1, sid), (2, stat)))))
                   for stat, sid in stat_ids.items()]
        out += _msg((1, _msg(*fields)))
    return out


def test_op_names_inherit_the_callers_and_keep_the_larger_program():
    step = _hlo_proto({
        1: [_instruction("fusion.1", "jit(s)/jvp(model.blocks)/while/body/"
                         "sin", called=[3]),
            _instruction("add.2"),                 # the loop's counter
            _instruction("tuple.3")],
        3: [_instruction("sine.4")],                # inside the fusion
        2: [_instruction("while.5", "jit(s)/jvp(model.blocks)/while",
                         called=[1, 4]),
            _instruction("copy.6"),
            _instruction("multiply.7", "jit(s)/step.optimizer/mul")],
        4: [_instruction("compare.8")],
    }, entry=2)
    small = _hlo_proto({1: [_instruction("multiply.7", "jit(loss)/mul"),
                            _instruction("other.9", "jit(loss)/neg")]},
                       entry=1)
    xspace = _xspace(
        ("/host:metadata", [("jit_loss(2)", [("Hlo Proto", 6, small)]),
                            ("jit_s(1)", [("Hlo Proto", 6, step)])]),
        ("/device:TPU:0", [("%fusion.1 = f32[4]{0} fusion(...)",
                            [("hlo_category", 5, "loop fusion")])]))
    names = scopes.op_names(xspace)
    assert names["fusion.1"] == names["sine.4"] == \
        "jit(s)/jvp(model.blocks)/while/body/sin"
    assert names["add.2"] == names["tuple.3"] == names["compare.8"] == \
        "jit(s)/jvp(model.blocks)/while"
    assert names["multiply.7"] == "jit(s)/step.optimizer/mul"
    assert names["other.9"] == "jit(loss)/neg"
    assert "copy.6" not in names and len(names) == 8
    assert scopes.op_names(_xspace(("/host:CPU", []))) == {}


def test_op_names_of_a_traced_cpu_program(tmp_path):
    """The metadata plane of a real trace gives each instruction's scopes,
    a scan's bookkeeping under the scope around the scan."""
    import jax
    import jax.numpy as jnp
    from repro.obs import device

    def f(x):
        with device.scope("model.blocks"):
            y, _ = jax.lax.scan(lambda c, _: (jnp.sin(c @ c), None), x,
                                None, length=3)
        with device.scope("step.optimizer"):
            return (y * 2).sum()

    g = jax.jit(jax.grad(f))
    x = jnp.ones((16, 16))
    g(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        g(x).block_until_ready()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        names = scopes.op_names(fh.read())
    found = {s for op in names.values() for s in device.scopes_of(op)}
    assert found == {"model.blocks", "step.optimizer"}
    loops = [n for n, op in names.items() if op.endswith("/while")]
    assert loops and all(device.scopes_of(names[n]) == ("model.blocks",)
                         for n in loops)


# --- device time by scope --------------------------------------------------

OPS = [Op(0, 10, "a", "fusion:kLoop"),
       Op(10, 20, "b", "fusion:kOutput"),
       Op(15, 25, "c", "fusion:kOutput"),            # overlaps b
       Op(30, 40, "d", "fusion:kLoop"),
       Op(40, 45, "e", "copy"),
       Op(0, 50, "while.1", "while"),
       Op(5, 35, "cp", "collective-permute-start", True)]
SCOPES = {"a": ("model.blocks",), "b": ("model.blocks", "model.attention"),
          "c": ("model.blocks", "model.attention"), "d": ("step.optimizer",),
          "while.1": ("model.blocks",), "cp": ("pipe.ticks",)}


def _of(name):
    return SCOPES.get(name, ())


def test_scope_time_is_the_union_of_each_scopes_ops():
    got = scopes.scope_time(OPS, _of, 0, 50)
    assert got == pytest.approx({"model.blocks": 25e-9,
                                 "model.attention": 15e-9,
                                 "step.optimizer": 10e-9})
    # clipped to the window; control flow and asynchronous parts excluded
    assert scopes.scope_time(OPS, _of, 12, 35) == pytest.approx(
        {"model.blocks": 13e-9, "model.attention": 13e-9,
         "step.optimizer": 5e-9})


def test_partition_counts_each_op_under_its_innermost_scope():
    got = scopes.partition({"d0": OPS, "d1": OPS[:2]}, _of, 0, 50)
    assert got == pytest.approx({"model.blocks": (10 + 10) / 2 * 1e-9,
                                 "model.attention": (15 + 10) / 2 * 1e-9,
                                 "step.optimizer": 10 / 2 * 1e-9,
                                 "unscoped": 5 / 2 * 1e-9})


def test_a_program_without_device_scopes_reads_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "_program_scopes", lambda: None)
    assert scopes.reduce_xplane("no such file") == []


def test_the_trace_is_the_running_harness_runs(tmp_path):
    """``window_xplane`` finds the ``trace_dir`` of a ``run`` of a module
    named ``...runner`` up the stack, and nothing elsewhere."""
    xplane = tmp_path / "plugins" / "profile" / "1" / "h.xplane.pb"
    xplane.parent.mkdir(parents=True)
    xplane.write_bytes(b"")
    runner = types.ModuleType("harness_fake.runner")
    exec("def run(trace_dir, read):\n    return read()\n", runner.__dict__)
    assert runner.run(str(tmp_path), scopes.window_xplane) == str(xplane)
    assert runner.run(None, scopes.window_xplane) is None
    assert scopes.window_xplane() is None


def _record(steps=2):
    return types.SimpleNamespace(steps_traced=steps)


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_scope_readers_are_silent_without_their_scope(name, monkeypatch):
    read = spec.metric_reader(name)
    for per_device in ([], [{}, {}], [{"model.blocks": 1.0}]):
        monkeypatch.setattr(scopes, "seconds", lambda: per_device)
        assert read(_record()) is None
    assert read(_record(steps=0)) is None
    monkeypatch.undo()
    assert read(_record()) is None              # no traced run up the stack


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_scope_readers_give_ms_per_step_mean_over_devices(name, monkeypatch):
    scope = spec.load_module(
        os.path.join(os.path.dirname(TESTS), "metrics", name + ".py"),
        "scope_reader_" + name.replace(".", "_")).SCOPE
    monkeypatch.setattr(scopes, "seconds", lambda: [
        {scope: 0.30, "model.blocks": 9.0}, {scope: 0.10}])
    assert spec.metric_reader(name)(_record(steps=4)) == pytest.approx(
        (0.30 + 0.10) / 2 / 4 * 1e3)


def _scoped_excerpts():
    out = []
    for path in sorted(glob.glob(os.path.join(TESTS, "data", "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if "scopes" in rec:
            out.append((os.path.basename(path), rec))
    return out


@pytest.mark.parametrize("rec", [r for _, r in _scoped_excerpts()],
                         ids=[n for n, _ in _scoped_excerpts()])
def test_a_recorded_excerpts_scopes_partition_its_busy_time(rec):
    """On the chip excerpt recorded with each instruction's scopes: every
    scope's time lies within the busy time of the harness's reduction, the
    partition adds up to that busy time, and nearly all of it is scoped."""
    lo, hi = rec["window"]
    devices = {n: [Op(*o) for o in ops] for n, ops in rec["devices"].items()}
    names = {k: tuple(v) for k, v in rec["scopes"].items()}

    def of(name):
        return names.get(name, ())

    busy = {n: trace.device_time(n, ops, lo, hi).busy_s
            for n, ops in devices.items()}
    for n, ops in devices.items():
        by_scope = scopes.scope_time(ops, of, lo, hi)
        assert by_scope and all(0 < v <= busy[n] + 1e-12
                                for v in by_scope.values())
    parts = scopes.partition(devices, of, lo, hi)
    assert sum(parts.values()) == pytest.approx(
        sum(busy.values()) / len(busy))
    assert parts.get("unscoped", 0.0) < 0.05 * sum(parts.values())
