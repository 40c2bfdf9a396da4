"""The reduction from a device trace to busy, idle, matmul and collective time.

Checked on intervals made by hand and on excerpts of traces recorded on
TPU v5e chips (``data/``): each device plane's operations and the host
spans, as ``trace.load`` returns them, over the excerpt's window.
"""

import glob
import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TESTS))

from harness import trace  # noqa: E402
from harness.trace import Op  # noqa: E402


def test_busy_is_the_union_of_overlapping_intervals():
    merged = trace.union([(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)])
    assert merged == [(0, 15), (20, 31)]
    assert trace.length(merged) == 26


def test_idle_is_the_window_less_busy():
    busy = trace.union([(2, 4), (3, 6), (8, 9)])
    assert trace.gaps(busy, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    d = trace.device_time("d", [Op(2, 4, "a", ""), Op(3, 6, "b", ""),
                                Op(8, 9, "c", "")], 0, 10)
    assert d.busy_s * 1e9 == pytest.approx(5)
    assert 1 - d.busy_s * 1e9 / 10 == pytest.approx(0.5)


def test_busy_is_clipped_to_the_window():
    d = trace.device_time("d", [Op(-5, 5, "a", ""), Op(8, 20, "b", "")],
                          0, 10)
    assert d.busy_s * 1e9 == pytest.approx(7)


def test_exposed_collective_time_leaves_out_overlap_with_compute():
    ops = [Op(0, 10, "collective-permute-start.1", "collective-permute-start",
              True),
           Op(4, 6, "fusion.3", "fusion:kLoop"),
           Op(8, 14, "all-reduce.2", "all-reduce"),
           Op(12, 13, "fusion.7", "fusion:kOutput"),
           Op(0, 20, "while.1", "while")]
    d = trace.device_time("d", ops, 0, 20)
    assert d.collective_s * 1e9 == pytest.approx(14)
    # collectives cover [0, 14); compute covers [4, 6) and [12, 13); the
    # loop around them and the asynchronous transfer are not busy time
    assert d.collective_exposed_s * 1e9 == pytest.approx(11)
    assert d.matmul_s * 1e9 == pytest.approx(1)
    assert d.busy_s * 1e9 == pytest.approx(2 + 6)


def test_hlo_text_gives_name_and_category():
    assert trace.parse_hlo(
        "%fusion.524.remat = (f32[2,16]{1,0:T(8,128)}, f32[2]{0}) "
        "fusion(pred[4]{0} %x), kind=kOutput, calls=%f.1") == \
        ("fusion.524.remat", "fusion:kOutput")
    assert trace.parse_hlo("%while.1 = (s32[], f32[2]{0:T(2)}) while((s32[], "
                           "f32[2]) %t), condition=%c, body=%b") == \
        ("while.1", "while")
    assert trace.parse_hlo("%copy.3 = bf16[8]{0:T(8)(2,1)} copy(bf16[8] %a)"
                           ) == ("copy.3", "copy")
    assert trace.parse_hlo("jit_step(123)") == ("jit_step(123)", "")


def test_subtract_walks_several_covers():
    assert trace.subtract([(0, 10), (12, 20)], [(1, 2), (3, 4), (9, 13)]) \
        == [(0, 1), (2, 3), (4, 9), (13, 20)]


def test_gaps_are_labelled_by_the_host_span_that_covers_them():
    devices = {"/device:TPU:0": [Op(0, 10, "a", ""), Op(30, 40, "b", "")]}
    spans = [(0, 12, "bench.dispatch"), (12, 31, "bench.h2d")]
    assert trace.label_gaps(devices, spans, 0, 40) == [["bench.h2d", 20e-9]]


def _sweep(intervals, lo, hi):
    """Length of the union by a sweep over start and end events: a second
    algorithm for what ``trace.union`` computes."""
    events = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                    + [(min(e, hi), -1) for s, e in intervals
                       if e > lo and s < hi])
    depth, last, total = 0, None, 0.0
    for t, d in events:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def _recorded():
    return sorted(glob.glob(os.path.join(TESTS, "data", "*.json")))


@pytest.mark.parametrize("path", _recorded(),
                         ids=lambda p: os.path.basename(p))
def test_recorded_chip_trace(path):
    """On an excerpt of a trace from the chip: busy is the union of the op
    intervals, idle its complement, exposed collective time the collective
    union less the others' union (each checked by a sweep)."""
    with open(path) as f:
        rec = json.load(f)
    spans = [tuple(s) for s in rec["host_spans"]]
    lo, hi = rec["window"]
    assert rec["devices"]
    for name, ops in rec["devices"].items():
        ops = [Op(*o) for o in ops]
        d = trace.device_time(name, ops, lo, hi)
        iv = [(o.start_ns, o.end_ns) for o in ops if trace.is_leaf(o)]
        busy = _sweep(iv, lo, hi)
        assert d.busy_s * 1e9 == pytest.approx(busy)
        assert 0 < busy < hi - lo
        coll = [(o.start_ns, o.end_ns) for o in ops if trace.is_collective(o)]
        other = [(o.start_ns, o.end_ns) for o in ops
                 if trace.is_leaf(o) and not trace.is_collective(o)]
        exposed = _sweep(coll + other, lo, hi) - _sweep(other, lo, hi)
        assert d.collective_exposed_s * 1e9 == pytest.approx(exposed, abs=1)
        assert 0 < d.matmul_s <= d.busy_s
        assert trace.label_gaps({name: ops}, spans, lo, hi)
