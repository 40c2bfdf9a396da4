"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic, entry and per-layer metrics are files under
``bench/`` found by name (``bench/harness/spec.py``). With ``--trace 0``
the result's metrics are the cell's end-to-end metrics; with ``--trace 1``
the window runs under the profiler and they are its per-layer metrics.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``) and, last, ``checks``: each number compared with its limit,
which also end standard error. Without a TPU, or with fewer chips than the
cell asks for, or on a device kind missing from ``bench/peaks.json``, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "src", "repro")):
        print("no program here: src/repro is missing", file=sys.stderr)
        return 2

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no /tmp/tpu_logs
    from harness import runner

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        result = runner.run(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, trace_dir=trace_dir,
            log=lambda s: print(s, file=sys.stderr, flush=True))
    except runner.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"correct {result['correct']}", file=sys.stderr)
    for n, v in result["checks"].items():
        print(f"check {n} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
