"""Tiny twins of the benchmark's cells, for tests on the CPU.

``make_root(tmp)`` writes a checkout-shaped directory whose
``BENCHMARK.json`` holds one twin of every cell of the real one, named
``tiny-<cell>``: the same entry, stages and chips, the cell's configuration
shrunk by its family's ``tiny(c)`` (``bench/arch/<family>.py``), a tiny
planted-bigram traffic, two micro-batches and limits set at these sizes.
Beside them: a peak table that knows the CPU, and directories ``metrics``,
``arch``, ``flops`` and ``reference`` whose files link to the checkout's, so
that a test can add a family's own files there. The harness then runs the
twins as it runs the real cells, and a cell added later is rehearsed with
no edit here.
"""

from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

#: every twin's traffic and micro-batches
TRAFFIC = {"generator": "planted_bigram", "batch": 4, "seq_len": 32,
           "bigram_rank": 16, "choices": 4, "follow_prob": 0.75, "pool": 2}
MICROBATCHES = 2

#: limits set from readings at these toy sizes on the CPU: the program
#: read at most 6.5e-5 / 1.9e-3 / 2.2e-2 over a few seeds, the float8
#: control at least 2.0e-4 / 1.4e-2 / 5.7e-3
LIMITS = {"loss_gap": 2e-4, "grad_gap": 5e-3, "change_gap": 0.05}

#: the directories of family and metric files that the twins use
LINKED = ("metrics", "arch", "flops", "reference")


def _read(path):
    with open(path) as f:
        return json.load(f)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def twins() -> list:
    """(twin name, chips) of every cell of ``BENCHMARK.json``, in order."""
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    return [("tiny-" + w["name"], w["chips"]) for w in bench["workloads"]]


def tiny_config(name: str) -> dict:
    """Configuration ``name`` at its family's toy widths and depth."""
    from harness import spec

    c = _read(os.path.join(BENCH, "configs", name + ".json"))
    return spec.family_module("arch", c["family"]).tiny(c)


def make_root(tmp: str, limits: dict | None = None) -> str:
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    traffic = "tiny-b{batch}-s{seq_len}".format(**TRAFFIC)
    _write(os.path.join(tmp, "bench", "traffic", traffic + ".json"), TRAFFIC)
    cells, configs = [], set()
    for w in bench["workloads"]:
        twin = "tiny-" + w["name"]
        cells.append(dict(w, name=twin, config="tiny-" + w["config"],
                          traffic=traffic, why="tiny"))
        configs.add(w["config"])
        workload = _read(os.path.join(BENCH, "workloads",
                                      w["name"] + ".json"))
        workload.update(microbatches=MICROBATCHES,
                        limits=dict(limits or LIMITS))
        _write(os.path.join(tmp, "bench", "workloads", twin + ".json"),
               workload)
    for config in configs:
        _write(os.path.join(tmp, "bench", "configs", f"tiny-{config}.json"),
               tiny_config(config))
    bench["workloads"] = cells
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    _write(os.path.join(tmp, "BENCHMARK.json"), bench)
    peaks = _read(os.path.join(BENCH, "peaks.json"))
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    _write(os.path.join(tmp, "bench", "peaks.json"), peaks)
    for d in LINKED:
        os.makedirs(os.path.join(tmp, "bench", d))
        for f in os.listdir(os.path.join(BENCH, d)):
            if f.endswith(".py"):
                os.symlink(os.path.join(BENCH, d, f),
                           os.path.join(tmp, "bench", d, f))
    return tmp
