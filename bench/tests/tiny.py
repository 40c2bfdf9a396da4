"""Tiny stand-ins for the benchmark's cells, for tests on the CPU.

``make_root(tmp)`` writes a checkout-shaped directory with a
``BENCHMARK.json`` of tiny cells (the published configurations' keys at toy
widths and depths), their traffic and workload files, a peak table that
knows the CPU, and links to the real ``bench/metrics``, ``bench/flops`` and
``bench/reference``. The harness then runs these cells as it runs the real
ones.
"""

from __future__ import annotations

import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY = {"num_hidden_layers": 4, "hidden_size": 64, "num_attention_heads": 4,
        "intermediate_size": 128, "head_dim": 16, "vocab_size": 256}

#: cell name -> (config, traffic (batch, seq), workload overrides, chips)
CELLS = {
    "tiny-q3.train": ("qwen3-0.6b", (4, 32),
                      {"entry": "train_step", "microbatches": 2}, 1),
    "tiny-q15.pipe4": ("qwen1.5-4b", (4, 32),
                       {"entry": "pipelined_train_step", "stages": 4,
                        "microbatches": 2}, 4),
}

#: limits set from readings at these toy sizes on the CPU: the program
#: read at most 6.5e-5 / 1.9e-3 / 2.2e-2 over a few seeds, the float8
#: control at least 2.0e-4 / 1.4e-2 / 5.7e-3
LIMITS = {"loss_gap": 2e-4, "grad_gap": 5e-3, "change_gap": 0.05}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        c = json.load(f)
    c = copy.deepcopy(c)
    c.update(TINY)
    if c["num_key_value_heads"] != c["num_attention_heads"] or \
            c["model_type"] == "qwen3":
        c["num_key_value_heads"] = 2
    else:
        c["num_key_value_heads"] = TINY["num_attention_heads"]
    return c


def make_root(tmp: str, limits: dict | None = None) -> str:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["workloads"], configs = [], {}
    for cell, (config, (b, s), wl, chips) in CELLS.items():
        traffic = f"tiny-b{b}-s{s}"
        bench["workloads"].append({"name": cell, "config": "tiny-" + config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "tiny"})
        configs[config] = True
        _write(os.path.join(tmp, "bench", "traffic", traffic + ".json"),
               {"generator": "planted_bigram", "batch": b, "seq_len": s,
                "bigram_rank": 16, "choices": 4, "follow_prob": 0.75,
                "pool": 2})
        real = json.load(open(os.path.join(BENCH, "workloads", {
            "train_step": "qwen3-0.6b.train-s1k",
            "pipelined_train_step": "qwen1.5-4b.pipe4-s1k"}[wl["entry"]]
            + ".json")))
        real.update(wl, limits=dict(limits or LIMITS))
        _write(os.path.join(tmp, "bench", "workloads", cell + ".json"), real)
    for config in configs:
        _write(os.path.join(tmp, "bench", "configs", f"tiny-{config}.json"),
               tiny_config(config))
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    _write(os.path.join(tmp, "BENCHMARK.json"), bench)
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    _write(os.path.join(tmp, "bench", "peaks.json"), peaks)
    for d in ("metrics", "flops", "reference"):
        os.symlink(os.path.join(BENCH, d), os.path.join(tmp, "bench", d))
    return tmp
