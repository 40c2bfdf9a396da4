"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic; the files
that hold them sit under ``bench/`` and are found by those names alone:

- ``bench/configs/<config>.json``: the model's published config, as run;
- ``bench/traffic/<traffic>.json``: the parameters of the traffic mix;
- ``bench/workloads/<cell>.json``: how the cell drives the program (entry,
  micro-batches, optimizer) and the limits of its correctness check;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
- ``bench/arch/<family>.py``, ``bench/flops/<family>.py`` and
  ``bench/reference/<family>.py``: how a model family's published config
  maps onto the program, its operation count and its plain reference.

A configuration holds the harness's own keys (``HARNESS_KEYS``) and the
published ones; ``load_cell`` refuses one whose family neither maps nor
declares neutral a key of it, or that gives a neutral key another value.

Nothing here imports JAX.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

#: configuration keys of the harness, not of the published config:
#: ``torch_dtype`` is checked against the program in ``programs.py``
HARNESS_KEYS = frozenset({"source", "registry", "family", "deployment",
                          "reduced", "published", "cuts", "assumed",
                          "torch_dtype"})
#: the value of a family's ``NEUTRAL`` key that never changes the computation
ANY = "any"


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file ``path`` as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict          # the model's published config, as run
    arch: object          # bench/arch/<family>.py: config -> program
    traffic: dict         # the traffic mix's parameters
    workload: dict        # entry, micro-batches, optimizer, limits
    end_to_end: list      # the BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def batch(self) -> int:
        return int(self.traffic["batch"])

    @property
    def seq(self) -> int:
        return int(self.traffic["seq_len"])

    @property
    def family(self) -> str:
        return self.config["family"]


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    w = entries[name]
    bench_dir = os.path.join(root, "bench")
    config = _json(os.path.join(bench_dir, "configs", w["config"] + ".json"))
    arch = family_module("arch", config["family"], root)
    check_config(config, arch, w["config"])
    traffic = _json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    workload = _json(os.path.join(bench_dir, "workloads", name + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config, arch=arch,
        traffic=traffic, workload=workload,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def check_config(config: dict, arch, name: str) -> None:
    """Raise, naming every key, where ``config`` asks for what its family's
    ``arch`` module does not run: a key it neither maps nor declares
    neutral, a neutral key with another value, or a mapped key missing."""
    unknown = sorted(k for k in config if k not in HARNESS_KEYS
                     and k not in arch.MAPPED and k not in arch.NEUTRAL)
    changed = sorted(f"{k}={config[k]!r} (runs only as {v!r})"
                     for k, v in arch.NEUTRAL.items()
                     if v != ANY and k in config and config[k] != v)
    missing = sorted(k for k, needed in arch.MAPPED.items()
                     if needed and k not in config)
    faults = [f"{what}: {', '.join(keys)}" for what, keys in (
        ("keys the family neither maps nor declares neutral", unknown),
        ("neutral keys with another value", changed),
        ("mapped keys missing", missing)) if keys]
    if faults:
        raise ValueError(f"configuration {name!r} (family "
                         f"{config['family']!r}) is not run as it states; "
                         + "; ".join(faults))


def metric_reader(name: str, root: str = ROOT):
    """The ``read(record)`` function of per-layer metric ``name``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    return load_module(path, "bench_metric_" + name.replace(".", "_")).read


def family_module(kind: str, family: str, root: str = ROOT):
    """``bench/<kind>/<family>.py`` (kind: ``arch``, ``flops`` or
    ``reference``)."""
    path = os.path.join(root, "bench", kind, family + ".py")
    return load_module(path, f"bench_{kind}_{family}")


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    table = _json(os.path.join(root, "bench", "peaks.json"))
    kinds = table["devices"]
    if device_kind not in kinds:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (known: {sorted(kinds)})")
    return kinds[device_kind]
