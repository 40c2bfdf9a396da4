"""Every name in BENCHMARK.json leads to its files, within the contract."""

import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_workload_names_files_that_exist(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs
        cell = spec.load_cell(w["name"])
        assert os.path.isfile(os.path.join(ROOT,
                                           configs[w["config"]]["file"]))
        assert cell.workload["entry"] in ("train_step", "pipelined_train_step")
        assert set(cell.workload["limits"]) == {"loss_gap", "grad_gap",
                                                "change_gap"}


def test_names_and_units_use_allowed_characters(bench):
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w["config"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    names += [m["name"] for m in metrics]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in bench[group]]
        assert len(seen) == len(set(seen))


def test_at_most_half_the_cells_take_four_chips(bench):
    cells = bench["workloads"]
    four = sum(w["chips"] == 4 for w in cells)
    assert all(w["chips"] in (1, 4) for w in cells)
    assert four <= max(1, len(cells) // 2)


def test_reduced_matches_the_cuts(bench):
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            c = json.load(f)
        assert entry["reduced"] == c["reduced"]
        assert set(c["reduced"]) == set(c["published"])
        for key, published in c["published"].items():
            assert c[key] != published, key


def test_every_metric_has_a_reader_and_every_family_its_files(bench):
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for w in bench["workloads"]:
        fam = spec.load_cell(w["name"]).family
        arch = spec.family_module("arch", fam)
        assert callable(arch.arch_config) and callable(arch.planner_profile)
        assert callable(arch.tiny)
        assert isinstance(arch.MAPPED, dict) and isinstance(arch.NEUTRAL,
                                                            dict)
        flops = spec.family_module("flops", fam)
        assert callable(flops.flops_per_token) and callable(flops.terms)
        assert isinstance(flops.KERNEL_SCOPES, dict)
        assert callable(spec.family_module("reference", fam).loss_and_grads)


def _run_bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    r = _run_bench(ROOT, "--workload", "qwen3-0.6b.train-s1k", "--seed",
                   str(2**31 + 3), "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not r.stdout.strip()


def test_run_without_the_program_exits_nonzero(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_bench(str(tmp_path), "--workload", "qwen3-0.6b.train-s1k",
                   "--seed", "1", "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert not r.stdout.strip()
