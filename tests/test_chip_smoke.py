"""``chip_smoke.py`` on the CPU: its phases at reduced size, and its refusal
to run without a TPU.

The phases are the script's own functions with a reduced config (the chip
runs them at published widths); the four-chip phase runs in a child on 4
fake CPU devices, since the main pytest process keeps 1 device.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _child_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
    return env


def test_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, cwd=REPO,
                       env=_child_env(), timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_planner_plans_one_chip(smoke):
    sp, pcfg = smoke.planner_phase(1, smoke.TRAIN_BATCH)
    assert sp.num_stages == 1 and pcfg.num_stages == 1
    assert smoke.TRAIN_BATCH % pcfg.num_microbatches == 0
    assert 0.0 <= sp.bubble_fraction < 1.0


def test_trainer_phase_reduced(smoke):
    _, pcfg = smoke.planner_phase(1, smoke.TRAIN_BATCH)
    losses = smoke.trainer_phase(pcfg.num_microbatches, reduced=True,
                                 seq=128, steps=3)
    assert len(losses) == 3


def test_paper_phase_interpreted(smoke):
    losses = smoke.paper_phase(3)
    assert len(losses) == 3


def test_four_chip_phase_on_fake_devices(smoke):
    code = textwrap.dedent("""
        import dataclasses, importlib.util, json
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      "chip_smoke.py")
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.configs import get_config
        cfg = dataclasses.replace(get_config("qwen3-0.6b", reduced=True),
                                  num_layers=8)
        print(json.dumps(smoke.four_chip_phase(cfg, batch=8, seq=64,
                                               steps=2)))
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=600,
        env=_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["grad_rel"] <= smoke.GRAD_RTOL
    assert len(result["losses"]) == 2

