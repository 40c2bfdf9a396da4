"""The reduced CPU rehearsal: whole runs of tiny cells, 1 and 4 devices.

The harness runs the program's own entries (``launch.steps`` on one device,
``pipeline.spmd`` over four virtual CPU devices) and the plain reference on
``tiny.py``'s twin of every cell of ``BENCHMARK.json``, and must come out
correct; then the timed path of the first twin of each kind is broken
underneath in each way a training cell can break, and ``correct`` must come
out false. Four devices need ``XLA_FLAGS`` before JAX starts, so those runs
are child processes on the CPU.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)
sys.path.insert(0, TESTS)

import tiny  # noqa: E402

SEED = 2**31 + 11
ONE_CHIP = [name for name, chips in tiny.twins() if chips == 1]
FOUR_CHIPS = [name for name, chips in tiny.twins() if chips == 4]


@pytest.fixture(autouse=True)
def _own_compile_cache(tmp_path, monkeypatch):
    """Each test compiles into its own cache, not the checkout's."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run_here(tmp_path, cell, seconds=0.5):
    from harness import runner

    root = tiny.make_root(str(tmp_path))
    return runner.run(cell, SEED, seconds, False, t_start=time.perf_counter(),
                      root=root, require_tpu=False, log=lambda s: None)


def _run_child(tmp_path, cell, patch: str = ""):
    """A whole run of ``cell`` on four virtual CPU devices, with ``patch``
    (Python source) applied first; returns the result object."""
    code = textwrap.dedent(f"""
        import json, sys, time
        sys.path[:0] = [{BENCH!r}, {TESTS!r}]
        import jax
        import tiny
        from harness import runner
    """) + textwrap.dedent(patch) + textwrap.dedent(f"""
        root = tiny.make_root({str(tmp_path)!r})
        r = runner.run({cell!r}, {SEED}, 0.5, False,
                       t_start=time.perf_counter(), root=root,
                       require_tpu=False, log=lambda s: None)
        print(json.dumps(r))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(BENCH), "src")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _assert_sound(r, chips):
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["count"] == chips
    assert set(r["metrics"]) == {"tokens_per_s", "mfu", "step_ms_p95",
                                 "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("twin", ONE_CHIP)
def test_one_device_run_is_correct(tmp_path, twin):
    _assert_sound(_run_here(tmp_path, twin), 1)


@pytest.mark.parametrize("twin", FOUR_CHIPS)
def test_four_device_pipeline_run_is_correct(tmp_path, twin):
    _assert_sound(_run_child(tmp_path, twin), 4)


# --- the timed path broken underneath --------------------------------------

def _break_step(monkeypatch, wrap):
    """Replace the program's step by ``wrap(step)`` wherever it is built."""
    from harness import programs

    real_build = programs.build

    def build(*a, **kw):
        prog = real_build(*a, **kw)
        prog.step = wrap(prog.step)
        return prog
    monkeypatch.setattr(programs, "build", build)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    _break_step(monkeypatch, lambda step: (
        lambda p, s, b: (p, s, step(p, s, b)[2])))
    r = _run_here(tmp_path, ONE_CHIP[0])
    assert not r["correct"]
    assert r["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    import jax

    _break_step(monkeypatch, lambda step: (
        lambda p, s, b: step(p, s, jax.tree.map(
            lambda x: x[: x.shape[0] // 2], b))))
    assert not _run_here(tmp_path, ONE_CHIP[0])["correct"]


def test_the_exchange_between_chips_left_out_is_not_correct(tmp_path):
    r = _run_child(tmp_path, FOUR_CHIPS[0], """
        jax.lax.ppermute = lambda x, axis_name, perm: x
    """)
    assert not r["correct"]


def test_the_lower_precision_control_is_not_correct(tmp_path, monkeypatch):
    """The reference in float8 in the program's place fails a limit."""
    from harness import runner

    def control(self, key, check_batches, prog=None):
        params, state, _ = real(self, key, check_batches, prog)
        return params, state, self.reference(key, check_batches,
                                             quant="fp8")
    real = runner.Harness.drive_check
    monkeypatch.setattr(runner.Harness, "drive_check", control)
    assert not _run_here(tmp_path, ONE_CHIP[0])["correct"]
