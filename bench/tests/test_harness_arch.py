"""A family's mapping from published config to program (``bench/arch``).

The dense mapping gives the program the same ``ArchConfig`` and the planner
the same profile for both accepted configurations as before it moved out of
the harness; ``spec.load_cell`` refuses, by name and before JAX starts, a
configuration that holds keys its family does not run; and a family that is
new files only runs ``correct`` through ``runner.run``.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)
sys.path.insert(0, TESTS)

import tiny  # noqa: E402
from harness import spec  # noqa: E402

ARCH = spec.family_module("arch", "dense_decoder")

#: the ArchConfig each accepted configuration ran with before the mapping
#: moved into bench/arch: layers, d, heads, kv, head_dim, d_ff, vocab, tie,
#: bias, qk_norm, rope_theta, eps, window
BEFORE = {
    "qwen3-0.6b": (28, 1024, 16, 8, 128, 3072, 151936, True, False, True,
                   1e6, 1e-6, 0),
    "qwen1.5-4b": (20, 2560, 20, 20, 128, 6912, 37984, False, True, False,
                   5e6, 1e-6, 0),
}
FIELDS = ("num_layers", "d_model", "n_heads", "n_kv", "head_dim", "d_ff",
          "vocab", "tie_embeddings", "qkv_bias", "qk_norm", "rope_theta",
          "norm_eps", "sliding_window")


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_arch_config_is_unchanged(name):
    from repro.configs import get_config

    c = _config(name)
    cfg = ARCH.arch_config(c)
    assert tuple(getattr(cfg, f) for f in FIELDS) == BEFORE[name]
    assert cfg.ffn_mult == 3
    base = get_config(c["registry"])
    moved = set(FIELDS) - {"head_dim"} | {"d_head", "ffn_mult"}
    for f in dataclasses.fields(cfg):
        if f.name not in moved:
            assert getattr(cfg, f.name) == getattr(base, f.name), f.name


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_planner_profile_is_unchanged(name):
    from repro.core.profiles import transformer_profile

    layers, d, h, kv, hd, ff, vocab = BEFORE[name][:7]
    c = _config(name)
    want = transformer_profile(c["registry"], layers, d, h, kv, ff, vocab,
                               1024, d_head=hd)
    got = ARCH.planner_profile(c, 1024)
    assert got.name == want.name
    for f in dataclasses.fields(want):
        if f.name != "name":
            np.testing.assert_array_equal(getattr(got, f.name),
                                          getattr(want, f.name), f.name)


# --- keys the family does not run ------------------------------------------

def _root_with(tmp_path, config: dict) -> tuple:
    """A tiny root whose first one-chip twin runs ``config``; returns
    (root, that twin's name)."""
    root = tiny.make_root(str(tmp_path))
    bench = spec.benchmark(root)
    w = next(w for w in bench["workloads"] if w["chips"] == 1)
    path = os.path.join(root, "bench", "configs", w["config"] + ".json")
    with open(path, "w") as f:
        json.dump(config, f)
    return root, w["name"]


TRINITY_MINI = {"moe_intermediate_size": 1024, "num_experts": 128,
                "num_shared_experts": 1, "global_attn_every_n_layers": 4}


@pytest.mark.parametrize("change,named", [
    (TRINITY_MINI, sorted(TRINITY_MINI)),
    ({"sliding_window": 1024, "use_sliding_window": True},
     ["use_sliding_window"]),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0,
                       "original_max_position_embeddings": 32768}},
     ["rope_scaling"]),
    ({"hidden_act": "gelu"}, ["hidden_act"]),
    ({"rope_theta": None}, ["rope_theta"]),
], ids=["trinity-mini-keys", "sliding-window", "rope-scaling", "gelu",
        "no-rope-theta"])
def test_a_config_its_family_does_not_run_is_refused(tmp_path, change,
                                                     named):
    c = _config("qwen3-0.6b")
    c.update(change)
    c = {k: v for k, v in c.items() if not (k in change and v is None)}
    root, cell = _root_with(tmp_path, c)
    with pytest.raises(ValueError) as e:
        spec.load_cell(cell, root)
    for key in named:
        assert key in str(e.value)


def test_the_accepted_configs_pass_and_the_refusal_comes_before_jax(
        tmp_path):
    for w in spec.benchmark()["workloads"]:
        spec.load_cell(w["name"])
    c = dict(_config("qwen3-0.6b"), **TRINITY_MINI)
    root, cell = _root_with(tmp_path, c)
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {BENCH!r})
        from harness import spec
        try:
            spec.load_cell({cell!r}, {root!r})
        except ValueError as e:
            print("refused" if "num_experts" in str(e) else e)
        print("jax" in sys.modules)
    """)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.stdout.split() == ["refused", "False"], p.stderr[-2000:]


def test_a_model_type_outside_the_family_is_refused():
    with pytest.raises(ValueError, match="model_type 'llama'"):
        ARCH.arch_config(dict(_config("qwen3-0.6b"), model_type="llama"))


@pytest.mark.parametrize("name,kv", [("qwen3-0.6b", 2), ("qwen1.5-4b", 4)])
def test_tiny_keeps_the_attention_kind(name, kv):
    t = ARCH.tiny(_config(name))
    assert t["num_key_value_heads"] == kv
    assert t["num_hidden_layers"] == 4 and t["hidden_size"] == 64


def test_the_harness_names_no_key_of_a_family():
    """Only the traffic's ``vocab_size`` is read by the harness itself."""
    harness = os.path.join(BENCH, "harness")
    source = "".join(open(os.path.join(harness, f)).read()
                     for f in os.listdir(harness) if f.endswith(".py"))
    for f in os.listdir(os.path.join(BENCH, "arch")):
        if f.endswith(".py"):
            arch = spec.family_module("arch", f[:-3])
            keys = (set(arch.MAPPED) | set(arch.NEUTRAL)) - {"vocab_size"}
            assert not [k for k in keys if f'"{k}"' in source]


# --- a new family is new files only ----------------------------------------

#: a family module that is ``dense_decoder``'s of the same kind, re-exported
REEXPORT = '''
import os
from harness.spec import load_module

_base = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "dense_decoder.py"), __name__ + "_base")
globals().update({k: v for k, v in vars(_base).items()
                  if not k.startswith("__")})
'''


def _checkout_files():
    out = {}
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            out[path] = os.stat(path).st_mtime_ns
    return out


def test_a_new_family_is_new_files_only(tmp_path, monkeypatch):
    from harness import runner

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = _checkout_files()
    root, cell = _root_with(tmp_path / "root", dict(
        tiny.tiny_config("qwen3-0.6b"), family="dense_copy"))
    for kind in ("arch", "flops", "reference"):
        with open(os.path.join(root, "bench", kind, "dense_copy.py"),
                  "w") as f:
            f.write(REEXPORT)
    assert spec.load_cell(cell, root).family == "dense_copy"
    r = runner.run(cell, 2**31 + 17, 0.5, False, t_start=time.perf_counter(),
                   root=root, require_tpu=False, log=lambda s: None)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert _checkout_files() == before
