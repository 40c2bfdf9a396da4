"""The persistent compilation cache lives where one helper says."""

import importlib
from pathlib import Path

import jax
import pytest

from repro.launch import cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_names_the_cache(monkeypatch, tmp_path, restore_cache_dir):
    want = str(tmp_path / "jax-cc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_default_cache_is_one_fixed_path_in_checkout(monkeypatch,
                                                     restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = cache.enable_compile_cache()
    second = cache.enable_compile_cache()
    assert first == second == jax.config.jax_compilation_cache_dir
    assert Path(first) == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_entry_points_leave_the_cache_alone_at_import(restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    for mod in ("repro.launch.train", "repro.launch.serve"):
        importlib.import_module(mod)
    assert jax.config.jax_compilation_cache_dir == before
