"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``) call :func:`enable_compile_cache` from ``main()``;
nothing calls it at import.  ``JAX_COMPILATION_CACHE_DIR``, where set, is
the cache and no other directory is configured.  Otherwise the cache is one
fixed directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored):
the path is part of the cache key, so it never varies with a temp name, a
pid or the time.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout-local cache: <repo>/.jax_cache (this file is
#: <repo>/src/repro/launch/cache.py)
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV_VAR) or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
