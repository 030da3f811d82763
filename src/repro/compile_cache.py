"""JAX's persistent compilation cache, placed from outside the program.

Entry points that compile for the device call :func:`enable_compile_cache`
before their first compile: ``chip_smoke.py``, ``python -m
repro.launch.market_sim`` and ``python -m benchmarks.run``.

* If ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and
  nowhere else.
* Otherwise the cache lives at one fixed directory inside the checkout,
  ``<repo>/.jax_cache``.  The path is part of what a cache entry is found
  by, so it never depends on a temp directory, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # the simulator's programs compile in well under JAX's default 1 s
    # threshold; keep them all, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
