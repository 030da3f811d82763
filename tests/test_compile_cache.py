"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, else to one fixed directory inside the checkout."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture(autouse=True)
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_env_dir_holds_the_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3.0 + 0.25)(jnp.arange(7.0)).block_until_ready()
    assert any(tmp_path.iterdir())


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = Path(__file__).resolve().parents[1]
    assert REPO_CACHE_DIR == repo / ".jax_cache"
    assert enable_compile_cache() == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
