"""The entry points' compile-cache helper: a fixed directory at the
repository root, or nothing at all when ``JAX_COMPILATION_CACHE_DIR`` is
set."""

import pathlib

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_is_fixed_at_repo_root(cache_dir_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.enable_compile_cache()
    root = pathlib.Path(__file__).resolve().parents[1]
    assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")


def test_env_cache_dir_is_left_to_jax(cache_dir_config, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None
