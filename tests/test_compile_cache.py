"""Persistent compile-cache placement: JAX_COMPILATION_CACHE_DIR wins,
otherwise a fixed directory inside the checkout."""
import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_config():
    dir0 = jax.config.jax_compilation_cache_dir
    on0 = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_compilation_cache_dir", dir0)
    jax.config.update("jax_enable_compilation_cache", on0)


def test_env_dir_is_used_and_not_overridden(monkeypatch, tmp_path,
                                            restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_default_is_fixed_path_in_checkout(monkeypatch,
                                           restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path  # stable across calls
