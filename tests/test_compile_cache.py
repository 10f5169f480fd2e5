"""Where the entry points put JAX's persistent compilation cache."""

import os

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_ROOT, enable_compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_dir_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == os.path.join(CHECKOUT_ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path           # the same on every call
    assert os.path.isfile(os.path.join(CHECKOUT_ROOT, "pyproject.toml"))
    with open(os.path.join(CHECKOUT_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
