"""``use_compile_cache``: the environment's directory wins, else one fixed
directory inside the checkout."""
from __future__ import annotations

import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_leaves_config_alone(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_dir_without_env(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    second = compile_cache.use_compile_cache()
    assert first == second == compile_cache.REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == first
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert first == os.path.join(root, ".jax_cache")
