"""Where JAX keeps its persistent compilation cache.

A path is part of the cache key, so the cache only pays off at a path that
never moves: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory. Sets nothing when ``JAX_COMPILATION_CACHE_DIR``
    is set. Call before the first compile of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
