"""JAX's persistent compilation cache for the launchers.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache``: the directory is part of each entry's key, so a
path built from a temporary name, a pid or a time would never hit again.
Only entry points call :func:`enable_compile_cache`; tests leave it off.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
