"""Persistent XLA compile cache at a path that can be placed from outside.

Every entry point that compiles (chip_smoke.py, the bench scripts,
serving/fleet_worker.py) calls :func:`enable_compile_cache` before its
first compile.  The directory is part of the cache key, so it must not
move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads
it itself and nothing is set in code; otherwise the cache is
``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Returns the directory the cache lives in."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
