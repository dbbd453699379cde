"""Persistent XLA compilation cache at a fixed place.

Entry points call `enable_compile_cache()` before their first compile;
nothing calls it at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads the variable itself and this sets nothing.  Otherwise the cache
lives at ``<checkout>/.jax_cache``: the path is part of what a cache entry
is found by, so it never depends on a temporary name, a PID or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
