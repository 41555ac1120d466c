"""JAX's persistent compilation cache, in one place per run.

The cache key includes the directory, so a cache that moves between runs
never hits.  :func:`enable_compile_cache` therefore places it at exactly
one of two fixed paths: the ``JAX_COMPILATION_CACHE_DIR`` a deployment
sets (JAX reads that variable itself, so nothing is set in code), or
``<checkout>/.jax_cache`` (git-ignored).  Entry points call it before
their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    outside = os.environ.get(ENV_VAR)
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
