"""JAX persistent compilation cache for the entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
sets nothing. Otherwise the cache goes to `<checkout>/.jax_cache`, a fixed
path (the path is part of the cache key, so a moving directory never
hits) that `.gitignore` lists. Entry points call this from `main`, never at
import, so importing them from tests writes no cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
