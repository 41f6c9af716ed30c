"""Where JAX keeps its persistent compilation cache.

Call ``enable_compile_cache()`` from an entry point's ``main()``, never at
import.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing else is set.  Otherwise the cache goes to a fixed directory inside
the checkout: the path is part of the cache key, so a directory built from a
temp name, pid or time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
