"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` once at start-up; library
modules never turn the cache on at import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing here
  overrides it.
* Otherwise the cache goes to ``.jax_cache/`` at the checkout root. The path
  is fixed because it is part of each entry's key: a cache that moves never
  hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
