"""JAX's persistent compilation cache for the entry points.

The cache key includes its directory, so the directory is fixed: a path
built from a temporary name, a pid or the time would never hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache (listed in .gitignore)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here. Otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
