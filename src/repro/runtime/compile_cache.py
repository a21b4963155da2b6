"""JAX's persistent compilation cache for the repo's entry points.

Every entry point that starts JAX (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train``, ``repro.launch.serve``) calls
:func:`enable_compile_cache` before its first compile.  The cache key
includes the cache directory, so the directory is fixed: a path that moved
between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

# <repo>/src/repro/runtime/compile_cache.py -> <repo>/.jax_cache
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and JAX reads it
    itself, so nothing is set here.  Otherwise the cache goes to the
    fixed ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
