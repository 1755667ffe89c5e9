"""JAX's persistent compilation cache for the entry points.

Called by ``chip_smoke.py`` and the launchers' ``__main__`` blocks, never at
import: tests and library callers keep JAX's own defaults.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache, from this file's place in <repo>/src/repro/launch/: a
# fixed path, since the path is part of what a cache entry is found by
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is chosen here; otherwise the cache goes to
    ``<repo>/.jax_cache``.  Every compile is kept, however short: the
    codec's many small kernels add up."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
