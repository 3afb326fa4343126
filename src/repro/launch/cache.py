"""Placement of JAX's persistent compilation cache for entry-point scripts.

Called by scripts (``chip_smoke.py``, ``benchmarks/run.py``), never on
library import: the tests must compile without a persistent cache.
"""
from __future__ import annotations

import os
from pathlib import Path

#: fixed, repository-relative cache directory: the path is part of what a
#: cache entry is found under, so it must not move between runs
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and no other path is set.  Otherwise the cache goes to
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
