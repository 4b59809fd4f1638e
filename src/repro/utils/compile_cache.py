"""JAX's persistent compilation cache for the repo's entry points."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored); this file is <checkout>/src/repro/utils/.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to one fixed directory
    inside the checkout: a cache that moves between runs is never hit.
    Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
    call this before their first compile; importing the library and the
    tests leave the cache alone.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
