"""Where JAX keeps its persistent compilation cache.

A cold start compiles the whole unrolled decode step; the persistent cache
lets the next process on the same machine load it instead.  The cache key
includes the directory, so the directory must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point the persistent compilation cache at its directory and return it.

    Call before the first compile.  If ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing is set here.  Otherwise the cache goes
    to ``.jax_cache`` at the root of the checkout (git-ignored)."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
