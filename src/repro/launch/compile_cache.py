"""Persistent compilation cache placement for the repo's entry points.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``perfbench/run.py``)
call :func:`use_compile_cache` once at start-up; library modules set
nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and no directory is set here.  Otherwise the cache lives at one fixed
path inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the path
is part of what makes a cache entry reusable, so it is never built from
a temporary name, a pid or the time.

The UCT kernels and their jit wrappers compile in about a second or two,
under JAX's default one-second threshold for caching a program, so the
threshold is lowered to zero to keep them in the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
