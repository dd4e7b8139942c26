"""JAX's persistent compilation cache, switched on by the entry points.

The cache's directory is part of its key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
it itself, and this module sets no other), else ``.jax_cache/`` at the
repository root.  Entry points call :func:`enable_compile_cache` once at
start-up; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "REPO_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory.

    Every compiled program is kept, however quick its compile: a kernel
    compiles in about a second, under JAX's default one-second floor.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
