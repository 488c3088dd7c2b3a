"""JAX's persistent compilation cache, kept at one fixed directory.

A cold process compiles every ACK tile shape and every batched pass it
meets; the persistent cache lets the next process on the same machine
load them instead.  JAX keys cache entries by the directory too, so the
directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when
it is set (JAX reads that variable itself), else ``.jax_cache/`` at the
repository root.  Entry points call :func:`enable_compile_cache` before
their first compile; the library never turns the cache on by itself.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and cache every executable (tile kernels compile in well under
    JAX's default one-second admission threshold).  Returns the
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
