"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a run finds earlier compiles
only where the directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` where
it is set (JAX reads that itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: used where ``JAX_COMPILATION_CACHE_DIR`` is not set; git ignores it
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its dir."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
