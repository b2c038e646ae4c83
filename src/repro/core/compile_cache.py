"""JAX's persistent compilation cache, placed for the repository's programs.

A compiled TPU plan takes seconds to minutes to build, so the programs that
run on the chip (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
keep XLA's output between processes. The cache key includes its directory,
so the directory is fixed: ``<repo>/.jax_cache`` (gitignored). Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it. Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
