"""JAX's persistent compilation cache, kept in one fixed place per checkout.

Every entry point (chip_smoke.py, benchmarks/run.py, repro.launch.serve,
repro.launch.train) calls `enable_compile_cache` before its first compile.
A run finds the entries of an earlier one only if the directory does not move
between runs: it is ``<repo>/.jax_cache`` (git-ignored), never a temporary
directory.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
