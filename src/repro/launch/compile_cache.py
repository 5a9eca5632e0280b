"""Persistent XLA compilation cache for the command-line entry points.

A cold TPU run compiles every kernel and jitted step; the persistent cache
lets later processes skip that.  Entry points (``chip_smoke.py``,
``repro.launch.stream``, ``repro.launch.serve``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` from their ``main``; library imports
never do, so importing ``repro`` leaves the caller's JAX configuration
alone.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``.jax_cache/`` at the root of the checkout (listed in .gitignore).  The
#: path is part of the cache key, so it is fixed rather than per-run.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
