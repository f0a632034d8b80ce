"""Persistent XLA compilation cache, placeable from outside.

A process that compiles the same programs as an earlier one (the smoke run,
a restarted server, the benchmark harness) reads them back instead of
compiling again.  The cache key includes the cache path, so the default path
is fixed: ``<checkout>/.jax_cache``, never a temp, pid- or time-derived
directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins — jax reads it
itself and this module leaves it alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable: a serving warmup is many sub-second compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
