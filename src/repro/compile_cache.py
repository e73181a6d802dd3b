"""JAX's persistent compilation cache, kept at one fixed place.

Every (preset, stage, engine, batch shape) is its own XLA program, and
a cold process compiles each of them again.  `enable_compile_cache`
turns JAX's persistent cache on for entry points that run whole grids
(`chip_smoke.py`, `benchmarks/run.py`):

* when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
  the cache lives there — nothing else is set;
* otherwise it lives at ``<repo>/.jax_cache`` (gitignored).  The path
  is fixed on purpose: the directory is part of where JAX looks an
  entry up, so a temporary or per-run name would never hit.

The library itself never turns the cache on; tests run without it.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the cache's home when ``JAX_COMPILATION_CACHE_DIR`` is not set
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
