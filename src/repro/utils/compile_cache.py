"""Where the program keeps JAX's persistent compilation cache.

One rule for every entry point that compiles for a deployment (the
serving CLI, the benchmark runner, ``chip_smoke.py``): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set; otherwise the cache lives at ``.jax_cache/`` in the
checkout.  The path is part of every cache key, so it is fixed, not
derived from the working directory or a temporary location.  Tests
never call this: a compile for a described (unattached) TPU is written
to the cache but cannot be read back.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory :func:`enable_compile_cache` uses."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
