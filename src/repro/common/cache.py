"""Where JAX keeps its persistent compilation cache.

A cold step of a full-width model compiles for minutes, so the entry points
(``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve``,
``benchmarks.run``) turn the cache on at start-up with
:func:`enable_compile_cache` — never on import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no code sets
another directory. Otherwise the cache sits at ``<checkout>/.jax_cache``: a
fixed path, since the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]   # <checkout>/src/repro/common


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``."""
    return os.environ.get(CACHE_ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    (leaving an environment-given directory to JAX) and return it."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
