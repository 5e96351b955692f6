"""Where JAX keeps its persistent compilation cache.

A cold step of a full-width model compiles for minutes, so the entry points
(``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve``,
``benchmarks.run``) turn the cache on at start-up with
:func:`enable_compile_cache` — never on import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no code sets
another directory. Otherwise the cache sits at ``<checkout>/.jax_cache``: a
fixed path, since the directory is part of what a later run must find again.

JAX keys a cached executable on its program with the debug information
stripped, but the executable keeps the op metadata it was compiled with: the
``jax.named_scope`` names and source lines that a device trace reports for
each op. Two versions of the code that differ only in their scopes would share
entries, and a warm run would then trace under the other version's names. So
:func:`enable_compile_cache` also keys the cache on a digest of this package's
sources.
"""
from __future__ import annotations

import functools
import hashlib
import os
from pathlib import Path

import jax
from jax._src import cache_key

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
PACKAGE = Path(__file__).resolve().parents[1]    # <checkout>/src/repro
CHECKOUT = PACKAGE.parents[1]


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``."""
    return os.environ.get(CACHE_ENV) or str(CHECKOUT / ".jax_cache")


@functools.cache
def source_digest() -> str:
    """SHA-256 over the paths and contents of the package's ``.py`` files."""
    h = hashlib.sha256()
    for f in sorted(PACKAGE.rglob("*.py")):
        h.update(f.relative_to(PACKAGE).as_posix().encode())
        h.update(f.read_bytes())
    return h.hexdigest()


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    (leaving an environment-given directory to JAX), key its entries on
    :func:`source_digest` as well, and return the directory."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    cache_key.custom_hook = source_digest
    return path
