"""JAX's persistent compilation cache, at one fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call :func:`enable_compile_cache` once, before their first compile, so a
later run of the same program skips the compiles it already paid for.
Importing ``repro`` never turns the cache on, and the tests leave it off.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed path inside the checkout (the path is part of every cache key's
#: lookup, so a directory that moved between runs would never hit)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
