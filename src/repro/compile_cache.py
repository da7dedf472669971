"""JAX's persistent compilation cache for the entry points.

A full-width model takes minutes to compile on a cold process; with the
cache on, the next process that builds the same programs reads them
back.  Entry points call ``enable_compile_cache()`` before their first
compile.  Tests never call it: a compile for a described (not attached)
chip is written to the cache but cannot be read back.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory inside the checkout: never a temp name, a pid or a
# time, so every run of this checkout finds the previous run's entries
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
