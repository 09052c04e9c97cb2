"""Persistent JAX compilation cache, placed from outside or at a fixed path.

A cold process pays for every program it compiles; the persistent cache
lets the next process on the same machine read them back.  JAX keys the
cache by its directory among other things, so the directory never moves.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (git-ignored); this file is <checkout>/src/repro/.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to `DEFAULT_CACHE_DIR`.
    Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]
