"""JAX's persistent compilation cache, at a place the caller can choose.

Chunk executables of a real model take tens of seconds each to compile,
and a fresh process compiles every one of them again unless the cache is
on.  :func:`enable_compile_cache` is called by the entry points at
start-up, never at import:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  sets nothing;
* otherwise the cache goes to ``.jax_cache/`` at the root of the
  checkout.  The path is fixed (never built from a temporary name, a pid
  or the time) because it is part of what a later process looks up.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

# src/repro/utils/compile_cache.py -> the checkout's root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    chosen = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if chosen:
        return chosen
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
