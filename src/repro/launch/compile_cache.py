"""JAX's persistent compilation cache, at a place the caller can choose.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at a fixed path
inside the checkout (``.jax_cache/``, listed in ``.gitignore``): a cache key
includes nothing about where the cache sits, but a directory that moves
between runs is never found again, so the path holds no temporary name,
process id or time.

Call :func:`enable_compile_cache` once, before the first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        import jax

        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
