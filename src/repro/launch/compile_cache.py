"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.  The cache directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the fixed
``<checkout>/.jax_cache``: the path is part of each entry's key, so a
directory that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(_CHECKOUT_CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
