"""Persistent XLA compilation cache, placed from outside or fixed in-tree.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by jax itself and nothing
here overrides it.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the directory is part of what a later process must
find again (never a temp name, pid or time).  Launchers and
``chip_smoke.py`` call ``enable_compile_cache()`` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
