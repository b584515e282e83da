"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point calls :func:`enable_compile_cache` before its first
compile.  ``JAX_COMPILATION_CACHE_DIR``, where set, names the directory and
JAX reads it itself; otherwise the cache is ``.jax_cache/`` at the root of
the checkout.  The path is part of what a cached program is found by, so it
never depends on a temp name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the default cache directory: ``.jax_cache/`` at the checkout's root
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
