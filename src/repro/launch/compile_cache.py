"""Persistent compilation cache placement for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, decides where compiled programs
are kept; JAX reads it itself and nothing here overrides it. Otherwise the
cache goes to ``.jax_cache/`` at the root of the checkout, a fixed path
(the path is part of the cache key, so a moving one would never hit),
which ``.gitignore`` lists.

Call :func:`enable_compile_cache` from ``main`` before the first compile;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those that took over a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
