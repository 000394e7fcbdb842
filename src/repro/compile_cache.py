"""JAX's persistent compilation cache for the repository's entry points.

``enable_compile_cache()`` is the first call of ``chip_smoke.py``,
``repro.launch.serve`` and ``benchmarks.run``; it is never called at
import, so tests and library users compile without a cache. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives at one fixed path in the checkout
(``.jax_cache/``, git-ignored): the directory is part of the cache key,
so a path built from a temporary name, a pid or the time never hits.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
