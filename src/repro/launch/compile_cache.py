"""Persistent JAX compilation cache for the entry points.

``enable()`` is called from the ``main`` of ``serve.py``, ``chip_smoke.py``
and the real-execution benchmarks — never when a library module is
imported, so tests and embedding callers keep JAX's own defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is: JAX reads
    it itself and no other directory is set here.  Otherwise the cache
    lives at the fixed ``<checkout>/.jax_cache`` (listed in
    ``.gitignore``); the path is part of what a cached entry is found by,
    so it must not move between runs."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
