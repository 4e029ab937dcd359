"""Persistent compilation cache placement for the entry points.

JAX keys cache entries by the cache directory among other things, so the
directory must not move between runs: a path built from a temporary name, a
process id or the time never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set,
wins (JAX reads it itself and nothing here overrides it); otherwise the cache
lives at a fixed ``.jax_cache/`` in the checkout root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: checkout root: src/repro/launch/cache.py -> three levels up from the package
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
