"""Persistent compilation cache placement for the entry points.

JAX keys cache entries by the cache directory among other things, so the
directory must not move between runs: a path built from a temporary name, a
process id or the time never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set,
wins (JAX reads it itself and nothing here overrides it); otherwise the cache
lives at a fixed ``.jax_cache/`` in the checkout root.

Entries are keyed with the program's metadata too.  JAX's default leaves it
out, so two checkouts whose programs differ only in metadata share entries
when a machine gives them one ``JAX_COMPILATION_CACHE_DIR``, and a hit
brings back the op_names of whichever compiled first; the device scopes of
:mod:`repro.obs.scopes` are read from them.  The price: the key holds the
source locations of the traced program, so a change of entry script or an
edit that moves lines of the traced code compiles afresh.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: checkout root: src/repro/launch/cache.py -> three levels up from the package
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
