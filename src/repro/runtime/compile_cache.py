"""Where JAX keeps its persistent compilation cache.

Entry points call ``setup_compile_cache()`` before their first compile so
that a second run (on the same host, or on a host that mounts the same
directory) reuses the compiled programs.  The directory is part of the
cache's key, so it is a fixed path: never a temporary name, a process id
or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/runtime/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Place the cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins: nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
