"""JAX's persistent compilation cache at one fixed place.

A cache kept in a directory that moves between runs (a temp name, a pid,
a time) is never found again, so the directory is fixed.  Call
:func:`use_compile_cache` at the top of an entry point, before anything
compiles.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the directory JAX keeps compiled programs in.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache`` in the
    checkout, the same path on every run.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
