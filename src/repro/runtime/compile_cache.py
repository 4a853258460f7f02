"""JAX's persistent compilation cache, at a place the next run finds again.

A cold run of a 32-layer serving step spends most of its first minute
compiling.  The cache keeps those programs across processes, but only if
every process looks in the same directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — listed in .gitignore
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone: JAX reads it at import, so nothing is set here.  Otherwise
    the cache goes to one fixed directory inside the checkout.  Call before
    the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
