"""Placement of JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives in a fixed, git-ignored
directory of the checkout, so that every run from the same checkout finds
what an earlier one compiled (the path is part of the cache's key: a
directory that moves never hits).  Tests enable no cache.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Point the cache at ``CHECKOUT_CACHE`` unless the environment already
    places it; call before the first compile.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
