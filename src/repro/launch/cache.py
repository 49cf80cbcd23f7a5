"""Persistent compile cache placement, shared by every entry point.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, nothing is
configured here.  Otherwise the cache goes to ``.jax_cache/`` at the root of
the checkout: a fixed path, because the path is part of the cache key, so a
directory built from a temp name, pid or time would never hit.  Call
:func:`enable_compile_cache` before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
