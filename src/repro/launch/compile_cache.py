"""Persistent compilation cache for the entry points.

Called from ``chip_smoke.py`` and ``examples/serve_specrouter.py`` — never
at import and never from tests.  JAX reads ``JAX_COMPILATION_CACHE_DIR``
itself, so when that is set nothing is configured here.  Otherwise the
cache goes to one fixed directory inside the checkout: the path is part of
the cache key, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
