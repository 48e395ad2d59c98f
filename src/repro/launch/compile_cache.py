"""Where the entry points keep JAX's persistent compilation cache.

A compiled program is keyed in part by the cache directory's path, so the
cache only pays off at a fixed location. Entry points call
:func:`enable_compile_cache` from ``main()``; nothing here runs at import.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout root: src/repro/launch/compile_cache.py -> parents[3]
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    this sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the
    checkout root.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
