"""Where JAX keeps its persistent compilation cache.

A chip run that starts cold compiles every program again; the persistent
cache lets processes of one checkout share their compiles.  The directory
is part of the cache's key, so it must not move between runs: either the
caller's ``JAX_COMPILATION_CACHE_DIR`` (JAX reads it itself, and nothing
here overrides it) or a fixed ``.jax_cache/`` at the checkout root, which
``.gitignore`` lists.

Call :func:`enable_compile_cache` at the start of a command-line entry
point (``chip_smoke.py``, the benchmark CLIs), never at library import or
in tests.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout-root fallback when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
