"""JAX's persistent compilation cache, switched on by the entry points.

Importing ``repro`` never touches the cache: scripts, examples and
benchmarks call :func:`enable_compile_cache` from their ``__main__`` before
they compile anything, and library users keep whatever they configured.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: ``.jax_cache/`` at the root of this checkout. A fixed path: a directory
#: that moved between runs would never be hit again.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache lives in
    :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
