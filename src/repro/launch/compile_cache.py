"""JAX's persistent compilation cache, for the program's entry points.

Call :func:`enable_compile_cache` from a ``main`` only, never at import of
a library module or in tests: a compile for a described (not attached) TPU
is written to the cache but cannot be read back without the chip.
"""
from __future__ import annotations

import os

import jax

#: fixed in-checkout location; the path is part of the cache key, so it
#: must not move between runs
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Return the cache directory in use.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX already reads it and nothing is set here; otherwise the cache
    goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
