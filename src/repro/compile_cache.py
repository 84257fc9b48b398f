"""JAX's persistent compilation cache, kept in one fixed directory.

A TPU run compiles the GA step programs and their Pallas kernels before
the first generation; a later process that finds them in the persistent
cache skips that. The directory is part of what lets a later process find
an entry, so it never moves: ``JAX_COMPILATION_CACHE_DIR`` where it is set,
``.jax_cache`` at the root of the checkout otherwise.
"""
import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself
    and nothing is set here. Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
