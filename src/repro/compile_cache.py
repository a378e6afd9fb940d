"""JAX's persistent compilation cache for the entry points.

Call :func:`configure_compile_cache` first thing in a ``main`` (never at
import): a TPU run that compiles the fused epoch program cold pays that
compile again in every new process unless the executable is cached.
"""
from __future__ import annotations

import os

import jax

# a fixed directory in the checkout: the cache key includes the path, so a
# temporary or per-process directory would never hit
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here; otherwise the cache goes to ``.jax_cache/``
    at the root of the checkout.  The cache key includes the programs'
    metadata: a profiler trace reads the fused program's named scopes
    from its executable's op names, and a key without them would load an
    executable compiled from the same program with other (stale) names.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
