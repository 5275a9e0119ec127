"""Persistent XLA compilation cache for every entry point.

A compile of the full-width train step takes minutes; the cache lets later
processes on the same machine skip it.  The directory must not move between
runs (a moving directory never hits), so it is either the one the
environment names or a fixed directory in the checkout.
"""
import os

import jax

CHECKOUT_CACHE = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
