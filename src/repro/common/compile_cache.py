"""JAX's persistent compilation cache for this checkout's entry points."""

from __future__ import annotations

import os

#: Where the cache lives when the environment names none: one fixed path
#: inside the checkout. The path is part of every cache key, so a name
#: that changed per run (a temporary directory, a pid, a time) never hits.
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to :data:`CHECKOUT_CACHE`.
    Entry points call this once; importing a module never does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
