"""Persistent XLA compile cache for every process that runs device code.

A fresh process otherwise recompiles every predicate program it
touches. The cache directory is part of the cache key, so it must not
move between runs: where `JAX_COMPILATION_CACHE_DIR` is set JAX reads
it itself and this module sets no path; otherwise the cache lives at a
fixed path inside the checkout (git-ignored).
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Call before the first jit. Returns the directory in effect."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # the default threshold is 1.0 s; most of this program's predicate
    # programs compile faster than that and would never be stored
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
