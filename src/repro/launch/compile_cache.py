"""JAX's persistent compilation cache for the launchers and the chip smoke.

Call :func:`enable_compile_cache` first thing in a process entry point,
before anything compiles.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and this sets no other directory; otherwise the cache lives
at the fixed ``<checkout>/.jax_cache`` (the path is part of the cache key, so
it never comes from a temporary directory, a process id or the time).
Libraries and tests never call this: importing ``repro`` turns nothing on.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` — resolved from this file (src/repro/launch/)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses.

    The minimum compile time for an entry is lowered to zero so the Pallas
    kernels, which compile in about a second each, are cached too.
    """
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
