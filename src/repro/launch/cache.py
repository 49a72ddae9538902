"""JAX's persistent compilation cache, as every entry point sets it.

A chip run compiles the gate kernel and the jitted steps from nothing
unless a persistent cache holds them.  Each ``main()`` calls
:func:`use_compile_cache` once; importing this module sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: ``.jax_cache/`` at the root of the checkout (git-ignored).  The path is
#: part of each entry's key, so it is never built from a temporary name, a
#: pid or the time.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
