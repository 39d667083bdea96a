"""JAX's persistent compilation cache for the repo's entry points.

A cold process on the TPU compiles every kernel and jitted step again;
the persistent cache lets a later run of the same code skip that.
Each entry point (`chip_smoke.py`, ``repro.serving.server``,
``repro.transport.worker``, ``benchmarks.run``) calls
:func:`enable_compile_cache` before its first compile.  Importing the
library never touches the cache setting.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this sets nothing.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache``: the directory is part of what a cache entry
is found by, so it is never derived from a temporary name, a pid or
the time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))            # <checkout>/src/repro/..
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
