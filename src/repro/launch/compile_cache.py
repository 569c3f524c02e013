"""Persistent XLA compilation cache for the command-line entry points.

``chip_smoke.py``, ``join_service`` ``main`` and ``engine_bench`` ``main``
call :func:`enable_compile_cache` once, before their first compile.
Importing ``repro`` sets nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and no
other directory is configured here.  Otherwise the cache lives at one fixed
path inside the checkout (:data:`CACHE_DIR`, git-ignored), never built from
a temp name, a pid or the time, so that a later run finds what an earlier
one wrote.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses.

    The join engine compiles many small programs (one stage/gather per
    log-bucketed capacity, one fused root per recovery-round layout), so
    the minimum compile time for an entry to be cached is lowered to 0.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
