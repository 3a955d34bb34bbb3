"""Where JAX keeps its persistent compilation cache.

Entry points (serve, train, ``benchmarks/run.py``, ``chip_smoke.py``)
call ``enable_compile_cache`` once before compiling anything; library
modules never set a cache. The path is part of the cache's key, so it is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it
itself, and no other path is set), otherwise ``<checkout>/.jax_cache``.

Every program is written, however fast it compiled: by default JAX keeps
only those that took a second or more, and a served model's many small
programs (slab set-up, page-table puts, bucket variants) would then be
compiled again by every new process.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
