"""JAX's persistent compilation cache, placed for the entry points.

Each entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py``, ``benchmarks/serve_trace.py``)
calls :func:`enable_compile_cache` first thing; importing ``repro`` never
does, so library users and the test suite keep JAX's own settings.
"""
from __future__ import annotations

import os
from pathlib import Path

# fixed: a cache directory that moves between runs is never found again
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache is ``.jax_cache`` at the
    root of the checkout (git ignores it)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
