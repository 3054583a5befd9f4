"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` once at start-up,
never at import.  The directory is ``$JAX_COMPILATION_CACHE_DIR`` when it
is set, else a fixed ``.jax_cache`` at the root of the checkout: the path
is part of the cache's key, so it must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout's root (``src/repro/launch/`` is three levels down)
_CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives for this process."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(_CHECKOUT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir`, caching
    every compiled program however quickly it compiled; returns the
    directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
