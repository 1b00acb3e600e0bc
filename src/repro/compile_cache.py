"""JAX's persistent compilation cache, turned on by the entry points.

``chip_smoke.py``, ``repro.launch.serve`` / ``train`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` once at start, so
a program compiled by one run is found again by the next.  Importing the
library never turns it on.

The directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set (and no
other), else the fixed ``<repo>/artifacts/jax_cache``: the path is part of
what the cache is keyed on, so it never moves between runs.
"""

from __future__ import annotations

import os


def cache_dir() -> str:
    """Where the entry points keep compiled programs."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, "artifacts", "jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir` and
    return that directory."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
