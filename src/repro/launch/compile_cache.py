"""JAX's persistent compilation cache, placed from outside.

Entry points (`chip_smoke.py`, `repro.launch.bfs_run`, the benchmark
workers) call `use_compile_cache()` once, before their first compile, so
their processes share compiled programs.  Library imports and tests never
call it.

Where JAX_COMPILATION_CACHE_DIR is set, JAX reads the variable itself and
nothing is set in code.  Otherwise the cache lives at `<checkout>/.jax_cache`
(listed in .gitignore): a fixed path, because the path is part of the
cache's key and a directory that moves never hits.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
