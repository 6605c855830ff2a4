"""JAX's persistent compilation cache, placed once by each entry point.

Entry points (``launch.train``, ``launch.serve``, ``chip_smoke.py``, the
benchmarks) call :func:`setup_compile_cache` before their first compile;
nothing calls it at import. The default directory is fixed (no temporary
name, pid or time in it), so the next run from the same checkout finds what
this one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache is ``<checkout>/.jax_cache``."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
