"""JAX's persistent compilation cache, placed once by each entry point, and
the compile counters of the telemetry registry.

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

# jax.monitoring events whose durations make up a compile. The backend
# compile's time includes a load from the persistent cache
# (``/jax/compilation_cache/cache_retrieval_time_sec`` times a part of it).
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  BACKEND_COMPILE)
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "jax.cache_hits",
                "/jax/compilation_cache/cache_misses": "jax.cache_misses"}

_counting = False


def _on_duration(event: str, secs: float, **_) -> None:
    from repro.telemetry import registry
    if event in COMPILE_EVENTS:
        registry().counter("jax.compile_s").inc(secs)
    if event == BACKEND_COMPILE:
        registry().counter("jax.compiles").inc()


def _on_event(event: str, **_) -> None:
    name = CACHE_EVENTS.get(event)
    if name is not None:
        from repro.telemetry import registry
        registry().counter(name).inc()


def count_compiles() -> None:
    """Feed the registry counters ``jax.compiles`` (executables built, by
    the compiler or from the persistent cache), ``jax.compile_s`` (seconds
    of tracing, lowering and building them), ``jax.cache_hits`` and
    ``jax.cache_misses`` (executables written to the persistent cache) from
    ``jax.monitoring``. Listens once per process; steady state compiles
    nothing, so the hot path pays nothing."""
    global _counting
    if _counting:
        return
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _counting = True


def setup_compile_cache() -> str:
    """Turn on the persistent compile cache, count compiles
    (:func:`count_compiles`) and return the cache's directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache is ``<checkout>/.jax_cache``."""
    count_compiles()
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
