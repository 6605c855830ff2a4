"""Seconds the process spent tracing, lowering and building executables (by
the compiler or from the persistent compile cache) up to the end of the
traced window: the registry counter ``jax.compile_s`` that
``utils/compile_cache.count_compiles`` feeds from ``jax.monitoring``.
Set-up holds all of it where the window compiles nothing. Source: a
program counter. Silent where the program keeps no such counter."""


def read(ctx):
    from repro.telemetry import registry
    return registry().snapshot()["counters"].get("jax.compile_s")
