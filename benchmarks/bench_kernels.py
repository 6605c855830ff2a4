"""Kernel backend autotune table — dispatch.autotune over every registered
op, timing each runnable backend (ref / chunked / pallas_interpret on CPU;
plus compiled pallas on TPU) and printing the per-op winner the registry
will use for subsequent auto dispatch.

Prints ``kernels/<op>/<backend>,us_per_call,winner=<best>`` CSV rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch


def _args(op, key):
    r = lambda i, shape, scale=1.0: (
        jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        * scale)
    if op == "flash_attention":
        shp = (2, 128, 4, 32)
        return (r(1, shp), r(2, shp), r(3, shp)), dict(causal=True)
    if op == "flash_decode":
        return (r(1, (4, 8, 32)), r(2, (4, 256, 2, 32)),
                r(3, (4, 256, 2, 32)), jnp.asarray(200, jnp.int32)), {}
    if op == "quant_matmul":
        wq = jax.random.randint(jax.random.fold_in(key, 2), (128, 256),
                                -127, 128, jnp.int32).astype(jnp.int8)
        return (r(1, (64, 128)), wq, jnp.abs(r(3, (256,))) * 0.02), {}
    if op == "gae":
        d = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.1, (128, 64))
        return (r(1, (128, 64)), r(2, (128, 64)), d, r(4, (64,)),
                0.99, 0.95), {}
    if op == "ssd":
        return (r(1, (2, 128, 4, 32), 0.5),
                jax.nn.softplus(r(2, (2, 128, 4))),
                -jnp.exp(r(3, (4,), 0.3)),
                r(4, (2, 128, 4, 16), 0.5),
                r(5, (2, 128, 4, 16), 0.5)), dict(chunk=32)
    if op == "pack":
        leaves = [jax.random.randint(jax.random.fold_in(key, i), (256, n),
                                     0, 256, jnp.int32).astype(jnp.uint8)
                  for i, n in enumerate((8, 32, 64))]
        return (leaves,), {}
    raise AssertionError(op)


def main(include_interpret: bool = False) -> None:
    """Interpret mode is 100-1000x slower than compiled paths — skipped by
    default so the table reflects deployable backends."""
    key = jax.random.PRNGKey(0)
    cells = {}
    try:
        for op in dispatch.OPS:
            impls = dispatch.available(op)
            if not include_interpret:
                impls = tuple(n for n in impls if n != dispatch.INTERPRET)
            args, kw = _args(op, key)
            results, best = dispatch.autotune(op, *args, impls=impls,
                                              iters=10, **kw)
            cells[f"{op}_best_calls_per_s"] = results[best]
            for name, calls_per_s in sorted(results.items(),
                                            key=lambda kv: -kv[1]):
                print(f"kernels/{op}/{name},{1e6 / calls_per_s:.1f},"
                      f"winner={best}")
    finally:
        # winners were tuned on this table's fixed shapes — don't let them
        # leak into auto dispatch for the rest of the process
        dispatch.clear_autotune()
    from repro.telemetry import benchwatch
    benchwatch.record("kernels", cells,
                      meta={"include_interpret": bool(include_interpret)})


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
