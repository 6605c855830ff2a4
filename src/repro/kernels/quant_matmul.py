"""Weight-quantized matmul (W8A16 / W4A16) as a Pallas TPU kernel.

Serving at 400B scale only fits a pod with ≤8-bit weights, and the win only
materializes if dequantization happens *in registers*: the kernel streams
int8/int4 weight tiles into VMEM, dequantizes per output channel, and feeds
the MXU — HBM traffic is the quantized bytes, never a materialized bf16
weight. (An XLA-level dequant writes the bf16 weight back to HBM first —
~3x the traffic; measured in EXPERIMENTS.md §Perf.)

Grid: (M/bm, N/bn, K/bk) — K sequential, f32 accumulator in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wq_ref, scale_ref, o_ref, acc_ref, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)              # (bm, bk)
    w = wq_ref[...].astype(jnp.float32)             # (bk, bn) dequant in VREG
    acc_ref[...] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())))

    @pl.when(ki == nk - 1)
    def _finish():
        s = scale_ref[...].astype(jnp.float32)      # (1, bn)
        o_ref[...] = (acc_ref[...] * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def quant_matmul(x, w_q, scale, *, block_m: int = 128, block_n: int = 128,
                 block_k: int = 128, interpret: bool = False):
    """x: (M, K) bf16/f32; w_q: (K, N) int8/int4; scale: (N,) f32.
    Returns x @ (w_q * scale) in x.dtype."""
    M, K = x.shape
    K2, N = w_q.shape
    assert K == K2 and scale.shape == (N,)
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    nk = K // block_k
    grid = (M // block_m, N // block_n, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_q, scale[None, :])
