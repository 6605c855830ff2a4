"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

The SSD insight: within a chunk of Q timesteps the recurrence collapses to a
masked (semiseparable) attention-like matmul — MXU food — while states are
passed *between* chunks by a cheap rank-preserving recurrence. We tile one
(head, chunk) per grid cell; the (hd × ds) state lives in VMEM scratch and is
carried across the sequential chunk dimension of the grid, so the whole
sequence is processed with one kernel launch and zero HBM state traffic.
The per-head decay rates ride in SMEM, read by the grid's head index.

Grid: (B*H, num_chunks) — last dim sequential (state carry).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hlast_ref, h_ref, *,
            chunk: int, num_chunks: int, num_heads: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)         # (Q, hd)
    dt = dt_ref[0].astype(jnp.float32)       # (Q, 1)
    A = a_ref[pl.program_id(0) % num_heads]  # scalar decay rate (negative)
    B = b_ref[0].astype(jnp.float32)         # (Q, ds)
    C = c_ref[0].astype(jnp.float32)         # (Q, ds)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    dA = dt * A                              # (Q, 1)
    # Mosaic has no cumsum and no (Q, 1) -> (1, Q) transpose: build both
    # orientations from masked reductions over a (Q, Q) tile instead
    dt_row = jnp.sum(jnp.where(rows == cols, dt, 0.0), axis=0,
                     keepdims=True)                                # (1, Q)
    dA_row = dt_row * A
    cum = jnp.sum(jnp.where(causal, dA_row, 0.0), axis=1,
                  keepdims=True)             # (Q, 1) inclusive cumsum
    cum_row = jnp.sum(jnp.where(rows <= cols, dA, 0.0), axis=0,
                      keepdims=True)         # (1, Q) the same, on lanes

    # Within-chunk (the "duality": a decay-masked attention matmul)
    # L[i,j] = exp(cum_i - cum_j) for i >= j
    L = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))   # (Q,Q)
    y = jax.lax.dot_general(scores * L * dt_row, x,
                            (((1,), (0,)), ((), ())))              # (Q,hd)

    # Inter-chunk: contribution of the carried state
    h = h_ref[...]                                                 # (hd,ds)
    y = y + jnp.exp(cum) * jax.lax.dot_general(C, h,
                                               (((1,), (1,)), ((), ())))
    y_ref[0] = y.astype(y_ref.dtype)

    # State update: h' = exp(cum_Q) h + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T
    cum_last = cum[chunk - 1:chunk, :]                             # (1,1)
    w = jnp.exp(cum_last - cum) * dt                               # (Q,1)
    upd = jax.lax.dot_general(x * w, B, (((0,), (0,)), ((), ())))  # (hd,ds)
    h_ref[...] = jnp.exp(cum_last) * h + upd

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        hlast_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B_, C, *, chunk: int = 128, interpret: bool = False):
    """Same contract as ref.ssd (h0 = 0). x: (B,T,H,hd), dt: (B,T,H),
    A: (H,), B_/C: (B,T,H,ds). Returns (y, h_last)."""
    Bb, T, H, hd = x.shape
    ds = B_.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    BH = Bb * H

    # (B,T,H,*) -> (B*H, T, *)
    xh = jnp.moveaxis(x, 2, 1).reshape(BH, T, hd)
    dth = jnp.moveaxis(dt, 2, 1).reshape(BH, T, 1)
    bh = jnp.moveaxis(B_, 2, 1).reshape(BH, T, ds)
    ch = jnp.moveaxis(C, 2, 1).reshape(BH, T, ds)

    grid = (BH, nc)
    y, hlast = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, num_chunks=nc, num_heads=H),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, ds), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, hd, ds), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, hd), x.dtype),
            jax.ShapeDtypeStruct((BH, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dth, A.astype(jnp.float32), bh, ch)

    y = jnp.moveaxis(y.reshape(Bb, H, T, hd), 1, 2)
    return y, hlast.reshape(Bb, H, hd, ds)
