"""Flash attention (causal, GQA) as a Pallas TPU kernel.

TPU adaptation of the classic GPU algorithm (DESIGN.md §2): instead of a
warp-level softmax we tile for the MXU — (block_q × head_dim) query tiles in
VMEM, streaming (block_k × head_dim) KV tiles; the online-softmax running
max/denominator live in VMEM scratch that persists across the sequential
KV grid dimension. GQA is handled in the index maps (K/V blocks are fetched
for head h // group_size), so KV tiles are never materially replicated.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — the last dimension is
sequential on TPU, which is what makes the scratch carry legal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            num_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # Causal: skip fully-masked KV blocks (they contribute nothing).
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]                              # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha +
                        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, T, H, hd); k, v: (B, S, K, hd); returns (B, T, H, hd)."""
    B, T, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    assert T % block_q == 0 and S % block_k == 0, (T, S, block_q, block_k)
    nq, nk = T // block_q, S // block_k

    # head-major layout so each grid cell touches one contiguous tile
    qh = jnp.moveaxis(q, 2, 1)            # (B, H, T, hd)
    kh = jnp.moveaxis(k, 2, 1)            # (B, K, S, hd)
    vh = jnp.moveaxis(v, 2, 1)

    grid = (B, H, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_kv_blocks=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return jnp.moveaxis(out, 1, 2)        # back to (B, T, H, hd)
