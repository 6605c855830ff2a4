"""Flash-decode: one-token attention against a long KV cache, as a Pallas
TPU kernel — the serve_step hot spot (decode_32k / long_500k shapes).

Per (batch, kv-head) grid cell the kernel streams (block_s × hd) KV tiles
through VMEM and attends all G grouped query heads against them at once
(GQA: the tile is loaded once per group, not per query head). The online
softmax statistics (m, l) and the (G × hd) output accumulator live in VMEM
scratch across the sequential KV-block dimension. Positions beyond the
filled cache length are masked, so one compiled kernel serves every prefix
length.

Grid: (B, K, S / block_s) — last dim sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_s: int, num_blocks: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bs, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, bs)

    # mask positions beyond the filled cache prefix (length is inclusive of
    # the token being attended from: positions [0, length] are valid)
    pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32,
                                                  (q.shape[0], block_s), 1)
    s = jnp.where(pos <= len_ref[0], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha +
                    jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
    m_ref[...] = m_new

    @pl.when(si == num_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(q, k, v, length, *, block_s: int = 512,
                 interpret: bool = False):
    """q: (B, H, hd) one query per sequence; k, v: (B, S, K, hd) caches;
    length: () int32 — index of the newest valid cache entry.
    Returns (B, H, hd)."""
    B, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / (hd ** 0.5)
    block_s = min(block_s, S)
    assert S % block_s == 0
    nb = S // block_s

    qg = q.reshape(B, K, G, hd)
    kh = jnp.moveaxis(k, 2, 1)            # (B, K, S, hd)
    vh = jnp.moveaxis(v, 2, 1)
    lens = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (1,))

    grid = (B, K, nb)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_s=block_s,
                          num_blocks=nb),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, s: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qg, kh, vh)
    return out.reshape(B, H, hd)
