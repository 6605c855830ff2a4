"""GAE as a Pallas kernel — PPO's sequential bottleneck, blocked in time.

GAE is a length-T reverse scalar recurrence per environment: tiny FLOPs,
purely memory-bound, and painful as T separate XLA ops. The kernel takes the
learner's time-major ``(T, B)`` layout as is: time on the sublane axis, envs
on the lane axis. A (block_t × block_b) tile sits in VMEM; the reverse walk
reads and writes one ``(1, block_b)`` row per step through ref indexing, so
every step is a handful of full-lane vector ops. Time blocks are walked in
reverse by the index map, and the carried (advantage, next-value) rows live
in VMEM scratch across the sequential time-grid dimension. One launch, one
pass over HBM.

Grid: (B / block_b, T / block_t) — time dim sequential, reversed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, v_ref, nt_ref, lastv_ref, adv_ref, carry_ref, *,
            gamma: float, lam: float, block_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        # carry rows: [0] = A_{t+1}, [1] = V_{t+1}
        carry_ref[0:1, :] = jnp.zeros_like(lastv_ref[...])
        carry_ref[1:2, :] = lastv_ref[...]

    def step(i, carry):
        adv_next, v_next = carry
        t = block_t - 1 - i
        rt = r_ref[pl.ds(t, 1), :]                 # (1, bb)
        vt = v_ref[pl.ds(t, 1), :]
        ntt = nt_ref[pl.ds(t, 1), :]
        delta = rt + gamma * v_next * ntt - vt
        adv = delta + gamma * lam * ntt * adv_next
        adv_ref[pl.ds(t, 1), :] = adv
        return adv, vt

    carry = (carry_ref[0:1, :], carry_ref[1:2, :])
    adv, vt = jax.lax.fori_loop(0, block_t, step, carry)
    carry_ref[0:1, :] = adv
    carry_ref[1:2, :] = vt


def _block(n: int, want: int, align: int) -> int:
    """Largest multiple of ``align`` that divides ``n`` and is at most
    ``want``; the whole axis when there is none (a full-dim block is always
    legal on the TPU)."""
    for cand in range(min(want, n) // align * align, 0, -align):
        if n % cand == 0:
            return cand
    return n


@functools.partial(jax.jit, static_argnames=("gamma", "lam", "block_b",
                                             "block_t", "interpret"))
def gae(rewards, values, dones, last_value, gamma: float, lam: float,
        *, block_b: int = 512, block_t: int = 128, interpret: bool = False):
    """Same contract as ref.gae. rewards/values/dones: (T, B) time-major;
    last_value: (B,). Returns advantages (T, B) float32."""
    T, B = rewards.shape
    block_b = _block(B, block_b, 128)
    block_t = _block(T, block_t, 8)
    nb, ntb = B // block_b, T // block_t
    nonterm = 1.0 - dones.astype(jnp.float32)

    rev = lambda b, t, n=ntb: (n - 1 - t, b)   # walk time blocks in reverse
    return pl.pallas_call(
        functools.partial(_kernel, gamma=gamma, lam=lam, block_t=block_t),
        grid=(nb, ntb),
        in_specs=[
            pl.BlockSpec((block_t, block_b), rev),
            pl.BlockSpec((block_t, block_b), rev),
            pl.BlockSpec((block_t, block_b), rev),
            pl.BlockSpec((1, block_b), lambda b, t: (0, b)),
        ],
        out_specs=pl.BlockSpec((block_t, block_b), rev),
        out_shape=jax.ShapeDtypeStruct((T, B), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, block_b), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rewards.astype(jnp.float32), values.astype(jnp.float32), nonterm,
      last_value.astype(jnp.float32)[None, :])
