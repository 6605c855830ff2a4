"""Paper Table 2: vectorized throughput — serial vs fused-vmap vs
double-buffered pool (the EnvPool analogue), on real envs.

The paper's result to reproduce: vectorization beats serial everywhere, and
pooling adds ≥30% on top for envs with any policy/step overlap to hide.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.emulation import Emulated
from repro.core.vector import VecEnv
from repro.core.pool import Pool
from repro.envs.ocean import OCEAN


def _actions(vec_or_pool, batch):
    return jnp.zeros((batch, 1), jnp.int32)


def _policy_like_work(obs):
    """Stand-in policy compute so the pool has something to overlap."""
    w = jnp.ones((obs.shape[-1], 64), obs.dtype)
    return jnp.tanh(obs @ w).sum()


def bench_serial(env, num_envs, steps):
    vec = VecEnv(Emulated(env), num_envs, backend="serial")
    state, obs = vec.init(jax.random.PRNGKey(0))
    act = jnp.zeros((vec.batch_size, len(vec.single_action_space.nvec)),
                    jnp.int32)
    state, obs, *_ = vec.step(state, act, jax.random.PRNGKey(1))
    jax.block_until_ready(obs)
    t0 = time.perf_counter()
    for i in range(steps):
        state, obs, *_ = vec.step(state, act, jax.random.fold_in(
            jax.random.PRNGKey(2), i))
        _policy_like_work(obs).block_until_ready()  # repro: noqa[HOST-SYNC] — measures per-step latency incl. the sync (deliberate)
    return steps * vec.batch_size / (time.perf_counter() - t0)


def bench_vmap(env, num_envs, steps):
    vec = VecEnv(Emulated(env), num_envs, backend="vmap")
    state, obs = vec.init(jax.random.PRNGKey(0))
    act = jnp.zeros((vec.batch_size, len(vec.single_action_space.nvec)),
                    jnp.int32)
    state, obs, *_ = vec.step(state, act, jax.random.PRNGKey(1))
    jax.block_until_ready(obs)
    t0 = time.perf_counter()
    for i in range(steps):
        state, obs, *_ = vec.step(state, act, jax.random.fold_in(
            jax.random.PRNGKey(2), i))
        _policy_like_work(obs).block_until_ready()  # repro: noqa[HOST-SYNC] — measures per-step latency incl. the sync (deliberate)
    return steps * vec.batch_size / (time.perf_counter() - t0)


def bench_pool(env, num_envs, steps, buffers=2):
    pool = Pool(Emulated(env), num_envs, num_buffers=buffers)
    act = jnp.zeros((pool.batch_size,
                     len(pool.vec.single_action_space.nvec)), jnp.int32)
    for _ in range(buffers):                    # warm both buffers
        obs, *_ , b = pool.recv()
        pool.send(act, b)
    t0 = time.perf_counter()
    for i in range(steps):
        obs, rew, done, info, b = pool.recv()
        _policy_like_work(obs)                  # NOT blocked — overlap
        pool.send(act, b)
    jax.block_until_ready(obs)
    return steps * pool.batch_size / (time.perf_counter() - t0)


def run(num_envs=64, steps=200):
    rows = []
    for name in ("squared", "bandit", "stochastic", "memory"):
        env_cls = OCEAN[name]
        r = {"env": name,
             "serial": bench_serial(env_cls(), min(num_envs, 8), steps // 4)
             * num_envs / min(num_envs, 8),   # extrapolated (serial is slow)
             "vmap": bench_vmap(env_cls(), num_envs, steps),
             "pool": bench_pool(env_cls(), num_envs, steps)}
        r["pool_vs_vmap_pct"] = (r["pool"] / r["vmap"] - 1) * 100
        rows.append(r)
    return rows


def main():
    from repro.telemetry import benchwatch
    rows = run()
    cells = {}
    for r in rows:
        print(f"bench_vector/{r['env']},{1e6 / r['vmap']:.2f},"
              f"serial_sps={r['serial']:.0f};vmap_sps={r['vmap']:.0f};"
              f"pool_sps={r['pool']:.0f};"
              f"pool_gain_pct={r['pool_vs_vmap_pct']:.1f}")
        cells[f"{r['env']}_vmap_sps"] = r["vmap"]
        cells[f"{r['env']}_pool_sps"] = r["pool"]
    benchwatch.record("vector", cells)


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
