"""Paper Table 2, the EnvPool claim proper: on jittered host envs, taking
the first N of M finishers beats synchronous vectorization by ≥30% (paper:
30%–6x, largest when step-time variance is high — e.g. Crafter resets).

We reproduce it with a host env whose step blocks (GIL released) for a
lognormal duration and a policy with fixed latency:
  sync      — N == M, wait for all (Gymnasium/SB3 semantics)
  pool 2N   — M = 2N, double-buffered (paper's recommended setting)
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.host import HostEnv, HostPool


class JitteredEnv(HostEnv):
    """Blocking step with lognormal latency — NetHack/Crafter-shaped."""

    def __init__(self, mean_ms: float = 2.0, sigma: float = 0.6,
                 reset_ms: float = 10.0, horizon: int = 64, seed: int = 0):
        self.rng = np.random.RandomState(seed)
        self.mean_ms, self.sigma, self.reset_ms = mean_ms, sigma, reset_ms
        self.horizon = horizon
        self.t = 0

    def reset(self, seed):
        time.sleep(self.reset_ms / 1e3)         # slow resets (paper: Crafter)
        self.t = 0
        return np.zeros(8, np.float32)

    def step(self, action):
        dt = self.rng.lognormal(np.log(self.mean_ms), self.sigma) / 1e3
        time.sleep(dt)
        self.t += 1
        done = self.t >= self.horizon
        return np.full(8, self.t, np.float32), 1.0, done, {}


def _policy(obs, latency_ms=1.5):
    time.sleep(latency_ms / 1e3)                # GPU forward stand-in
    return np.zeros((obs.shape[0],), np.int64)


def run_once(M: int, N: int, steps: int = 300, seed: int = 0):
    pool = HostPool([lambda i=i: JitteredEnv(seed=seed + i)
                     for i in range(M)], batch_size=N, seed=seed)
    t0 = time.perf_counter()
    for _ in range(steps):
        obs, rew, done, info, ids = pool.recv()
        act = _policy(obs)
        pool.send(act, ids)
    sps = steps * N / (time.perf_counter() - t0)
    pool.close()
    return sps


def run(N: int = 8, steps: int = 200):
    sync = run_once(M=N, N=N, steps=steps)          # wait-for-all baseline
    pool2 = run_once(M=2 * N, N=N, steps=steps)     # paper's M = 2N
    pool4 = run_once(M=4 * N, N=N, steps=steps)     # M >> 2N straggler mode
    return {"sync_sps": sync, "pool2_sps": pool2, "pool4_sps": pool4,
            "pool2_gain_pct": (pool2 / sync - 1) * 100,
            "pool4_gain_pct": (pool4 / sync - 1) * 100}


def main():
    from repro.telemetry import benchwatch
    r = run()
    print(f"bench_pool_host/envpool,{1e6 / r['pool2_sps']:.1f},"
          f"sync_sps={r['sync_sps']:.0f};pool2_sps={r['pool2_sps']:.0f};"
          f"pool4_sps={r['pool4_sps']:.0f};"
          f"pool2_gain_pct={r['pool2_gain_pct']:.1f};"
          f"pool4_gain_pct={r['pool4_gain_pct']:.1f}")
    benchwatch.record(
        "pool_host", {k: r[k] for k in ("sync_sps", "pool2_sps",
                                        "pool4_sps")})


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
