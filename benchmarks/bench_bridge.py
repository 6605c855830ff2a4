"""HostBridge sync-vs-async SPS under injected step jitter (paper Table 2).

The EnvPool claim the ``host`` tier inherits: on jittered host envs, batching
the first N of M = 2N finishers beats synchronous (M = N, wait-for-all)
vectorization by ≥ 30%, because stragglers never gate the batch and env
stepping overlaps policy compute. Measured twice:

  * ``vecenv`` — a bridge-wrapped Gymnasium-API env with lognormal step
    latency driven by a fixed-latency policy stand-in (pure bridge overhead,
    no learner).
  * ``engine`` — the real thing: ``TrainEngine(backend="host")`` PPO on the
    jittered ``HostBandit`` mirror, M = N vs M = 2N.

  PYTHONPATH=src python benchmarks/bench_bridge.py --quick

Writes BENCH_bridge.json; acceptance: async (M = 2N) ≥ 1.3× sync (M = N) on
the vecenv benchmark.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


class JitteredGymEnv:
    """Gymnasium-API env with lognormal step latency — NetHack-shaped."""

    def __init__(self, mean_ms: float = 2.0, sigma: float = 0.6,
                 reset_ms: float = 10.0, horizon: int = 64, seed: int = 0):
        from repro.envs.ocean_host import _gym_box
        self.observation_space = _gym_box(-1.0, 1.0, (8,))
        self.action_space = _gym_box(-1.0, 1.0, (1,))
        self.rng = np.random.RandomState(seed)
        self.mean_ms, self.sigma, self.reset_ms = mean_ms, sigma, reset_ms
        self.horizon = horizon
        self.t = 0

    def reset(self, *, seed=None, options=None):
        time.sleep(self.reset_ms / 1e3)         # slow resets (Crafter-shaped)
        if seed is not None:
            # derive the latency stream from the pool's per-env reset seed:
            # distinct streams per env under BOTH backends (a constructor
            # seed can't vary per worker once factories are pickled)
            self.rng = np.random.RandomState(int(seed) % (2 ** 32))
        self.t = 0
        return np.zeros(8, np.float32), {}

    def step(self, action):
        dt = self.rng.lognormal(np.log(self.mean_ms), self.sigma) / 1e3
        time.sleep(dt)
        self.t += 1
        truncated = self.t >= self.horizon
        info = {"score": 0.5} if truncated else {}
        return (np.full(8, self.t / self.horizon, np.float32), 1.0, False,
                truncated, info)


def run_once(M: int, N: int, steps: int = 200, seed: int = 0,
             policy_latency_ms: float = 1.5,
             backend: str = "thread") -> float:
    """SPS of a recv→policy→send loop over the bridged jittered env.
    Per-env latency streams stay distinct (a shared stream would phase-lock
    the envs and understate the straggler variance the pool exploits): each
    env reseeds from the pool's ``seed + i`` reset seed."""
    from repro.bridge import wrap
    venv = wrap(JitteredGymEnv, num_envs=M,
                batch_size=N, seed=seed, backend=backend)
    try:
        obs, _rew, _done, _info, ids = venv.recv(timeout=60)
        t0 = time.perf_counter()
        for _ in range(steps):
            time.sleep(policy_latency_ms / 1e3)     # device forward stand-in
            venv.send(np.zeros((N, 1), np.float32), ids)
            obs, _rew, _done, _info, ids = venv.recv(timeout=60)
        sps = steps * N / (time.perf_counter() - t0)
    finally:
        venv.close()
    return sps


def engine_once(M_mult: int, updates: int = 12, jitter_ms: float = 2.0,
                seed: int = 0) -> float:
    """Training SPS of the host tier on jittered HostBandit, M = M_mult·N."""
    import itertools
    from repro.bridge import make_host_engine
    from repro.configs.base import TrainConfig
    from repro.envs.ocean_host import HostBandit
    tcfg = TrainConfig(num_envs=16, unroll_length=16, update_epochs=2,
                       num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                       pool_buffers=M_mult)
    counter = itertools.count(seed)             # distinct per-env jitter
    eng = make_host_engine(
        lambda: HostBandit(jitter_ms=jitter_ms, jitter_seed=next(counter)),
        tcfg, hidden=32, kernel_mode="ref", seed=seed)
    try:
        eng.run(2 * eng.steps_per_update)           # warmup: compile act+learn
        t0 = time.perf_counter()
        hist, _ = eng.run(updates * eng.steps_per_update)
        dt = time.perf_counter() - t0
        assert len(hist) == updates
        return updates * eng.steps_per_update / dt
    finally:
        eng.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timed steps (CI smoke)")
    ap.add_argument("--backend", default="thread",
                    choices=("thread", "proc"),
                    help="HostPool worker backend for the vecenv cells "
                         "(thread-vs-proc head-to-head lives in "
                         "bench_hostpool.py)")
    ap.add_argument("--out", default="BENCH_bridge.json")
    args = ap.parse_args(argv)

    N = 8
    steps = 120 if args.quick else 300
    sync = run_once(M=N, N=N, steps=steps, backend=args.backend)
    async2 = run_once(M=2 * N, N=N, steps=steps, backend=args.backend)
    async4 = run_once(M=4 * N, N=N, steps=steps, backend=args.backend)
    gain2 = async2 / sync
    print(f"bench_bridge/vecenv,{1e6 / async2:.1f},sync_sps={sync:.0f};"
          f"async2_sps={async2:.0f};async4_sps={async4:.0f};"
          f"async2_gain={gain2:.2f}x")

    upd = 8 if args.quick else 16
    engine = {}
    for jitter in ((2.0,) if args.quick else (2.0, 4.0)):
        eng_sync = engine_once(1, updates=upd, jitter_ms=jitter)
        eng_async = engine_once(2, updates=upd, jitter_ms=jitter)
        engine[f"jitter_{jitter:g}ms"] = {
            "sync_sps": round(eng_sync, 1),
            "async_sps": round(eng_async, 1),
            "gain": round(eng_async / eng_sync, 3)}
        print(f"bench_bridge/engine_j{jitter:g},{1e6 / eng_async:.1f},"
              f"sync_sps={eng_sync:.0f};async_sps={eng_async:.0f};"
              f"gain={eng_async / eng_sync:.2f}x")

    out = {
        "meta": {"batch_envs": N, "steps": steps, "engine_updates": upd,
                 "quick": bool(args.quick), "backend": args.backend,
                 "jitter": {"vecenv_mean_ms": 2.0, "vecenv_sigma": 0.6,
                            "policy_latency_ms": 1.5}},
        "vecenv": {"sync_sps": round(sync, 1),
                   "async2_sps": round(async2, 1),
                   "async4_sps": round(async4, 1),
                   "async2_gain": round(gain2, 3)},
        "engine": engine,
        "acceptance": {"async2_ge_1p3x_sync": gain2 >= 1.3},
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")
    from repro.telemetry import benchwatch
    bw_cells = {"vecenv_sync_sps": round(sync, 1),
                "vecenv_async2_sps": round(async2, 1),
                "vecenv_async4_sps": round(async4, 1)}
    for jkey, cell in engine.items():
        bw_cells[f"engine_{jkey}_sync_sps"] = cell["sync_sps"]
        bw_cells[f"engine_{jkey}_async_sps"] = cell["async_sps"]
    benchwatch.record("bridge", bw_cells,
                      acceptance={"async2_ge_1p3x_sync": gain2 >= 1.3},
                      meta={"quick": bool(args.quick), "steps": steps})


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
