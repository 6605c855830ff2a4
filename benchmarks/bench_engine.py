"""TrainEngine SPS trajectory: K fused updates per dispatch × backend.

The paper's pitch is IPC-count reduction; the engine's is dispatch-count
reduction. This benchmark measures steps/second for K ∈ {1, 4, 16, 64}
(one launch = one ``lax.scan`` of K fused PPO updates) on the jit and
shard_map tiers, in the small-unroll Ocean regime where per-update dispatch
and host sync dominate. K=1 is the per-update-dispatch baseline the repo
trained with before the engine landed.

  PYTHONPATH=src python benchmarks/bench_engine.py --quick
  PYTHONPATH=src python benchmarks/bench_engine.py --devices 8   # shard_map DP=8

Writes BENCH_engine.json: the SPS grid, the K16/K1 speedups (acceptance:
≥ 1.5× on ≥ 2 envs), and the shard_map seed-match parity (max |Δparam| vs
the single-device run).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build_engine(env_cls, tcfg, backend, num_shards=1, seed=0):
    import jax
    from repro.core.emulation import Emulated
    from repro.models.policy import OceanPolicy
    from repro.rl.distributions import Dist
    from repro.rl.engine import TrainEngine
    em = Emulated(env_cls())
    dist = Dist("categorical", nvec=em.act_spec.nvec)
    pol = OceanPolicy(em.obs_spec.total, dist.nvec, hidden=32,
                      num_outputs=dist.num_outputs)
    return TrainEngine(em, pol, tcfg, dist, key=jax.random.PRNGKey(seed),
                       backend=backend, kernel_mode="ref",
                       num_shards=num_shards)


def bench_one(env_cls, tcfg, backend, num_updates):
    import jax
    eng = build_engine(env_cls, tcfg, backend)
    eng.run(eng.K * eng.steps_per_update)            # warmup: compile K launch
    # tail launches compile a second program; warm it too when sizes differ
    tail = num_updates % eng.K
    if tail:
        eng.run(tail * eng.steps_per_update)
    jax.block_until_ready(eng.ts.params)
    t0 = time.perf_counter()
    hist, _ = eng.run(num_updates * eng.steps_per_update)
    jax.block_until_ready(eng.ts.params)
    dt = time.perf_counter() - t0
    assert len(hist) == num_updates
    return num_updates * eng.steps_per_update / dt


def shard_parity(env_cls, tcfg, updates=6):
    """Max |Δparam| between the S-device shard_map run and the seed-matched
    single-device S-block emulation."""
    import jax
    import numpy as np
    S = jax.device_count()
    single = build_engine(env_cls, tcfg, "jit", num_shards=S)
    single.run(updates * single.steps_per_update)
    sharded = build_engine(env_cls, tcfg, "shard_map")
    sharded.run(updates * sharded.steps_per_update)
    diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree.leaves(jax.device_get(single.ts.params)),
                             jax.tree.leaves(jax.device_get(sharded.ts.params)))]
    return {"devices": S, "updates": updates, "max_param_diff": max(diffs)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timed updates; skip K=64")
    ap.add_argument("--devices", type=int, default=0,
                    help="force host platform device count (shard_map tier)")
    ap.add_argument("--out", default="BENCH_engine.json")
    args = ap.parse_args(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count="
                                   f"{args.devices}")

    import jax
    from repro.configs.base import TrainConfig
    from repro.envs.ocean import Bandit, Squared

    envs = {"bandit": Bandit, "squared": Squared}
    ks = (1, 4, 16) if args.quick else (1, 4, 16, 64)
    backends = ["jit"]
    ndev = jax.device_count()
    if ndev > 1 or args.devices:
        backends.append("shard_map")

    def tcfg_for(k):
        return TrainConfig(num_envs=16, unroll_length=16, update_epochs=2,
                           num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                           updates_per_launch=k)

    num_updates = 64 if args.quick else 192
    results = []
    for env_name, env_cls in envs.items():
        for backend in backends:
            for k in ks:
                if backend == "shard_map" and k not in (1, 16):
                    continue          # trajectory endpoints only
                sps = bench_one(env_cls, tcfg_for(k), backend, num_updates)
                results.append({"env": env_name, "backend": backend, "K": k,
                                "sps": round(sps, 1)})
                print(f"bench_engine/{env_name}/{backend}/K{k},"
                      f"{num_updates * 256 / sps * 1e6:.0f},sps={sps:.0f}")

    speedups = {}
    for env_name in envs:
        row = {r["K"]: r["sps"] for r in results
               if r["env"] == env_name and r["backend"] == "jit"}
        speedups[env_name] = round(row[16] / row[1], 2)
        print(f"bench_engine/{env_name}/speedup_K16_over_K1,"
              f"0,x={speedups[env_name]:.2f}")

    parity = None
    if ndev > 1:
        parity = shard_parity(Bandit, tcfg_for(3))
        print(f"bench_engine/shard_parity,0,"
              f"max_param_diff={parity['max_param_diff']:.2e};"
              f"devices={parity['devices']}")

    out = {
        "meta": {"num_updates": num_updates, "devices": ndev,
                 "steps_per_update": 256, "quick": bool(args.quick),
                 "config": {"num_envs": 16, "unroll_length": 16,
                            "update_epochs": 2, "num_minibatches": 2,
                            "hidden": 32}},
        "results": results,
        "speedup_K16_over_K1": speedups,
        "shard_parity": parity,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")
    from repro.telemetry import benchwatch
    benchwatch.record(
        "engine",
        {f"{r['env']}_{r['backend']}_K{r['K']}_sps": r["sps"]
         for r in results},
        meta={"quick": bool(args.quick), "devices": ndev})


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
