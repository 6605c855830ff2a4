"""Async actor–learner SPS vs the host and jit tiers under actor jitter.

Three cells train the same bandit MDP with the same policy/learner math:

  * ``jit``   — the fused single-process tier on the jax-native ``Bandit``;
                no host latency is physically possible here, so this is the
                no-jitter ceiling.
  * ``host``  — the bridged first-finisher tier on ``HostBandit`` with
                ~``jitter_ms`` of lognormal per-step host latency: the
                learner still waits for a full batch of N envs each update.
  * ``async`` — the actor–learner tier (2 spawn actors) on ``Bandit`` with
                ``actor_jitter_ms = jitter_ms`` injected in the actor loop:
                actors absorb the latency while the learner consumes
                fragments at its own rate.

SPS is measured from the *second* update onward (the first update's wall
time is dominated by XLA compilation in every tier).

The report is machine-aware, same contract as BENCH_hostpool.json: hiding
actor latency needs the actors and the learner to actually run in parallel,
so the ``async >= 1.3x host`` criterion is only asserted when ``cores >= 2``
— ``acceptance.acceptance_applicable`` records the machine's verdict and the
measured ratios are written honestly either way.

  PYTHONPATH=src python benchmarks/bench_actor.py --quick

Writes BENCH_actor.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def timed_sps(run_fn, spu: int):
    """(sps, updates) with the compile-dominated first update excluded."""
    stamps = []
    hist = run_fn(lambda u, md: stamps.append(time.perf_counter()))
    if len(stamps) < 2:
        return 0.0, len(stamps)
    return (len(stamps) - 1) * spu / (stamps[-1] - stamps[0]), len(stamps)


def bench_jit(tcfg, updates: int):
    import jax
    from repro.envs.ocean import Bandit
    from repro.rl.engine import TrainEngine
    from repro.rl.trainer import ocean_policy_stack
    em, dist, policy = ocean_policy_stack(Bandit(), hidden=32,
                                          recurrent=False, conv=None)
    eng = TrainEngine(em, policy, tcfg, dist, key=jax.random.PRNGKey(0),
                      backend="jit", kernel_mode="ref", checkpoint_dir=None)
    spu = eng.steps_per_update
    try:
        return timed_sps(lambda cb: eng.run(total_steps=spu * updates,
                                            on_update=cb), spu)
    finally:
        eng.close()


def bench_host(tcfg, updates: int, jitter_ms: float):
    import functools
    from repro.bridge import make_host_engine
    from repro.envs.ocean_host import HostBandit
    fn = functools.partial(HostBandit, jitter_ms=jitter_ms)
    eng = make_host_engine(fn, tcfg, hidden=32, kernel_mode="ref")
    spu = eng.steps_per_update
    try:
        return timed_sps(lambda cb: eng.run(total_steps=spu * updates,
                                            on_update=cb), spu)
    finally:
        eng.close()


def bench_async(tcfg, updates: int):
    import jax
    from repro.envs.ocean import Bandit
    from repro.rl.engine import TrainEngine
    from repro.rl.trainer import ocean_policy_stack
    em, dist, policy = ocean_policy_stack(Bandit(), hidden=32,
                                          recurrent=False, conv=None)
    eng = TrainEngine(em, policy, tcfg, dist, key=jax.random.PRNGKey(0),
                      backend="async", kernel_mode="ref", checkpoint_dir=None)
    spu = eng.steps_per_update
    try:
        sps, n = timed_sps(lambda cb: eng.run(total_steps=spu * updates,
                                              on_update=cb), spu)
        return sps, n, eng.rollouts.layout.nbytes
    finally:
        eng.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timed updates (CI smoke)")
    ap.add_argument("--out", default="BENCH_actor.json")
    ap.add_argument("--jitter-ms", type=float, default=2.0,
                    help="injected per-step actor/env host latency")
    args = ap.parse_args(argv)

    from repro.configs.base import TrainConfig

    cores = os.cpu_count() or 1
    updates = 4 if args.quick else 8
    base = dict(num_envs=16, unroll_length=32, update_epochs=2,
                num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                checkpoint_every=0)
    print(f"cores={cores}, updates={updates}, "
          f"jitter={args.jitter_ms:.1f} ms/step")

    cells = {}
    sps, n = bench_jit(TrainConfig(**base), updates)
    cells["jit"] = {"sps": round(sps, 1), "updates": n, "jitter_ms": 0.0}
    print(f"bench_actor/jit,{1e6 / max(sps, 1e-9):.2f},sps={sps:.0f}")

    sps, n = bench_host(TrainConfig(**base), updates, args.jitter_ms)
    cells["host"] = {"sps": round(sps, 1), "updates": n,
                     "jitter_ms": args.jitter_ms}
    print(f"bench_actor/host,{1e6 / max(sps, 1e-9):.2f},sps={sps:.0f}")

    acfg = TrainConfig(**base, num_actors=2,
                       actor_jitter_ms=args.jitter_ms)
    sps, n, slab = bench_async(acfg, updates)
    cells["async"] = {"sps": round(sps, 1), "updates": n,
                      "jitter_ms": args.jitter_ms, "num_actors": 2}
    print(f"bench_actor/async,{1e6 / max(sps, 1e-9):.2f},sps={sps:.0f}")

    ratio = cells["async"]["sps"] / max(cells["host"]["sps"], 1e-9)
    print(f"  async/host = {ratio:.2f}x, "
          f"async/jit = {cells['async']['sps'] / max(cells['jit']['sps'], 1e-9):.2f}x")

    multicore = cores >= 2
    ok = ratio >= 1.3
    if not multicore:
        print("=" * 72)
        print("WARNING: SINGLE-CORE MACHINE — ACCEPTANCE CRITERIA NOT "
              "APPLICABLE")
        print("  Hiding actor latency needs the actors and the learner to")
        print("  run in parallel; on one core they time-slice and the slab")
        print("  handshake itself competes for the only CPU. Measured")
        print("  ratios are recorded honestly; the >=1.3x criterion is not")
        print("  asserted. acceptance.acceptance_applicable=false in the")
        print("  JSON — re-run on a multicore machine (CI runners) for")
        print("  numbers the criterion applies to.")
        print("=" * 72)
    out = {
        "meta": {
            "updates": updates, "quick": bool(args.quick), "cores": cores,
            "python": sys.version.split()[0],
            "jitter_ms": args.jitter_ms,
            "tcfg": {k: base[k] for k in ("num_envs", "unroll_length",
                                          "update_epochs",
                                          "num_minibatches")},
            "async": {"num_actors": 2, "shards_per_actor": 1,
                      "actor_slots": 2, "slab_bytes": slab},
            "sps_excludes_first_update": True,
        },
        "cells": cells,
        "acceptance": {
            # the criterion needs real parallelism (see the warning above);
            # single-core machines record the measured ratio, assert nothing
            "acceptance_applicable": multicore,
            "async_over_host": round(ratio, 3),
            "async_ge_1p3x_host": ok if multicore else None,
        },
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")
    from repro.telemetry import benchwatch
    benchwatch.record(
        "actor",
        {f"{tier}_sps": cells[tier]["sps"] for tier in cells},
        acceptance={"acceptance_applicable": multicore,
                    "async_ge_1p3x_host": ok if multicore else None},
        meta={"updates": updates, "quick": bool(args.quick),
              "jitter_ms": args.jitter_ms})
    if multicore and not ok:
        print("FAIL: async < 1.3x host under jitter on a multicore machine")
        return 1
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    raise SystemExit(main())
