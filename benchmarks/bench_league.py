"""Policy-League arena benchmark: vmapped K-opponent pool vs sequential
per-opponent dispatch.

The arena's pitch is the engine's pitch applied to evaluation: a K-opponent
pool stacked along a leading param axis evaluates as ONE vmapped/jitted
rollout scan instead of K Python-dispatched matches. In the small-model
Ocean regime per-dispatch overhead dominates, so the fused launch should
win by a wide margin — acceptance is ≥ 3× at K = 8 (both paths warmed, so
the comparison is pure dispatch + batching, not compile time).

  PYTHONPATH=src python benchmarks/bench_league.py --quick

Writes BENCH_league.json: per-K timings, the K=8 speedup vs acceptance,
an Elo sanity record (planted ordering recovered from noisy matches), and
the match-count bookkeeping.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build_arena(num_envs, steps, hidden=64):
    from repro.envs.ocean import Duel
    from repro.league import Arena
    from repro.rl.trainer import ocean_policy_stack
    em, dist, pol = ocean_policy_stack(Duel(), hidden=hidden)
    return pol, Arena(em, pol, dist, num_envs=num_envs, steps=steps)


def bench_pool(arena, pol, K, repeats):
    """Warmed wall-time of one learner-vs-K-pool evaluation, both paths."""
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(0)
    pa = pol.init(jax.random.fold_in(key, 1000))
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[pol.init(jax.random.fold_in(key, i)) for i in range(K)])

    # warm both programs (compile excluded from timing)
    arena.vs_pool(pa, stacked, key)
    arena.vs_pool_sequential(pa, stacked, key)

    def timed(fn):
        # min over repeats: the least-noise estimate of the true cost on a
        # shared machine (both paths measured the same way)
        best, out = float("inf"), None
        for r in range(repeats):
            t0 = time.perf_counter()
            out = fn(jax.random.fold_in(key, r))
            best = min(best, time.perf_counter() - t0)
        return best, out

    t_vmap, out_v = timed(lambda k: arena.vs_pool(pa, stacked, k))
    t_seq, out_s = timed(lambda k: arena.vs_pool_sequential(pa, stacked, k))

    # same keys ⇒ the two paths must agree exactly
    for a, b in zip(out_v, out_s):
        assert abs(a["outcome"] - b["outcome"]) < 1e-6, (a, b)
    return t_vmap, t_seq


def elo_sanity():
    """The ranker recovers 5 planted skill tiers from noisy outcomes."""
    import numpy as np
    from repro.league import Ranker
    skills = [-2.0, -1.0, 0.0, 1.0, 2.0]
    rng = np.random.default_rng(7)
    ranker = Ranker()
    for _ in range(400):
        a, b = rng.choice(5, size=2, replace=False)
        p_a = 1.0 / (1.0 + np.exp(-(skills[a] - skills[b])))
        ranker.update(int(a), int(b), float(rng.random() < p_a))
    return {"planted_order": [4, 3, 2, 1, 0], "recovered": ranker.rank(),
            "ok": ranker.rank() == [4, 3, 2, 1, 0]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller arena + fewer repeats (CI)")
    ap.add_argument("--out", default="BENCH_league.json")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src"))
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    # the paper's small-env regime: per-match compute is tiny, so dispatch
    # count is the cost — exactly where the fused pool launch pays off
    num_envs = 8
    steps = 40 if args.quick else 64
    repeats = 5 if args.quick else 10

    pol, arena = build_arena(num_envs, steps)
    results = {}
    for K in (2, 4, 8):
        t_vmap, t_seq = bench_pool(arena, pol, K, repeats)
        results[f"K{K}"] = {
            "vmapped_s": round(t_vmap, 4), "sequential_s": round(t_seq, 4),
            "speedup": round(t_seq / t_vmap, 2),
            "matches": K, "envs_per_match": num_envs, "steps": steps,
        }
        print(f"K={K}: vmapped {t_vmap*1e3:7.1f} ms  "
              f"sequential {t_seq*1e3:7.1f} ms  "
              f"speedup {t_seq / t_vmap:5.2f}x")

    elo = elo_sanity()
    print(f"elo planted-order recovery: {'OK' if elo['ok'] else 'FAILED'}")

    sp8 = results["K8"]["speedup"]
    out = {
        "bench": "league_arena",
        "acceptance": {"metric": "K8 vmapped pool vs sequential dispatch",
                       "threshold_x": 3.0, "measured_x": sp8,
                       "ok": sp8 >= 3.0},
        "results": results,
        "elo_sanity": elo,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}; K8 speedup {sp8}x "
          f"(acceptance >= 3x: {'OK' if sp8 >= 3.0 else 'FAILED'})")
    from repro.telemetry import benchwatch
    benchwatch.record(
        "league",
        {f"{k}_speedup": v["speedup"] for k, v in results.items()},
        acceptance={"k8_speedup_ge_3x": sp8 >= 3.0,
                    "elo_sanity": bool(elo["ok"])},
        meta={"quick": bool(args.quick)})
    if not out["acceptance"]["ok"] or not elo["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
