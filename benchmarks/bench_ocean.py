"""Paper §4 claim: each Ocean env solved (score > 0.9) in roughly 30k
interactions with one barely-tuned hyperparameter set; whole suite in a
coffee break on one CPU core."""
from __future__ import annotations

import time

from repro.configs.base import TrainConfig
from repro.envs.ocean import OCEAN
from repro.rl.trainer import Trainer

TCFG = TrainConfig(num_envs=64, unroll_length=64, update_epochs=4,
                   num_minibatches=4, learning_rate=1e-3, gamma=0.95,
                   ent_coef=0.01)

BUDGET = {"squared": 300_000, "password": 300_000, "stochastic": 200_000,
          "memory": 500_000, "multiagent": 150_000, "spaces": 200_000,
          "bandit": 150_000, "continuous": 400_000}


def run():
    rows = []
    for name, cls in OCEAN.items():
        t0 = time.perf_counter()
        tr = Trainer(cls(), TCFG, hidden=64, recurrent=(name == "memory"),
                     kernel_mode="ref")
        m = tr.train(BUDGET[name], target_score=0.9)
        rows.append({"env": name, "score": m["score"],
                     "env_steps": m["env_steps"],
                     "solved": m["score"] >= 0.9,
                     "wall_s": time.perf_counter() - t0})
    return rows


def main():
    from repro.telemetry import benchwatch
    rows = run()
    for r in rows:
        print(f"bench_ocean/{r['env']},{r['wall_s']*1e6:.0f},"
              f"score={r['score']:.3f};steps={r['env_steps']};"
              f"solved={int(r['solved'])}")
    benchwatch.record(
        "ocean",
        {f"{r['env']}_sps": r["env_steps"] / max(r["wall_s"], 1e-9)
         for r in rows},
        acceptance={f"{r['env']}_solved": bool(r["solved"]) for r in rows})


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
