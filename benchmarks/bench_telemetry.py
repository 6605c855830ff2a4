"""Telemetry overhead: enabled-vs-disabled end-to-end PPO SPS.

Two tiers train the same bandit MDP twice each — spans + registry OFF
(the shipped default) and ON (``telemetry.enable`` with a run dir, the
``--run-dir`` path) — and the bench records the relative SPS cost:

  * ``jit``  — the fused single-process tier: the worst case for span
               overhead, since there is no host latency to hide behind
               (every span brackets a dispatch that is itself fast).
  * ``host`` — the bridged first-finisher tier: spans wrap real recv/send
               waits, plus the proc-stat path exercised by thread workers.

SPS is measured from the *second* update onward (the first is XLA
compilation) and each cell takes the best of ``--repeats`` runs, which
rejects transient machine noise without hiding a systematic slowdown.

Acceptance (``overhead <= 3%``) is machine-aware, same contract as the
other BENCH_*.json files: on a single-core box the enabled run's flush
I/O and the trainer time-slice the only CPU, so the criterion is only
asserted when ``cores >= 2``; measured overheads are recorded honestly
either way. The enabled jit cell's spans are also exported as a sample
Chrome trace (``--trace-out``) for Perfetto.

  PYTHONPATH=src python benchmarks/bench_telemetry.py --quick

Writes BENCH_telemetry.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def timed_sps(run_fn, spu: int):
    """(sps, updates) with the compile-dominated first update excluded."""
    stamps = []
    run_fn(lambda u, md: stamps.append(time.perf_counter()))
    if len(stamps) < 2:
        return 0.0, len(stamps)
    return (len(stamps) - 1) * spu / (stamps[-1] - stamps[0]), len(stamps)


def make_engine(tier, tcfg):
    import jax
    if tier == "host":
        from repro.bridge import make_host_engine
        from repro.envs.ocean_host import HostBandit
        return make_host_engine(HostBandit, tcfg, hidden=32,
                                kernel_mode="ref")
    from repro.envs.ocean import Bandit
    from repro.rl.engine import TrainEngine
    from repro.rl.trainer import ocean_policy_stack
    em, dist, policy = ocean_policy_stack(Bandit(), hidden=32,
                                          recurrent=False, conv=None)
    return TrainEngine(em, policy, tcfg, dist, key=jax.random.PRNGKey(0),
                       backend=tier, kernel_mode="ref", checkpoint_dir=None)


def bench_cell(tier, tcfg, updates, enabled, run_dir, repeats):
    """Best-of-``repeats`` SPS for one (tier, telemetry on/off) cell."""
    from repro import telemetry
    best, n_seen = 0.0, 0
    for _ in range(repeats):
        eng = make_engine(tier, tcfg)
        spu = eng.steps_per_update
        try:
            if enabled:
                telemetry.enable(run_dir=run_dir)
            sps, n = timed_sps(
                lambda cb: eng.run(total_steps=spu * updates, on_update=cb),
                spu)
        finally:
            if enabled:
                telemetry.disable()       # flushes spans to run_dir
            eng.close()
        best, n_seen = max(best, sps), n
    return best, n_seen


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timed updates (CI smoke)")
    ap.add_argument("--out", default="BENCH_telemetry.json")
    ap.add_argument("--trace-out", default="",
                    help="sample Chrome trace from the enabled jit cell "
                         "(default <out dir>/docs/trace_sample.json)")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)

    from repro.configs.base import TrainConfig
    from repro.telemetry.__main__ import export_trace

    cores = os.cpu_count() or 1
    updates = 8 if args.quick else 16
    base = dict(num_envs=16, unroll_length=32, update_epochs=2,
                num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                checkpoint_every=0)
    trace_out = args.trace_out or os.path.join(
        os.path.dirname(os.path.abspath(args.out)), "docs",
        "trace_sample.json")
    os.makedirs(os.path.dirname(os.path.abspath(trace_out)), exist_ok=True)
    print(f"cores={cores}, updates={updates}, repeats={args.repeats}")

    cells = {}
    overheads = {}
    with tempfile.TemporaryDirectory() as tmp:
        for tier in ("jit", "host"):
            run_dir = os.path.join(tmp, tier)
            off, n = bench_cell(tier, TrainConfig(**base), updates,
                                enabled=False, run_dir=None,
                                repeats=args.repeats)
            on, _ = bench_cell(tier, TrainConfig(**base), updates,
                               enabled=True, run_dir=run_dir,
                               repeats=args.repeats)
            ovh = (off - on) / max(off, 1e-9)
            cells[tier] = {"sps_disabled": round(off, 1),
                           "sps_enabled": round(on, 1),
                           "updates": n,
                           "overhead_pct": round(100 * ovh, 2)}
            overheads[tier] = ovh
            print(f"bench_telemetry/{tier},off={off:.0f},on={on:.0f},"
                  f"overhead={100 * ovh:+.2f}%")
            if tier == "jit":
                n_ev = export_trace(run_dir, trace_out)
                print(f"  sample trace: {n_ev} events -> {trace_out}")

    worst = max(overheads.values())
    multicore = cores >= 2
    ok = worst <= 0.03
    if not multicore:
        print("=" * 72)
        print("WARNING: SINGLE-CORE MACHINE — ACCEPTANCE CRITERIA NOT "
              "APPLICABLE")
        print("  The enabled run's span flush and the trainer time-slice")
        print("  the only CPU, and run-to-run SPS noise on a contended")
        print("  single core exceeds the 3% criterion. Measured overheads")
        print("  are recorded honestly; the <=3% bound is not asserted.")
        print("  acceptance.acceptance_applicable=false in the JSON —")
        print("  re-run on a multicore machine (CI runners) for numbers")
        print("  the criterion applies to.")
        print("=" * 72)
    out = {
        "meta": {
            "updates": updates, "quick": bool(args.quick),
            "repeats": args.repeats, "cores": cores,
            "python": sys.version.split()[0],
            "tcfg": {k: base[k] for k in ("num_envs", "unroll_length",
                                          "update_epochs",
                                          "num_minibatches")},
            "sps_excludes_first_update": True,
            "cells_take_best_of_repeats": True,
        },
        "cells": cells,
        "acceptance": {
            "acceptance_applicable": multicore,
            "worst_overhead_pct": round(100 * worst, 2),
            "overhead_le_3pct": ok if multicore else None,
        },
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")
    from repro.telemetry import benchwatch
    benchwatch.record(
        "telemetry",
        {f"{tier}_{mode}_sps": cells[tier][f"sps_{mode}"]
         for tier in cells for mode in ("disabled", "enabled")},
        acceptance={"acceptance_applicable": multicore,
                    "overhead_le_3pct": bool(ok) if multicore else None},
        meta={"updates": updates, "quick": bool(args.quick)})
    if multicore and not ok:
        print(f"FAIL: telemetry overhead {100 * worst:.2f}% > 3% on a "
              f"multicore machine")
        return 1
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    raise SystemExit(main())
