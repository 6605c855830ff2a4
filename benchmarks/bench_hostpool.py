"""HostPool thread-vs-proc SPS: where does killing the GIL pay?

Two workload cells over the same first-finisher pool (M = 16, N = 8):

  * ``cpu``   — ``HostCrafterLite`` with its pure-Python LCG burn calibrated
                to ~2 ms/step. Threads serialize on the GIL; ``proc``
                (shared-memory spawn workers) parallelizes across cores.
                Acceptance (multicore only): proc ≥ 2× thread async SPS.
  * ``sleep`` — the same env with a GIL-*releasing* ``time.sleep`` step and
                no burn. Threads are already optimal here; proc must not
                regress materially. Acceptance: proc ≥ 0.85× thread.

The report is machine-aware: the ≥ 2× criterion is *physically impossible*
on a single core (processes cannot run in parallel), so ``acceptance``
records ``multicore_criteria_applicable`` and only asserts the ratios when
``cores >= 2`` — CI's multicore runners regenerate the artifact and enforce
them for real. Slab section sizes and the busy-wait ladder parameters are
recorded alongside the numbers so regressions are attributable.

  PYTHONPATH=src python benchmarks/bench_hostpool.py --quick

Writes BENCH_hostpool.json.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np


def calibrate_work(target_ms: float = 2.0) -> tuple:
    """LCG iterations per step for ~``target_ms`` of pure-Python burn on
    this machine, plus the step time measured at that setting."""
    from repro.envs.ocean_host import HostCrafterLite
    probe = HostCrafterLite(work=20_000)
    probe.reset(0)
    t0 = time.perf_counter()
    for t in range(20):
        probe.step(t % 6)
    per_iter = (time.perf_counter() - t0) / 20 / 20_000
    work = max(1000, int(target_ms / 1e3 / per_iter))
    env = HostCrafterLite(work=work)
    env.reset(0)
    t0 = time.perf_counter()
    for t in range(20):
        env.step(t % 6)
    return work, (time.perf_counter() - t0) / 20 * 1e3


def pool_sps(env_fn, M: int, N: int, steps: int, backend: str,
             spin=None) -> float:
    """SPS of a bare recv→send loop (no policy) over ``HostVecEnv``."""
    from repro.bridge import wrap
    venv = wrap(env_fn, num_envs=M, batch_size=N, seed=0,
                recv_timeout=120.0, backend=backend, spin=spin)
    try:
        _obs, _r, _d, _i, ids = venv.recv()
        t0 = time.perf_counter()
        for _ in range(steps):
            venv.send(np.zeros((N, 1), np.int64), ids)
            _obs, _r, _d, _i, ids = venv.recv()
        return steps * N / (time.perf_counter() - t0)
    finally:
        venv.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timed steps (CI smoke)")
    ap.add_argument("--out", default="BENCH_hostpool.json")
    ap.add_argument("--target-step-ms", type=float, default=2.0,
                    help="calibrated pure-Python step cost for the cpu cell")
    args = ap.parse_args(argv)

    from repro.core import shm
    from repro.envs.ocean_host import HostCrafterLite

    M, N = 16, 8
    steps = 30 if args.quick else 120
    cores = os.cpu_count() or 1
    spin = shm.default_spin(workers=M)

    work, step_ms = calibrate_work(args.target_step_ms)
    print(f"calibrated work={work} (~{step_ms:.2f} ms/step), cores={cores}")

    cells = {}
    cpu_fn = functools.partial(HostCrafterLite, work=work)
    sleep_fn = functools.partial(HostCrafterLite, work=0,
                                 sleep_ms=args.target_step_ms)
    for cell, fn in (("cpu", cpu_fn), ("sleep", sleep_fn)):
        res = {}
        for backend in ("thread", "proc"):
            res[backend] = pool_sps(fn, M, N, steps, backend, spin=spin)
            print(f"bench_hostpool/{cell}_{backend},"
                  f"{1e6 / res[backend]:.1f},sps={res[backend]:.0f}")
        res["proc_over_thread"] = res["proc"] / res["thread"]
        print(f"  {cell}: proc/thread = {res['proc_over_thread']:.2f}x")
        cells[cell] = {k: round(v, 2) for k, v in res.items()}

    multicore = cores >= 2
    cpu_ok = cells["cpu"]["proc_over_thread"] >= 2.0
    sleep_ok = cells["sleep"]["proc_over_thread"] >= 0.85
    if not multicore:
        print("=" * 72)
        print("WARNING: SINGLE-CORE MACHINE — ACCEPTANCE CRITERIA NOT "
              "APPLICABLE")
        print("  Both proc-vs-thread criteria need real parallelism: on one")
        print("  core the proc backend cannot beat threads by construction")
        print("  (cpu cell), and the spin/flag handshake itself has nowhere")
        print("  to run (sleep cell). Measured ratios are recorded honestly;")
        print("  neither is asserted. acceptance.acceptance_applicable=false")
        print("  in the JSON — re-run on a multicore machine (CI runners)")
        print("  for numbers the >=2x / >=0.85x criteria apply to.")
        print("=" * 72)
    layout = shm.SlabLayout(
        shm.SlabSpec(obs_shape=(8 * 8 + 4,), act_shape=(1,)), M)
    out = {
        "meta": {
            "M": M, "N": N, "steps": steps, "quick": bool(args.quick),
            "cores": cores,
            "python": sys.version.split()[0],
            "cpu_cell": {"work": work, "measured_step_ms":
                         round(step_ms, 3)},
            "sleep_cell": {"sleep_ms": args.target_step_ms},
            "spin": {"spin": spin.spin, "yields": spin.yields,
                     "min_sleep_us": spin.min_sleep_us,
                     "max_sleep_us": spin.max_sleep_us,
                     "idle_sleep_us": spin.idle_sleep_us,
                     "idle_after_s": spin.idle_after_s},
            "slab_bytes": layout.slab_bytes(),
            "slab_total_bytes": layout.nbytes,
        },
        "cells": cells,
        "acceptance": {
            # both criteria need real parallelism: on one core the proc
            # backend cannot beat threads by construction (cpu cell), and
            # the flag handshake itself has nowhere to run (sleep cell).
            # acceptance_applicable is THE machine-applicability bit readers
            # should key on (multicore_criteria_applicable kept as an alias
            # for earlier consumers of this artifact)
            "acceptance_applicable": multicore,
            "multicore_criteria_applicable": multicore,
            "cpu_proc_ge_2x_thread": cpu_ok if multicore else None,
            "sleep_proc_ge_0p85x_thread": sleep_ok if multicore else None,
        },
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")
    from repro.telemetry import benchwatch
    benchwatch.record(
        "hostpool",
        {f"{cell}_{bk}_sps": cells[cell][bk]
         for cell in cells for bk in ("thread", "proc")},
        acceptance={
            "acceptance_applicable": multicore,
            "cpu_proc_ge_2x_thread": cpu_ok if multicore else None,
            "sleep_proc_ge_0p85x_thread": sleep_ok if multicore else None},
        meta={"quick": bool(args.quick), "M": M, "N": N})
    if multicore and not cpu_ok:
        print("FAIL: cpu cell proc < 2x thread on a multicore machine")
        return 1
    if multicore and not sleep_ok:
        print("FAIL: sleep cell proc < 0.85x thread")
        return 1
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    raise SystemExit(main())
