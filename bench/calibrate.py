"""Readings that the limits of ``correct`` are set from, for one cell, in
one process: the program's compared numbers over many seeds (the lower
reading), the control's (the reference in the next lower precision put in
the program's place) and each planted fault's (the upper reading).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--faults a,b]

No window is measured. Each reading is one JSON line on stdout and in
``bench/out/calibrate/<workload>.jsonl``.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import harness
    bm = harness.benchmark()
    w = harness.workload(bm, args.workload)
    dev = harness.device(w["chips"])
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    cfg = harness.config(w["config"])
    tfc = harness.traffic(w["traffic"])
    drv = harness.driver(w["config"])
    out_dir = harness.OUT / "calibrate"
    out_dir.mkdir(parents=True, exist_ok=True)
    log_path = out_dir / f"{args.workload}.jsonl"

    def emit(kind, seed, numbers, t0, **extra):
        rec = dict(kind=kind, workload=args.workload, seed=seed,
                   seconds=time.perf_counter() - t0, device=dev,
                   numbers=numbers, **extra)
        line = json.dumps(rec)
        print(line, flush=True)
        with open(log_path, "a") as log:
            log.write(line + "\n")

    def program(seed, fault=None):
        cell = drv.Cell(cfg, tfc, seed, w["chips"])
        if fault is None:
            cell.setup()
        else:
            with drv.FAULTS[fault]():
                cell.setup()
        peak = harness.memory_peak(w["chips"])
        cell.release()
        return cell, peak

    from bench import compare
    refs = {}

    def ref(cell):
        if cell.seed not in refs:
            refs[cell.seed] = cell.reference_side()
        return refs[cell.seed]

    for seed in args.seeds:
        t0 = time.perf_counter()
        cell, peak = program(seed)
        emit("program", seed, cell.numbers(ref(cell)), t0,
             memory_peak_bytes=peak)
    for seed in args.control_seeds:
        t0 = time.perf_counter()
        cell = drv.Cell(cfg, tfc, seed, w["chips"])
        emit("control", seed, compare.training_numbers(
            cell.reference_side(control=True), ref(cell)), t0)
    for fault in [f for f in args.faults.split(",") if f]:
        for seed in args.fault_seeds:
            t0 = time.perf_counter()
            cell, _ = program(seed, fault)
            emit(f"fault:{fault}", seed, cell.numbers(ref(cell)), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
