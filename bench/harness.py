"""One run of one benchmark cell, driven by data.

``BENCHMARK.json`` names each cell's configuration and traffic. The
harness finds everything else by those names:

  bench/configs/<config>/config.json   the configuration as it is run
  bench/configs/<config>/driver.py     ``Cell``: drives the program
  bench/configs/<config>/reference.py  the plain reference
  bench/traffic/<traffic>.json         the cell's traffic and its limits
  bench/metrics/<metric>.py            ``read(ctx)`` of one per-layer metric

A run: check the device, set up (build, compile, first steps), measure
``seconds`` of work (``trace=0``: the end-to-end metrics) or profile a
short window (``trace=1``: the per-layer metrics), read the peak memory,
free the program's state, run the reference and compare.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
TRACE_SECONDS = 5.0


class NoChip(RuntimeError):
    pass


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _modname(*parts) -> str:
    return "bench_" + "_".join(
        "".join(c if c.isalnum() else "_" for c in p) for p in parts)


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "configs" / name / "config.json").read_text())


def traffic(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def driver(name: str, bench: Path = BENCH):
    return load_module(bench / "configs" / name / "driver.py",
                       _modname("driver", name))


def reader(metric: str, bench: Path = BENCH):
    return load_module(bench / "metrics" / f"{metric}.py",
                       _modname("metric", metric))


def cell_metrics(bm: dict, section: str, cell: str) -> list:
    """The metrics of ``section`` that cell ``cell`` reports."""
    return [m for m in bm[section]
            if "workloads" not in m or cell in m["workloads"]]


def device(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"JAX found no TPU: platform {d.platform!r} with "
                     f"{len(devs)} device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _window_spans(lo_ns: int, hi_ns: int) -> list:
    from repro import telemetry
    tracer = telemetry.get_tracer()
    recs = tracer.records() if tracer is not None else []
    return [r for r in recs if r.ts_ns >= lo_ns and r.ts_ns + r.dur_ns
            <= hi_ns]


def traced_window(cell, name: str, seconds: float):
    """Profile ``seconds`` of the cell's work with the program's spans on;
    returns (work, elapsed, steps, trace reduction, spans in the
    window)."""
    import jax
    from repro import telemetry
    from bench import trace
    tdir = OUT / "trace" / name
    shutil.rmtree(tdir, ignore_errors=True)
    telemetry.enable()
    jax.profiler.start_trace(str(tdir))
    try:
        lo = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            work, elapsed, steps = cell.window(seconds)
        hi = time.monotonic_ns()
    finally:
        jax.profiler.stop_trace()
    spans = _window_spans(lo, hi)
    telemetry.disable()
    files = sorted(tdir.rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {tdir}")
    red = trace.reduce(trace.load(str(files[-1])),
                       cell.info().get("kernels", {}))
    return work, elapsed, steps, red, spans


def run(name: str, seed: int, seconds: float, traced: bool, *,
        t_start: float = None, require_tpu: bool = True,
        traffic_override: dict = None, bench: Path = BENCH,
        root: Path = ROOT) -> dict:
    """One run of cell ``name``; returns the result object. The traffic
    override serves tests that run a cell at a small size on the CPU."""
    t_start = time.perf_counter() if t_start is None else t_start
    bm = benchmark(root)
    w = workload(bm, name)
    chips = w["chips"]
    dev = device(chips, require_tpu)
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    tfc = dict(traffic(w["traffic"], bench), **(traffic_override or {}))
    cfg = config(w["config"], bench)
    cell = driver(w["config"], bench).Cell(cfg, tfc, seed, chips)
    t_cell = time.perf_counter()
    cell.setup()
    setup_s = time.perf_counter() - t_start
    phases = dict(start_to_cell=t_cell - t_start,
                  **getattr(cell, "phases", {}))
    print("setup " + " ".join(f"{k} {v:.3f}" for k, v in phases.items()),
          file=sys.stderr)

    metrics, breakdown = {}, None
    if not traced:
        work, elapsed, steps = cell.window(seconds)
        iv = sorted(getattr(cell, "intervals", []))
        if iv:
            print(f"window {steps} steps in {elapsed:.3f} s; between "
                  f"launches median {1e3 * iv[len(iv) // 2]:.3f} ms, max "
                  f"{1e3 * iv[-1]:.3f} ms", file=sys.stderr)
        for m in cell_metrics(bm, "end_to_end", name):
            if m["name"] == "setup_s":
                val = setup_s
            elif m["name"] == f"{cell.unit}_per_s":
                val = work / elapsed
            else:
                continue
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        from bench import flops
        work, elapsed, steps, red, spans = traced_window(
            cell, name, min(seconds, TRACE_SECONDS))
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        ctx = {"trace": red, "spans": spans, "work": work,
               "elapsed": elapsed, "rate": work / elapsed, "chips": chips,
               "peak": flops.peaks(dev["kind"]),
               "info": cell.info()}
        for m in cell_metrics(bm, "per_layer", name):
            val = reader(m["name"], bench).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
    dev["memory_peak_bytes"] = memory_peak(chips)
    cell.release()

    numbers = cell.numbers()
    limits = tfc["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()
              if k in limits}
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    out = {"correct": correct, "attempted": int(steps), "failed": 0,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def report(result: dict) -> None:
    """The check lines on stderr, then the result as the last stdout
    line."""
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
