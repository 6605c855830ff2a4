"""Device time per phase of the Ocean PPO update, and the device's idle gaps
named by the program's spans.

The program marks the phases of its update with named scopes (``ppo_rollout``,
``ppo_env_step``, ``ppo_learn``, ``ppo_minibatch_rows``). The TPU trace does
not carry them: a device op event holds its HLO instruction's text. The
program maps each instruction of a compiled launch to its scope
(``repro.rl.engine.hlo_op_scopes``); this module reads that map from the
live executables of the process that ran the traced window, and counts an op
event toward a scope when it runs inside an execution of that module (its
``XLA Modules`` line) and its text begins with one of the scope's keys.

A scope's time is its own: ``ppo_learn`` leaves out the row reads that
``ppo_minibatch_rows`` holds, ``ppo_rollout`` the env step. The readers add
them up. The harness writes the traced window's profile under
``bench/out/trace/<cell>/``; the newest one is the run's.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

from bench import trace

MODULES_LINE = "XLA Modules"
NO_SPAN = "(no program span)"

_cache: dict = {}


def launch_scopes() -> Tuple[Tuple[str, ...], Dict[str, str]]:
    """The names of the live compiled modules that hold ``ppo_*`` scopes,
    and their instruction keys mapped to scopes. Empty where the program
    marks no scopes."""
    import jax
    try:
        from repro.rl.engine import hlo_op_scopes
    except ImportError:
        return (), {}
    names, keys = [], {}
    for ex in jax.devices()[0].client.live_executables():
        for mod in ex.hlo_modules():
            found = hlo_op_scopes(mod.to_string())
            if found:
                names.append(mod.name)
                keys.update(found)
    return tuple(names), keys


def latest_trace() -> Optional[str]:
    from bench import harness
    files = sorted((harness.OUT / "trace").rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return str(files[-1]) if files else None


def scope_time(profile, modules: Iterable[str],
               keys: Dict[str, str]) -> dict:
    """Device seconds per scope, summed over devices, of the op events
    inside the traced window that run within an execution of one of
    ``modules`` and begin with a key of ``keys``; and ``launches``, the
    executions of those modules that start in the window, summed over
    devices."""
    lo, hi = trace._window(profile)
    prefixes = tuple(f"{m}(" for m in modules)
    by_name: Dict[str, list] = {}
    for k, scope in keys.items():
        by_name.setdefault(k.split(" = ", 1)[0], []).append((k, scope))
    out = {"launches": 0, "seconds": {}}
    for plane in profile.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if MODULES_LINE not in lines or trace.OPS_LINE not in lines:
            continue
        runs = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in lines[MODULES_LINE].events
                if ev.name.startswith(prefixes)]
        out["launches"] += sum(lo <= s < hi for s, _ in runs)
        j = 0
        for ev in lines[trace.OPS_LINE].events:
            s = ev.start_ns
            while j < len(runs) and runs[j][1] <= s:
                j += 1
            if j == len(runs) or s < runs[j][0]:
                continue
            e = min(s + ev.duration_ns, hi)
            s = max(s, lo)
            if e <= s:
                continue
            text = ev.name
            for k, scope in by_name.get(text.split(" = ", 1)[0], ()):
                if text.startswith(k):
                    out["seconds"][scope] = (out["seconds"].get(scope, 0.0)
                                             + (e - s) * 1e-9)
                    break
    return out


def reading() -> Optional[dict]:
    """``scope_time`` of the newest traced window with this process's
    scope map, computed once per trace."""
    path = latest_trace()
    if path is None:
        return None
    if path not in _cache:
        modules, keys = launch_scopes()
        _cache[path] = (scope_time(trace.load(path), modules, keys)
                        if keys else None)
    return _cache[path]


def ms_per_launch(*scopes: str) -> Optional[float]:
    """Device ms per launch per chip of the ops under ``scopes``. One launch
    is one update where the engine launches one update at a time (K = 1),
    as the benchmark's Ocean cells do."""
    r = reading()
    if not r or not r["launches"]:
        return None
    s = sum(r["seconds"].get(x, 0.0) for x in scopes)
    return 1e3 * s / r["launches"] if s > 0 else None


def idle_by_span(profile, names: Iterable[str], top: int = 10) -> list:
    """The ``top`` longest gaps in which the first device ran nothing, each
    named by the innermost program span (one of ``names``) on the host
    plane that covers the gap's middle, or ``NO_SPAN``, and summed by that
    name. The program's spans reach the host plane where the tracer
    mirrors them into the profiler."""
    names = set(names)
    lo, hi = trace._window(profile)
    per_dev = trace.device_ops(profile, lo, hi)
    host = [h for h in trace._host_events(profile, lo, hi) if h.name in names]
    gaps = trace.idle_gaps(per_dev[min(per_dev)], host, lo, hi, top)
    return [[NO_SPAN if k == "(no host event)" else k, v] for k, v in gaps]
