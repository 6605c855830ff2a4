"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time, time per named kernel, collective time that no
compute hides, the ops that took most time, and the longest idle gaps with
what the host was doing in each.

Only events inside the host annotation ``WINDOW`` (the traced window the
harness opens with ``jax.profiler.TraceAnnotation``) count; device events
are clipped to it. A device is a plane named ``/device:TPU:<n>``; its ops
are the events of its ``XLA Ops`` line.

    python bench/trace.py <file.xplane.pb>     # prints the reduction as JSON
"""
from __future__ import annotations

import json
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")


class Event(NamedTuple):
    start_ns: float
    end_ns: float
    name: str          # the HLO instruction's name, e.g. ``gae.2``
    text: str          # the whole event name (the HLO instruction) and
                       # its string stats, joined


def _text(ev) -> str:
    parts = [ev.name]
    for _k, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def _short(name: str) -> str:
    """``%gae.2 = f32[64,4096] custom-call(...)`` -> ``gae.2``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str):
    """The trace at ``path`` (``.xplane.pb``, or an XSpace text proto
    ``.pbtxt``) as ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData
    if str(path).endswith(".pbtxt"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def _window(profile) -> Tuple[float, float]:
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    raise ValueError(f"trace has no host annotation {WINDOW!r}")


def _host_events(profile, lo, hi) -> List[Event]:
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > lo and s < hi and ev.duration_ns > 0 \
                        and ev.name != WINDOW:
                    out.append(Event(s, e, ev.name, ev.name))
    return out


def device_ops(profile, lo, hi) -> Dict[int, List[Event]]:
    """Per device ordinal, its op events clipped to ``[lo, hi]``."""
    out: Dict[int, List[Event]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        evs = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e > s:
                    evs.append(Event(s, e, _short(ev.name), _text(ev)))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                 float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def _minus(a: List[Tuple[float, float]], b: List[Tuple[float, float]]):
    """Length of the union of ``a`` not covered by the union of ``b``."""
    a, b = union(a), union(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def kernel_pattern(name: str) -> str:
    """Matches the Pallas kernel wrapped by the jitted function ``name``
    (``%gae.2 = ... custom_call_target="tpu_custom_call"``), and not the
    ops that merely read its result."""
    return rf'^%?{re.escape(name)}(\.\d+)? = .*"tpu_custom_call"'


def reduce(profile, kernels: Optional[Dict[str, str]] = None,
           top: int = 10) -> dict:
    """The reduction of one trace. ``kernels`` maps a kernel's name to a
    regular expression searched in each op's whole text (its HLO
    instruction and string stats); an op that matches counts toward that
    kernel's time and calls. A Pallas kernel is a ``tpu_custom_call``
    instruction named after the jitted function that wraps it, so
    ``kernel_pattern`` gives the expression.

    Returns seconds, averaged over devices where a per-device figure is
    meant (``busy_s``, ``collective_s``), summed over devices for
    ``kernel_s`` and ``kernel_calls``."""
    lo, hi = _window(profile)
    per_dev = device_ops(profile, lo, hi)
    if not per_dev:
        raise ValueError("trace has no TPU device plane with XLA ops")
    pats = {k: re.compile(p) for k, p in (kernels or {}).items()}
    busy, coll = [], []
    kernel_s = {k: 0.0 for k in pats}
    kernel_calls = {k: 0 for k in pats}
    by_name: Dict[str, float] = {}
    for evs in per_dev.values():
        iv = [(e.start_ns, e.end_ns) for e in evs]
        busy.append(covered(iv))
        c = [(e.start_ns, e.end_ns) for e in evs if COLLECTIVE.match(e.name)]
        rest = [(e.start_ns, e.end_ns) for e in evs
                if not COLLECTIVE.match(e.name)]
        coll.append(_minus(c, rest))
        for e in evs:
            by_name[e.name] = by_name.get(e.name, 0.0) + (e.end_ns
                                                          - e.start_ns)
            for k, p in pats.items():
                if p.search(e.text):
                    kernel_s[k] += (e.end_ns - e.start_ns) * 1e-9
                    kernel_calls[k] += 1
    n = len(per_dev)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "collective_s": sum(coll) / n * 1e-9,
        "kernel_s": kernel_s,
        "kernel_calls": kernel_calls,
        "device_ops": [[k, v / n * 1e-9] for k, v in ops],
        "idle_gaps": idle_gaps(per_dev[min(per_dev)],
                               _host_events(profile, lo, hi), lo, hi, top),
    }


def idle_gaps(evs: List[Event], host: List[Event], lo, hi,
              top: int = 10) -> list:
    """The ``top`` longest gaps in which device ``evs`` ran nothing, each
    named by the shortest host event that covers the gap's middle (what
    the host was doing), and summed by that name."""
    gaps, cur = [], lo
    for s, e in union((x.start_ns, x.end_ns) for x in evs):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out: Dict[str, float] = {}
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        cover = [h for h in host if h.start_ns <= mid <= h.end_ns]
        name = (min(cover, key=lambda h: h.end_ns - h.start_ns).name
                if cover else "(no host event)")
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])]


if __name__ == "__main__":
    print(json.dumps(reduce(load(sys.argv[1])), indent=1))
