"""TierTimer: the one SPS / launch / fetch clock shared by all engine tiers.

Before this existed, each of the five ``rl/engine.py`` tiers computed SPS
with its own ad-hoc ``time.perf_counter()`` arithmetic — five slightly
different formulas for the same number. TierTimer centralizes it so every
tier's history records carry the *same* keys with the *same* semantics:

- ``sps``       steps/sec since ``run()`` started, resume-aware (steps done
                in previous runs are subtracted from the numerator).
- ``launch_ms`` wall-time of the most recent learner/launch dispatch.
- ``fetch_ms``  wall-time of the most recent device→host metrics fetch.

``launch()`` / ``fetch()`` return context managers that both time the block
and open the matching span (``engine.launch`` / ``engine.fetch``), so the
Chrome trace and the history records agree by construction. ``drain()`` is
the span alone (``engine.drain``): the host work on a fetched update's
metrics (stamp, history, logger, ``on_update``, target check). All three
contexts are pre-built once per TierTimer on a ``CachedSpan`` — the
per-launch hot loop allocates nothing and the tracer enabled-check happens
exactly once per block entry, whether tracing is on or off.

jax-free (stdlib only).
"""
from __future__ import annotations

import time

from repro.telemetry.spans import CachedSpan

__all__ = ["TierTimer"]


class _Timed:
    """Times a block into ``timer.<attr>`` (ms) and mirrors it as a span.
    Reused across launches — one instance per (timer, attr); not reentrant,
    which launch/fetch blocks never are."""
    __slots__ = ("_timer", "_attr", "_span", "_t0")

    def __init__(self, timer: "TierTimer", attr: str, span_name: str):
        self._timer = timer
        self._attr = attr
        self._span = CachedSpan(span_name)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        setattr(self._timer, self._attr,
                (time.perf_counter() - self._t0) * 1e3)
        return self._span.__exit__(et, ev, tb)


class TierTimer:
    """Per-``run()`` clock. ``done_before_steps`` is the env-step count
    already completed by previous (resumed) runs, so a resumed run reports
    the rate of *this* run, not a number polluted by zero-cost history."""

    def __init__(self, steps_per_update: int, done_before_steps: int = 0):
        self.spu = int(steps_per_update)
        self.done_before = int(done_before_steps)
        self.t0 = time.perf_counter()
        self.launch_ms = 0.0
        self.fetch_ms = 0.0
        self._launch = _Timed(self, "launch_ms", "engine.launch")
        self._fetch = _Timed(self, "fetch_ms", "engine.fetch")
        self._drain = CachedSpan("engine.drain")

    def launch(self) -> _Timed:
        return self._launch

    def fetch(self) -> _Timed:
        return self._fetch

    def drain(self) -> CachedSpan:
        return self._drain

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def sps(self, env_steps: int) -> float:
        return (int(env_steps) - self.done_before) / max(
            self.elapsed(), 1e-9)

    def stamp(self, md: dict, env_steps: int) -> dict:
        """Set the unified keys on one history/metrics record in place."""
        md["env_steps"] = int(env_steps)
        md["sps"] = self.sps(env_steps)
        md["launch_ms"] = self.launch_ms
        md["fetch_ms"] = self.fetch_ms
        return md
