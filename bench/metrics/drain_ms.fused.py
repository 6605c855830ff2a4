"""Host milliseconds per launch inside the engine's ``engine.drain`` span
(``TierTimer.drain``, ``rl/engine.py`` ``_run_fused``): the host work on a
fetched launch's metrics (unpack, stamp, history, logger, ``on_update``,
target check, logger flush), averaged over the traced window. Source: the
program's spans. Silent where the program has no such span."""


def read(ctx):
    ms = [r.dur_ns * 1e-6 for r in ctx["spans"] if r.name == "engine.drain"]
    return sum(ms) / len(ms) if ms else None
