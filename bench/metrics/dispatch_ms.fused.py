"""Host milliseconds per launch inside the engine's ``engine.launch`` span
(``TierTimer.launch``, ``rl/engine.py`` ``_run_fused``), averaged over the
traced window. One launch is ``updates_per_launch`` updates. Source: the
program's spans."""


def read(ctx):
    ms = [r.dur_ns * 1e-6 for r in ctx["spans"] if r.name == "engine.launch"]
    return sum(ms) / len(ms) if ms else None
