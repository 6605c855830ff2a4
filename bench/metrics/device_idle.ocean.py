"""Device idle share of the traced window, in %: 1 - (union of the device's
op intervals / window), averaged over the cell's chips. Source: the
profiler trace (``bench/trace.py``)."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
