"""Roofline share of the Pallas GAE kernel (``kernels/gae_scan.py``), in %:
calls x the least time of one call at the cell's per-chip shape
(``flops.gae_cost``, memory-bound) over the device time of its trace
events. Silent where the trace holds no such event."""
from bench import flops


def read(ctx):
    calls = ctx["trace"]["kernel_calls"].get("gae", 0)
    if not calls:
        return None
    t_min, _ = flops.least_time(*ctx["info"]["kernel_cost"]["gae"],
                                ctx["peak"])
    return 100.0 * calls * t_min / ctx["trace"]["kernel_s"]["gae"]
