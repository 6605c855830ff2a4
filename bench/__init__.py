"""The on-chip benchmark: one cell, one run, driven by ``BENCHMARK.json``
(see ``harness.py``)."""
