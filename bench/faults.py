"""Faults that the control tests plant under a cell's timed path, to see
``correct`` come out false. Each is a context manager that patches one
function of the program while the cell is built and run; the drivers list
theirs in ``FAULTS``."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_unchanged():
    """Every optimizer step returns the parameters and state it got."""
    from repro.optim import adamw
    real = adamw.update

    def update(grads, state, params, **kw):
        _, _, stats = real(grads, state, params, **kw)
        return params, state, stats
    return patched(adamw, "update", update)
