"""The numbers that decide ``correct`` for a training cell, each computed
the same way for the program and for the reference (or the control put in
the program's place).

For the first steps of a run: each step's loss, the norm of the first
gradient as the optimizer holds it after one step, and the norm of the
parameters' change after the last step. Norms are compared leaf by leaf:
the gap between the program's norm and the reference's, over the larger of
the reference's norm of that leaf and the median leaf's, and the worst leaf
is the number. Leaves whose reference gradient is under a thousandth of the
median leaf's are left out of the change: they move under Adam by
round-off alone.
"""
from __future__ import annotations

import numpy as np

DEAD_GRAD = 1e-3


def leaf_norms(tree: dict) -> dict:
    """Flat ``{path: float32 L2 norm}`` of a nested dict of arrays."""
    out = {}

    def walk(prefix, t):
        if isinstance(t, dict):
            for k in sorted(t):
                walk(f"{prefix}/{k}" if prefix else k, t[k])
        else:
            out[prefix] = float(np.linalg.norm(
                np.asarray(t, np.float32).ravel()))
    walk("", tree)
    return out


def change_norms(before: dict, after: dict) -> dict:
    """Per-leaf norm of ``after - before``."""
    out = {}

    def walk(prefix, a, b):
        if isinstance(a, dict):
            for k in sorted(a):
                walk(f"{prefix}/{k}" if prefix else k, a[k], b[k])
        else:
            d = np.asarray(b, np.float32) - np.asarray(a, np.float32)
            out[prefix] = float(np.linalg.norm(d.ravel()))
    walk("", before, after)
    return out


def worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    """Largest per-leaf gap ``|prog - ref| / max(ref, median ref)``."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in names)


def live_leaves(ref_grad: dict) -> set:
    med = float(np.median(list(ref_grad.values())))
    return {k for k, v in ref_grad.items() if v >= DEAD_GRAD * med}


def side(losses, grad1: dict, p0: dict, p_last: dict) -> dict:
    """What one side (program, reference or control) brings to the
    comparison: its losses, first-gradient leaf norms and change leaf
    norms."""
    return {"losses": list(losses), "grad1": grad1,
            "change": change_norms(p0, p_last)}


def training_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: ``losses`` (list), ``grad1`` and ``change`` (leaf
    norm dicts). Returns the compared numbers by name."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss_step{i + 1}"] = abs(a - b) / max(abs(b), 1e-30)
    out["grad1_worst_leaf"] = worst_leaf(prog["grad1"], ref["grad1"])
    out["change_worst_leaf"] = worst_leaf(prog["change"], ref["change"],
                                          live_leaves(ref["grad1"]))
    return out
