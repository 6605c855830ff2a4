"""Layer 2: trace-and-inspect audit of real jaxprs and compiled HLO.

Where the AST lint (layer 1) over-approximates from source text, this layer
under-approximates from the actual program: it traces a function under
canonical arguments and asserts the four properties that make a JAX stack
"play nice" at speed —

  * **no host callbacks** in the jaxpr (a ``pure_callback``/``io_callback``
    anywhere under jit reintroduces the per-step host round-trip the paper
    eliminates),
  * **retrace count ≤ 1 per distinct arg signature** across a shape/dtype
    sweep (a function that retraces on every call recompiles in the hot
    loop),
  * **donation consumed**: if the caller passes ``donate_argnums``, the
    compiled HLO must actually alias those input buffers into the output
    (``input_output_alias`` in the module header, parsed by
    ``launch.hlo_analysis``) — otherwise train-state double-buffers,
  * **no silent f32→f64 promotion**: no float64 intermediate appears unless
    a float64 input was given.

Entry point: :func:`audit_fn`. Target enumeration for the repo's own
kernels/engines/envs lives in ``analysis.targets``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

# primitive names that smuggle a host round-trip into a jitted program
CALLBACK_PRIMS = ("pure_callback", "io_callback", "python_callback",
                  "callback", "debug_callback")


@dataclass(frozen=True)
class AuditViolation:
    check: str       # host-callback | retrace | donation | f64-promotion
    target: str
    message: str

    def render(self) -> str:
        return f"[{self.check}] {self.target}: {self.message}"

    def to_dict(self) -> dict:
        return {"check": self.check, "target": self.target,
                "message": self.message}


@dataclass
class AuditResult:
    target: str
    checks: List[str] = field(default_factory=list)
    violations: List[AuditViolation] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations


# ---------------------------------------------------------------------------
# jaxpr walking

def _subjaxprs(params: dict):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def leaves(v):
        if isinstance(v, (ClosedJaxpr,)):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from leaves(x)
    for v in params.values():
        yield from leaves(v)


def callback_eqns(jaxpr, found: Optional[list] = None) -> list:
    """All (primitive_name, eqn) pairs for host-callback primitives,
    recursing through scan/cond/pjit sub-jaxprs."""
    if found is None:
        found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS or "callback" in name:
            found.append((name, eqn))
        for sub in _subjaxprs(eqn.params):
            callback_eqns(sub, found)
    return found


def _is_f64(dt) -> bool:
    try:
        return np.dtype(dt) == np.float64
    except TypeError:                    # extended dtypes (PRNG keys)
        return False


def _f64_outvars(jaxpr, found: Optional[list] = None) -> list:
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and getattr(aval, "dtype", None) is not None \
                    and _is_f64(aval.dtype):
                found = found if found is not None else []
                found.append((eqn.primitive.name, aval))
                break
        for sub in _subjaxprs(eqn.params):
            out = _f64_outvars(sub, found)
            found = out if found is None else found
    return found if found is not None else []


# ---------------------------------------------------------------------------
# argument plumbing

def _is_static(arg: Any) -> bool:
    return isinstance(arg, (bool, int, float, str, bytes, type(None)))


def _static_argnums(args: Sequence[Any]) -> Tuple[int, ...]:
    return tuple(i for i, a in enumerate(args) if _is_static(a))


def _array_leaves(args: Sequence[Any]) -> list:
    return [l for l in jax.tree_util.tree_leaves(list(args))
            if hasattr(l, "shape") and hasattr(l, "dtype")]


def _aval_signature(args: Sequence[Any]) -> tuple:
    """Shape/dtype fingerprint of the array leaves — statics excluded on
    purpose: the contract is one trace per distinct *aval* signature, so a
    function retraced because a supposedly-fixed static flipped is a bug."""
    return tuple((tuple(l.shape), str(l.dtype))
                 for l in _array_leaves(args))


# ---------------------------------------------------------------------------
# the audit

def audit_fn(fn: Callable, args: Sequence[Any], *,
             name: Optional[str] = None,
             variants: Sequence[Sequence[Any]] = (),
             donate_argnums: Optional[Tuple[int, ...]] = None,
             check_callbacks: bool = True,
             check_retrace: bool = True,
             check_f64: bool = True,
             allow_callbacks: Sequence[str] = ()) -> AuditResult:
    """Audit ``fn`` under canonical ``args`` (plus optional sweep
    ``variants`` — alternative arg tuples, typically other batch sizes).

    Non-array scalars in ``args`` are treated as static arguments (matching
    how the repo passes flags like ``causal=True`` through jit).
    ``allow_callbacks`` whitelists primitive names (e.g. a deliberate
    ``io_callback`` in a host-bridge op).
    """
    target = name or getattr(fn, "__name__", repr(fn))
    res = AuditResult(target=target)
    statics = _static_argnums(args)

    # -- jaxpr checks: callbacks + f64 --------------------------------------
    jaxpr = None
    if check_callbacks or check_f64:
        try:
            jaxpr = jax.make_jaxpr(fn, static_argnums=statics)(*args).jaxpr
        except Exception as e:          # tracing itself failed
            res.checks.append("trace")
            res.violations.append(AuditViolation(
                "trace", target, f"tracing failed: {type(e).__name__}: {e}"))
            return res

    if check_callbacks:
        res.checks.append("host-callback")
        for prim, _eqn in callback_eqns(jaxpr):
            if prim in allow_callbacks:
                continue
            res.violations.append(AuditViolation(
                "host-callback", target,
                f"jaxpr contains host callback primitive '{prim}' — every "
                f"call round-trips to python, serializing the device"))

    if check_f64:
        res.checks.append("f64-promotion")
        has_f64_input = any(_is_f64(l.dtype) for l in _array_leaves(args))
        if not has_f64_input:
            hits = _f64_outvars(jaxpr)
            if hits:
                prim, aval = hits[0]
                res.violations.append(AuditViolation(
                    "f64-promotion", target,
                    f"float64 intermediate produced by '{prim}' "
                    f"({aval.dtype}{list(getattr(aval, 'shape', ()))}) with "
                    f"no float64 input — doubles memory traffic and falls "
                    f"off the fast path silently"))

    # -- retrace across the sweep -------------------------------------------
    if check_retrace:
        res.checks.append("retrace")
        traces = 0

        def counting(*a, **kw):
            nonlocal traces
            traces += 1
            return fn(*a, **kw)

        jitted = jax.jit(counting, static_argnums=statics)
        sweep = [tuple(args)] + [tuple(v) for v in variants]
        try:
            for v in sweep:
                jax.block_until_ready(jitted(*v))  # repro: noqa[HOST-SYNC] — the audit must force compilation to count traces
                jax.block_until_ready(jitted(*v))  # repro: noqa[HOST-SYNC] — second call must hit the jit cache
        except Exception as e:
            res.violations.append(AuditViolation(
                "retrace", target,
                f"execution failed during sweep: {type(e).__name__}: {e}"))
        else:
            distinct = len({_aval_signature(v) for v in sweep})
            if traces > distinct:
                res.violations.append(AuditViolation(
                    "retrace", target,
                    f"traced {traces}× for {distinct} distinct arg "
                    f"signature(s) — something non-aval (a static flag, a "
                    f"fresh closure, weak types) is busting the jit cache"))

    # -- donation consumed --------------------------------------------------
    if donate_argnums:
        res.checks.append("donation")
        try:
            jitted = jax.jit(fn, static_argnums=statics,
                             donate_argnums=donate_argnums,
                             keep_unused=True)
            hlo = jitted.lower(*args).compile().as_text()
        except Exception as e:
            res.violations.append(AuditViolation(
                "donation", target,
                f"compile failed: {type(e).__name__}: {e}"))
        else:
            from repro.launch.hlo_analysis import donated_params
            consumed = donated_params(hlo)
            # flat param numbering: dynamic args flattened in order
            flat_idx, expected = 0, {}
            for i, a in enumerate(args):
                if i in statics:
                    continue
                n = len(jax.tree_util.tree_leaves(a))
                if i in donate_argnums:
                    expected[i] = set(range(flat_idx, flat_idx + n))
                flat_idx += n
            for i, want in expected.items():
                if want and not (want & consumed):
                    res.violations.append(AuditViolation(
                        "donation", target,
                        f"arg {i} was donated but none of its "
                        f"{len(want)} buffer(s) are aliased into the "
                        f"output (no input_output_alias in compiled "
                        f"HLO) — the donation silently double-buffers"))
    return res
