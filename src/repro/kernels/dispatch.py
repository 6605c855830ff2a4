"""Kernel dispatch: one registry from op name to its implementations.

Each op (``flash_attention``, ``flash_decode``, ``quant_matmul``,
``gae``, ``ssd``, ``pack``) registers up to four backends:

  ``ref``              pure-jnp oracle — correctness ground truth, CPU default
  ``chunked``          kernel-equivalent jnp program under a ``KERNEL_`` named
                       scope (the dry-run roofline stand-in, launch.hlo_analysis)
  ``pallas_interpret`` the real Pallas kernel body interpreted on CPU
                       (``interpret`` is accepted as an alias)
  ``pallas``           compiled Pallas — TPU default

Selection per (op, platform), highest precedence first:

  1. explicit ``mode=`` at the call site
  2. a ``dispatch.using(mode)`` scope — replaces threading ``kernel=``
     strings through every model layer
  3. per-op env override  ``REPRO_KERNEL_<OP>``   (strict: unknown ⇒ error)
  4. global env override  ``REPRO_KERNELS``       (lenient: skipped where
     the named impl is not registered for the op)
  5. the cached :func:`autotune` winner for (op, platform)
  6. platform default — ``pallas`` on TPU, ``ref`` elsewhere

Compiled Pallas requires a TPU and is excluded from :func:`available`
elsewhere, so the platform default (ref math on CPU, compiled Pallas on
TPU) is a property of the registry, not of each call site. Nothing falls
back silently: an impl that fails to lower or run raises.
"""
from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, Dict, Tuple

import jax

OPS = ("flash_attention", "flash_decode", "quant_matmul", "gae", "ssd",
       "pack")

REF = "ref"
CHUNKED = "chunked"
INTERPRET = "pallas_interpret"
PALLAS = "pallas"

ENV_GLOBAL = "REPRO_KERNELS"
_ALIASES = {"interpret": INTERPRET}


def env_var(op: str) -> str:
    return "REPRO_KERNEL_" + op.upper()


@dataclass(frozen=True)
class Impl:
    op: str
    name: str
    fn: Callable
    requires_tpu: bool = False


_REGISTRY: Dict[str, Dict[str, Impl]] = {}
_AUTOTUNED: Dict[Tuple[str, str], str] = {}
_TLS = threading.local()


def register(op: str, name: str, *, requires_tpu: bool = False):
    """Decorator: register ``fn`` as implementation ``name`` of ``op``."""
    def deco(fn):
        _REGISTRY.setdefault(op, {})[name] = Impl(op, name, fn, requires_tpu)
        return fn
    return deco


def _check_op(op: str):
    if op not in _REGISTRY:
        # built-in impls live in kernels.ops and register on import; pull
        # them in lazily so `import dispatch` alone sees a full registry
        import repro.kernels.ops  # noqa: F401
    if op not in _REGISTRY:
        raise KeyError(f"unknown kernel op {op!r}; registered: "
                       f"{tuple(sorted(_REGISTRY))}")


def ops() -> tuple:
    if not _REGISTRY:
        import repro.kernels.ops  # noqa: F401
    return tuple(sorted(_REGISTRY))


def implementations(op: str) -> tuple:
    _check_op(op)
    return tuple(_REGISTRY[op])


def platform() -> str:
    return jax.default_backend()


def _usable(impl: Impl, plat: str) -> bool:
    return plat == "tpu" or not impl.requires_tpu


def available(op: str, plat: str = None) -> tuple:
    """Impl names runnable on ``plat`` (default: this host)."""
    _check_op(op)
    plat = plat or platform()
    return tuple(n for n, i in _REGISTRY[op].items() if _usable(i, plat))


# -- scoped override ----------------------------------------------------------

@contextmanager
def using(mode: str):
    """Scoped default backend: ``with dispatch.using("interpret"): ...``
    applies to every op call in the block (and anything it traces) that
    doesn't pass an explicit ``mode=``. Thread-local and reentrant."""
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append(mode)
    try:
        yield
    finally:
        stack.pop()


def _scoped_mode():
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


# -- resolution ---------------------------------------------------------------

def resolve(op: str, mode: str = None, plat: str = None) -> str:
    """Pick the impl name for ``op`` (see module docstring for precedence).
    ``mode`` in (None, "auto") means "dispatch decides"."""
    _check_op(op)
    plat = plat or platform()
    if mode not in (None, "auto"):
        name = _ALIASES.get(mode, mode)
        if name not in _REGISTRY[op]:
            raise KeyError(f"{op}: no implementation {mode!r}; "
                           f"have {implementations(op)}")
        return name
    # (candidate, strict): scoped/global overrides are lenient because they
    # blanket-cover ops that may not register every backend (e.g. pack has
    # no "chunked"); the per-op env names exactly one op, so typos raise.
    for cand, strict in ((_scoped_mode(), False),
                         (os.environ.get(env_var(op)), True),
                         (os.environ.get(ENV_GLOBAL), False)):
        if cand and cand != "auto":
            name = _ALIASES.get(cand, cand)
            if name in _REGISTRY[op] and _usable(_REGISTRY[op][name], plat):
                return name
            if strict:
                raise KeyError(
                    f"{env_var(op)}={cand!r} is not a usable implementation "
                    f"of {op} on {plat}; have {available(op, plat)}")
    tuned = _AUTOTUNED.get((op, plat))
    if tuned in _REGISTRY[op]:
        return tuned
    if plat == "tpu" and PALLAS in available(op, plat):
        return PALLAS
    return REF


def call(op: str, *args, mode: str = None, **kwargs):
    """Resolve and invoke: the single entry point ops.py wraps."""
    name = resolve(op, mode)   # also lazy-loads the built-in registry
    return _REGISTRY[op][name].fn(*args, **kwargs)


# -- autotune (paper §3.3, mirroring core.vector.autotune) --------------------

def autotune(op: str, *args, impls: tuple = None, iters: int = 3,
             warmup: int = 1, **kwargs):
    """Time every runnable impl of ``op`` on the given concrete args.

    Returns ``({impl: calls_per_second}, best)`` and caches the winner so
    subsequent ``mode=None/"auto"`` dispatch on this platform uses it
    (cleared with :func:`clear_autotune`). An impl that fails to lower or
    run raises: a broken kernel is a fault to fix, not a loser."""
    _check_op(op)
    results = {}
    names = tuple(_ALIASES.get(n, n) for n in impls) if impls \
        else available(op)
    for name in names:
        if name not in _REGISTRY[op]:
            raise KeyError(f"{op}: no implementation {name!r}; "
                           f"have {implementations(op)}")
        fn = _REGISTRY[op][name].fn
        for _ in range(warmup):
            jax.block_until_ready(fn(*args, **kwargs))  # repro: noqa[HOST-SYNC] — autotune warmup (deliberate sync)
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args, **kwargs)
        jax.block_until_ready(out)  # repro: noqa[HOST-SYNC] — autotune timing barrier (deliberate)
        results[name] = iters / (time.perf_counter() - t0)
    best = max(results, key=results.get)
    _AUTOTUNED[(op, platform())] = best
    return results, best


def clear_autotune():
    _AUTOTUNED.clear()
