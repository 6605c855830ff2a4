"""Public kernel ops — thin wrappers over the dispatch registry.

Every op registers up to four backends in ``kernels.dispatch``:

  ref              — pure-jnp oracle (CPU default)
  chunked          — kernel-equivalent jnp program under a ``KERNEL_`` named
                     scope (dry-run roofline lowering; the HLO of these
                     regions stands in for the Pallas kernel, see
                     launch.hlo_analysis)
  pallas_interpret — the real Pallas kernel body interpreted on CPU
                     ("interpret" is accepted as an alias)
  pallas           — compiled Pallas (TPU default)

Selection: explicit ``mode=`` > ``dispatch.using(...)`` scope >
``REPRO_KERNEL_<OP>`` / ``REPRO_KERNELS`` env > cached autotune winner >
platform default. ``mode=None`` and ``mode="auto"`` both mean
"dispatch decides"; see kernels/dispatch.py.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import dispatch
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import gae_scan as _gae
from repro.kernels import pack as _pack
from repro.kernels import quant_matmul as _qmm
from repro.kernels import ref as _ref
from repro.kernels import ssd as _ssd


# "KERNEL_" named scopes mark regions whose HLO stands in for a Pallas kernel
# during CPU dry-run lowering: launch.hlo_analysis excludes their *internal*
# HBM traffic (VMEM-resident on the real TPU kernel) while keeping their
# FLOPs. Inputs/outputs are still counted by the unmarked neighbor ops.
# Scopes are created fresh per call — jax.named_scope context managers are
# single-use (the mlp_apply reuse bug class; see tests/test_dispatch.py).


# -- flash_attention ----------------------------------------------------------

@dispatch.register("flash_attention", dispatch.REF)
def _fa_ref(q, k, v, *, causal=True, block_q=128, block_k=128):
    return _ref.flash_attention(q, k, v, causal=causal)


@dispatch.register("flash_attention", dispatch.CHUNKED)
def _fa_chunked(q, k, v, *, causal=True, block_q=128, block_k=128):
    with jax.named_scope("KERNEL_flash"):
        return _ref.flash_attention_chunked(q, k, v, causal=causal)


# -- ssd ----------------------------------------------------------------------

@dispatch.register("ssd", dispatch.REF)
def _ssd_ref(x, dt, A, B_, C, *, chunk=128):
    return _ref.ssd(x, dt, A, B_, C)


@dispatch.register("ssd", dispatch.CHUNKED)
def _ssd_chunked(x, dt, A, B_, C, *, chunk=128):
    with jax.named_scope("KERNEL_ssd"):
        return _ref.ssd_chunked(x, dt, A, B_, C, chunk=chunk)


# -- gae ----------------------------------------------------------------------

def _gae_ref(rewards, values, dones, last_value, gamma, lam, *, block_t=128):
    with jax.named_scope("KERNEL_gae"):
        return _ref.gae(rewards, values, dones, last_value, gamma, lam)


dispatch.register("gae", dispatch.REF)(_gae_ref)
dispatch.register("gae", dispatch.CHUNKED)(_gae_ref)


# -- pack ---------------------------------------------------------------------

@dispatch.register("pack", dispatch.REF)
def _pack_ref(leaves):
    return _ref.pack(leaves)


# -- quant_matmul -------------------------------------------------------------

def _qmm_ref(x, w_q, scale):
    with jax.named_scope("KERNEL_qmm"):
        return _ref.quant_matmul(x, w_q, scale)


dispatch.register("quant_matmul", dispatch.REF)(_qmm_ref)
dispatch.register("quant_matmul", dispatch.CHUNKED)(_qmm_ref)


# -- flash_decode -------------------------------------------------------------

def _fd_ref(q, k, v, length, *, block_s=512):
    with jax.named_scope("KERNEL_flash_decode"):
        return _ref.flash_decode(q, k, v, length)


dispatch.register("flash_decode", dispatch.REF)(_fd_ref)
dispatch.register("flash_decode", dispatch.CHUNKED)(_fd_ref)


# -- Pallas backends (interpret + compiled share one body per op) -------------

def _pallas_pair(op, fn):
    """Register ``fn(*a, interpret=...)`` as both the interpret-mode CI
    backend and the compiled TPU backend of ``op``."""
    dispatch.register(op, dispatch.INTERPRET)(
        functools.partial(fn, interpret=True))
    dispatch.register(op, dispatch.PALLAS, requires_tpu=True)(
        functools.partial(fn, interpret=False))


def _grad_via(kernel, twin, *args):
    """``kernel(*args)`` forward, differentiated through its jnp ``twin``:
    ``pallas_call`` has no autodiff rule, and the models train through
    flash attention and SSD. The backward recomputes the twin from the
    saved inputs."""
    @jax.custom_vjp
    def f(*a):
        return kernel(*a)

    def fwd(*a):
        return kernel(*a), a

    def bwd(a, g):
        return jax.vjp(twin, *a)[1](g)

    f.defvjp(fwd, bwd)
    return f(*args)


_pallas_pair("flash_attention",
             lambda q, k, v, *, causal=True, block_q=128, block_k=128,
             interpret: _grad_via(
                 functools.partial(_fa.flash_attention, causal=causal,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret),
                 functools.partial(_ref.flash_attention_chunked,
                                   causal=causal), q, k, v))
_pallas_pair("ssd",
             lambda x, dt, A, B_, C, *, chunk=128, interpret: _grad_via(
                 functools.partial(_ssd.ssd, chunk=chunk,
                                   interpret=interpret),
                 functools.partial(_ref.ssd_chunked, chunk=chunk),
                 x, dt, A, B_, C))
_pallas_pair("gae",
             lambda rewards, values, dones, last_value, gamma, lam, *,
             block_t=128, interpret: _gae.gae(
                 rewards, values, dones, last_value, gamma, lam,
                 block_t=block_t, interpret=interpret))
_pallas_pair("pack",
             lambda leaves, *, interpret:
             _pack.pack(leaves, interpret=interpret))
_pallas_pair("quant_matmul",
             lambda x, w_q, scale, *, interpret:
             _qmm.quant_matmul(x, w_q, scale, interpret=interpret))
_pallas_pair("flash_decode",
             lambda q, k, v, length, *, block_s=512, interpret:
             _fd.flash_decode(q, k, v, length, block_s=block_s,
                              interpret=interpret))


# -- public ops ---------------------------------------------------------------

def flash_attention(q, k, v, causal: bool = True, mode: str = None,
                    block_q: int = 128, block_k: int = 128):
    return dispatch.call("flash_attention", q, k, v, mode=mode,
                         causal=causal, block_q=block_q, block_k=block_k)


def ssd(x, dt, A, B_, C, chunk: int = 128, mode: str = None):
    return dispatch.call("ssd", x, dt, A, B_, C, mode=mode, chunk=chunk)


def gae(rewards, values, dones, last_value, gamma: float, lam: float,
        mode: str = None, block_t: int = 128):
    """Time-major: rewards/values/dones (T, B), last_value (B,)."""
    return dispatch.call("gae", rewards, values, dones, last_value,
                         gamma, lam, mode=mode, block_t=block_t)


def pack(leaves, mode: str = None):
    return dispatch.call("pack", leaves, mode=mode)


def quant_matmul(x, w_q, scale, mode: str = None):
    return dispatch.call("quant_matmul", x, w_q, scale, mode=mode)


def flash_decode(q, k, v, length, mode: str = None, block_s: int = 512):
    return dispatch.call("flash_decode", q, k, v, length, mode=mode,
                         block_s=block_s)
