"""Pure-jnp oracles for every Pallas kernel.

These are the correctness ground truth (tests assert_allclose kernels against
them across shape/dtype sweeps) AND the CPU execution path used by models
when no TPU is present.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention(q, k, v, causal: bool = True, scale: float = None):
    """q: (B,T,H,hd); k,v: (B,S,K,hd) with H = K*G (GQA). f32 softmax."""
    B, T, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / jnp.sqrt(float(hd))
    qg = q.reshape(B, T, K, G, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskh->btkgh", w, v.astype(jnp.float32))
    return o.reshape(B, T, H, hd).astype(q.dtype)


def flash_attention_chunked(q, k, v, causal: bool = True, scale: float = None,
                            block_k: int = 512):
    """Online-softmax attention, scanning KV blocks — the pure-jnp program
    whose HLO has the SAME memory/collective profile as the Pallas flash
    kernel (no materialized (T, S) scores or masks). Used as the kernel
    stand-in for CPU dry-run lowering; numerically identical to
    ``flash_attention`` (tested)."""
    B, T, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / jnp.sqrt(float(hd))
    block_k = min(block_k, S)
    assert S % block_k == 0
    nb = S // block_k
    qg = q.reshape(B, T, K, G, hd).astype(jnp.float32)
    rows = jnp.arange(T)[:, None]

    # GSPMD loses batch sharding on loop-carried tensors without explicit
    # constraints (measured: full-batch all-gathers inside the block scan)
    from repro.models.params import constrain as _con
    _c4 = lambda t: _con(t, "batch", "null", "kv_heads", "null")
    _c5 = lambda t: _con(t, "batch", "null", "kv_heads", "null", "null")

    def step(carry, i):
        m, l, acc = carry
        kb = _c4(jax.lax.dynamic_slice_in_dim(k, i * block_k, block_k, 1))
        vb = _c4(jax.lax.dynamic_slice_in_dim(v, i * block_k, block_k, 1))
        s = jnp.einsum("btkgh,bskh->btkgs", qg,
                       kb.astype(jnp.float32)) * scale
        if causal:
            cols = i * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where((rows >= cols)[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "btkgs,bskh->btkgh", p, vb.astype(jnp.float32))
        return (_c4(m_new), _c4(l), _c5(acc)), None

    init = (_c4(jnp.full((B, T, K, G), -1e30)),
            _c4(jnp.zeros((B, T, K, G))),
            _c5(jnp.zeros((B, T, K, G, hd))))
    (m, l, acc), _ = jax.lax.scan(step, init, jnp.arange(nb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, T, H, hd).astype(q.dtype)


def ssd(x, dt, A, B_, C, h0=None):
    """Mamba2 selective-state recurrence, exact step-by-step oracle.

    x:  (B, T, H, hd)   inputs per head
    dt: (B, T, H)       positive step sizes (post-softplus)
    A:  (H,)            negative decay rates
    B_: (B, T, H, ds)   input gates (already head-expanded)
    C:  (B, T, H, ds)   output gates
    h0: (B, H, hd, ds)  optional initial state
    returns y (B, T, H, hd), h_last (B, H, hd, ds)
    """
    Bb, T, H, hd = x.shape
    ds = B_.shape[-1]
    f32 = jnp.float32
    in_dtype = x.dtype
    x, dt, B_, C = (t.astype(f32) for t in (x, dt, B_, C))
    A = A.astype(f32)
    if h0 is None:
        h0 = jnp.zeros((Bb, H, hd, ds), f32)

    def step(h, inp):
        xt, dtt, Bt, Ct = inp                       # (B,H,hd),(B,H),(B,H,ds)x2
        decay = jnp.exp(dtt * A[None])              # (B,H)
        upd = jnp.einsum("bh,bhd,bhs->bhds", dtt, xt, Bt)
        h = h * decay[..., None, None] + upd
        y = jnp.einsum("bhds,bhs->bhd", h, Ct)
        return h, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B_, 1, 0), jnp.moveaxis(C, 1, 0))
    h_last, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(in_dtype), h_last


def ssd_chunked(x, dt, A, B_, C, chunk: int = 128):
    """Chunked SSD in pure jnp — the exact algorithm of kernels/ssd.py
    (within-chunk dual matmuls + inter-chunk state scan), used as the
    kernel stand-in for dry-run lowering. Same contract as ``ssd``."""
    Bb, T, H, hd = x.shape
    ds = B_.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk
    f32 = jnp.float32
    in_dtype = x.dtype
    xc = x.astype(f32).reshape(Bb, nc, chunk, H, hd)
    dtc = dt.astype(f32).reshape(Bb, nc, chunk, H)
    Bc = B_.astype(f32).reshape(Bb, nc, chunk, H, ds)
    Cc = C.astype(f32).reshape(Bb, nc, chunk, H, ds)
    A = A.astype(f32)

    dA = dtc * A[None, None, None]                   # (B,nc,Q,H)
    cum = jnp.cumsum(dA, axis=2)
    li = cum[:, :, :, None] - cum[:, :, None, :]     # (B,nc,Qi,Qj,H)
    mask = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
    L = jnp.where(mask[None, None, :, :, None], jnp.exp(li), 0.0)
    scores = jnp.einsum("bnihs,bnjhs->bnijh", Cc, Bc)
    y_diag = jnp.einsum("bnijh,bnjh,bnjhd->bnihd", scores * L, dtc, xc)

    # per-chunk candidate states and decay
    w = jnp.exp(cum[:, :, -1:, :] - cum) * dtc       # (B,nc,Q,H)
    s_new = jnp.einsum("bnjh,bnjhd,bnjhs->bnhds", w, xc, Bc)
    chunk_decay = jnp.exp(cum[:, :, -1])             # (B,nc,H)

    def scan_fn(h, inp):
        s_n, dec = inp                                # (B,H,hd,ds),(B,H)
        h_out = h
        h = h * dec[..., None, None] + s_n
        return h, h_out

    h0 = jnp.zeros((Bb, H, hd, ds), f32)
    h_last, h_prev = jax.lax.scan(
        scan_fn, h0, (jnp.moveaxis(s_new, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)              # (B,nc,H,hd,ds)
    y_off = jnp.einsum("bnihs,bnhds->bnihd", Cc * jnp.exp(cum)[..., None],
                       h_prev)
    y = (y_diag + y_off).reshape(Bb, T, H, hd).astype(in_dtype)
    return y, h_last


def gae(rewards, values, dones, last_value, gamma: float, lam: float):
    """Generalized advantage estimation, time-major reverse scan oracle.

    rewards/values/dones: (T, B) time-major, the learner's rollout layout;
    last_value: (B,). done_t marks that the episode ended *at* step t (no
    bootstrap across it). returns advantages (T, B).
    """
    f32 = jnp.float32
    rewards, values, last_value = (t.astype(f32) for t in
                                   (rewards, values, last_value))
    nonterm = 1.0 - dones.astype(f32)

    def step(carry, inp):
        adv_next, v_next = carry
        r, v, nt = inp
        delta = r + gamma * v_next * nt - v
        adv = delta + gamma * lam * nt * adv_next
        return (adv, v), adv

    _, advs = jax.lax.scan(step, (jnp.zeros_like(last_value), last_value),
                           (rewards, values, nonterm), reverse=True)
    return advs


def pack(leaves):
    """Batched flat-buffer packing oracle: [(B, n_i) u8] -> (B, sum n_i) u8."""
    return jnp.concatenate(leaves, axis=-1)


def quant_matmul(x, w_q, scale):
    """W8/W4A16 oracle: x @ (w_q · scale) with f32 accumulation."""
    w = w_q.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def flash_decode(q, k, v, length):
    """One-token decode attention oracle. q: (B,H,hd); k,v: (B,S,K,hd);
    length: () — newest valid cache index. Returns (B,H,hd)."""
    B, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(float(hd))
    valid = jnp.arange(S)[None, None, None, :] <= length
    s = jnp.where(valid, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", w, v.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)
