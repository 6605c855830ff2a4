"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracle,
swept across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@pytest.mark.parametrize("B,T,H,K,hd,bq,bk", [
    (1, 32, 2, 2, 16, 16, 16),      # MHA
    (2, 64, 4, 2, 32, 32, 32),      # GQA 2:1
    (1, 128, 8, 2, 64, 128, 64),    # GQA 4:1, uneven blocks
    (2, 64, 4, 1, 32, 16, 64),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, T, H, K, hd, bq, bk, dtype):
    k0 = jax.random.PRNGKey(B * T + H)
    q = _rand(k0, (B, T, H, hd), dtype)
    k = _rand(jax.random.fold_in(k0, 1), (B, T, K, hd), dtype)
    v = _rand(jax.random.fold_in(k0, 2), (B, T, K, hd), dtype)
    want = ref.flash_attention(q, k, v, causal=True)
    got = ops.flash_attention(q, k, v, causal=True, mode="interpret",
                              block_q=bq, block_k=bk)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_attention_chunked_matches():
    k0 = jax.random.PRNGKey(7)
    q = _rand(k0, (2, 64, 4, 32))
    k = _rand(jax.random.fold_in(k0, 1), (2, 64, 2, 32))
    v = _rand(jax.random.fold_in(k0, 2), (2, 64, 2, 32))
    np.testing.assert_allclose(
        np.asarray(ref.flash_attention_chunked(q, k, v, block_k=16)),
        np.asarray(ref.flash_attention(q, k, v)), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,T,H,hd,ds,chunk", [
    (1, 16, 1, 8, 8, 4),
    (2, 64, 3, 16, 32, 16),
    (1, 128, 2, 32, 16, 64),
])
def test_ssd_sweep(B, T, H, hd, ds, chunk):
    k0 = jax.random.PRNGKey(T + H)
    x = _rand(k0, (B, T, H, hd), scale=0.5)
    dt = jax.nn.softplus(_rand(jax.random.fold_in(k0, 1), (B, T, H)))
    A = -jnp.exp(_rand(jax.random.fold_in(k0, 2), (H,), scale=0.3))
    B_ = _rand(jax.random.fold_in(k0, 3), (B, T, H, ds), scale=0.5)
    C = _rand(jax.random.fold_in(k0, 4), (B, T, H, ds), scale=0.5)
    y_ref, h_ref = ref.ssd(x, dt, A, B_, C)
    y_pl, h_pl = ops.ssd(x, dt, A, B_, C, chunk=chunk, mode="interpret")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_ref),
                               atol=1e-4, rtol=1e-4)
    # chunked-jnp twin (the dry-run stand-in) must match too
    y_ch, h_ch = ref.ssd_chunked(x, dt, A, B_, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_ch), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_ch), np.asarray(h_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,T,bt", [(4, 32, 8), (8, 128, 128), (2, 64, 16)])
@pytest.mark.parametrize("done_p", [0.0, 0.1, 0.5])
def test_gae_sweep(B, T, bt, done_p):
    k0 = jax.random.PRNGKey(B + T)
    r = _rand(k0, (T, B))                   # time-major, as the learner
    v = _rand(jax.random.fold_in(k0, 1), (T, B))
    d = jax.random.bernoulli(jax.random.fold_in(k0, 2), done_p, (T, B))
    lv = _rand(jax.random.fold_in(k0, 3), (B,))
    want = ref.gae(r, v, d, lv, 0.99, 0.95)
    got = ops.gae(r, v, d, lv, 0.99, 0.95, mode="interpret", block_t=bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_gae_matches_python_reference():
    """Oracle vs an independent step-by-step python implementation."""
    rng = np.random.RandomState(0)
    B, T, g, lam = 3, 20, 0.9, 0.8
    r = rng.randn(B, T).astype(np.float32)
    v = rng.randn(B, T).astype(np.float32)
    d = rng.rand(B, T) < 0.2
    lv = rng.randn(B).astype(np.float32)
    adv = np.zeros((B, T), np.float32)
    for b in range(B):
        a = 0.0
        for t in reversed(range(T)):
            nt = 1.0 - float(d[b, t])
            vn = lv[b] if t == T - 1 else v[b, t + 1]
            delta = r[b, t] + g * vn * nt - v[b, t]
            a = delta + g * lam * nt * a
            adv[b, t] = a
    got = ref.gae(jnp.asarray(r.T), jnp.asarray(v.T), jnp.asarray(d.T),
                  jnp.asarray(lv), g, lam)          # oracle is time-major
    np.testing.assert_allclose(np.asarray(got).T, adv, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("sizes", [(3,), (3, 7, 16), (1, 1, 1, 128)])
@pytest.mark.parametrize("B", [4, 8])
def test_pack_sweep(sizes, B):
    k0 = jax.random.PRNGKey(sum(sizes))
    leaves = [jax.random.randint(jax.random.fold_in(k0, i), (B, n), 0, 256,
                                 jnp.int32).astype(jnp.uint8)
              for i, n in enumerate(sizes)]
    want = ref.pack(leaves)
    got = ops.pack(leaves, mode="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("qdtype,qmax", [(jnp.int8, 127), (jnp.int4, 7)])
@pytest.mark.parametrize("M,K,N,bm,bk", [(32, 64, 128, 16, 32),
                                         (64, 128, 128, 64, 64)])
def test_quant_matmul_sweep(qdtype, qmax, M, K, N, bm, bk):
    k0 = jax.random.PRNGKey(M + N)
    x = _rand(k0, (M, K))
    wq = jax.random.randint(jax.random.fold_in(k0, 1), (K, N), -qmax,
                            qmax + 1, jnp.int32).astype(qdtype)
    s = jnp.abs(_rand(jax.random.fold_in(k0, 2), (N,))) * 0.02
    want = ref.quant_matmul(x, wq, s)
    got = ops.quant_matmul(x, wq, s, mode="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,H,K,hd,S,bs", [
    (2, 4, 2, 32, 64, 16),     # GQA 2:1
    (1, 8, 2, 64, 128, 32),    # GQA 4:1
    (3, 4, 4, 16, 64, 64),     # MHA, single block
    (2, 4, 1, 32, 96, 32),     # MQA
])
@pytest.mark.parametrize("frac", [0.0, 0.6, 1.0])
def test_flash_decode_sweep(B, H, K, hd, S, bs, frac):
    """Decode attention kernel vs oracle across GQA ratios and cache fills."""
    k0 = jax.random.PRNGKey(B * S + H)
    q = _rand(k0, (B, H, hd))
    k = _rand(jax.random.fold_in(k0, 1), (B, S, K, hd))
    v = _rand(jax.random.fold_in(k0, 2), (B, S, K, hd))
    L = jnp.asarray(int(frac * (S - 1)), jnp.int32)
    want = ref.flash_decode(q, k, v, L)
    got = ops.flash_decode(q, k, v, L, mode="interpret", block_s=bs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
