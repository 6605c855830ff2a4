"""Kernel dispatch subsystem: registry resolution, overrides, autotune,
interpret-vs-ref parity for every registered op, and a regression test for
the reused-named-scope bug class."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, ops, ref


@pytest.fixture(autouse=True)
def _clean_kernel_env(monkeypatch):
    """Resolution must not depend on overrides set in the developer's shell."""
    monkeypatch.delenv(dispatch.ENV_GLOBAL, raising=False)
    for op in dispatch.OPS:
        monkeypatch.delenv(dispatch.env_var(op), raising=False)


# -- registry -----------------------------------------------------------------

def test_all_ops_registered():
    assert set(dispatch.OPS) <= set(dispatch.ops())
    for op in dispatch.OPS:
        impls = dispatch.implementations(op)
        assert dispatch.REF in impls, op
        assert dispatch.INTERPRET in impls, op
        assert dispatch.PALLAS in impls, op


def test_platform_resolution():
    for op in dispatch.OPS:
        assert dispatch.resolve(op, plat="cpu") == dispatch.REF
        assert dispatch.resolve(op, plat="tpu") == dispatch.PALLAS


def test_compiled_pallas_unavailable_off_tpu():
    for op in dispatch.OPS:
        assert dispatch.PALLAS not in dispatch.available(op, plat="cpu")
        assert dispatch.PALLAS in dispatch.available(op, plat="tpu")
        assert dispatch.REF in dispatch.available(op, plat="cpu")


def test_interpret_alias():
    assert dispatch.resolve("gae", mode="interpret") == dispatch.INTERPRET
    assert dispatch.resolve("gae", mode="pallas_interpret") == \
        dispatch.INTERPRET


def test_unknown_op_and_impl_raise():
    with pytest.raises(KeyError):
        dispatch.resolve("not_an_op")
    with pytest.raises(KeyError):
        dispatch.resolve("pack", mode="chunked")   # pack has no chunked


def test_explicit_mode_beats_env(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_GLOBAL, "chunked")
    assert dispatch.resolve("flash_attention", mode="ref") == dispatch.REF


def test_per_op_env_beats_global(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_GLOBAL, "interpret")
    monkeypatch.setenv(dispatch.env_var("gae"), "ref")
    assert dispatch.resolve("gae") == dispatch.REF
    assert dispatch.resolve("flash_attention") == dispatch.INTERPRET


def test_per_op_env_unknown_impl_raises(monkeypatch):
    monkeypatch.setenv(dispatch.env_var("pack"), "chunked")
    with pytest.raises(KeyError):
        dispatch.resolve("pack")


def test_global_env_lenient_fallback(monkeypatch):
    # "chunked" isn't registered for pack: global override skips it
    monkeypatch.setenv(dispatch.ENV_GLOBAL, "chunked")
    assert dispatch.resolve("flash_attention", plat="cpu") == dispatch.CHUNKED
    assert dispatch.resolve("pack", plat="cpu") == dispatch.REF


def test_using_scope(monkeypatch):
    assert dispatch.resolve("flash_attention", plat="cpu") == dispatch.REF
    with dispatch.using("chunked"):
        assert dispatch.resolve("flash_attention", plat="cpu") == \
            dispatch.CHUNKED
        assert dispatch.resolve("pack", plat="cpu") == dispatch.REF  # lenient
        with dispatch.using("ref"):   # reentrant, innermost wins
            assert dispatch.resolve("flash_attention", plat="cpu") == \
                dispatch.REF
        assert dispatch.resolve("flash_attention", plat="cpu") == \
            dispatch.CHUNKED
    assert dispatch.resolve("flash_attention", plat="cpu") == dispatch.REF


def test_scope_beats_env(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_GLOBAL, "ref")
    with dispatch.using("chunked"):
        assert dispatch.resolve("flash_attention", plat="cpu") == \
            dispatch.CHUNKED


# -- interpret-mode parity for every registered op ----------------------------

def _parity_args(op):
    k0 = jax.random.PRNGKey(0)
    r = lambda i, shape, scale=1.0: (
        jax.random.normal(jax.random.fold_in(k0, i), shape, jnp.float32)
        * scale)
    if op == "flash_attention":
        q, k, v = r(1, (1, 32, 2, 16)), r(2, (1, 32, 2, 16)), \
            r(3, (1, 32, 2, 16))
        return (q, k, v), dict(causal=True, block_q=16, block_k=16)
    if op == "flash_decode":
        q, k, v = r(1, (2, 4, 16)), r(2, (2, 32, 2, 16)), r(3, (2, 32, 2, 16))
        return (q, k, v, jnp.asarray(17, jnp.int32)), dict(block_s=16)
    if op == "quant_matmul":
        x = r(1, (16, 32))
        wq = jax.random.randint(jax.random.fold_in(k0, 2), (32, 128),
                                -127, 128, jnp.int32).astype(jnp.int8)
        s = jnp.abs(r(3, (128,))) * 0.02
        return (x, wq, s), {}
    if op == "gae":
        return (r(1, (32, 4)), r(2, (32, 4)),
                jax.random.bernoulli(jax.random.fold_in(k0, 3), 0.2, (32, 4)),
                r(4, (4,)), 0.99, 0.95), dict(block_t=8)
    if op == "ssd":
        # batch 2: grid rows past the head count must find their head's A
        x = r(1, (2, 32, 2, 8), 0.5)
        dt = jax.nn.softplus(r(2, (2, 32, 2)))
        A = -jnp.exp(r(3, (2,), 0.3))
        B_ = r(4, (2, 32, 2, 8), 0.5)
        C = r(5, (2, 32, 2, 8), 0.5)
        return (x, dt, A, B_, C), dict(chunk=8)
    if op == "pack":
        leaves = [jax.random.randint(jax.random.fold_in(k0, i), (4, n),
                                     0, 256, jnp.int32).astype(jnp.uint8)
                  for i, n in enumerate((3, 7, 16))]
        return (leaves,), {}
    raise AssertionError(op)


@pytest.mark.parametrize("op", dispatch.OPS)
def test_interpret_matches_ref(op):
    """Every registered op: real Pallas body (interpreted) == jnp oracle."""
    args, kw = _parity_args(op)
    want = dispatch.call(op, *args, mode="ref", **kw)
    got = dispatch.call(op, *args, mode="interpret", **kw)
    jax.tree.map(
        lambda g, w: np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=1e-4, rtol=1e-4),
        got, want)


@pytest.mark.parametrize("op", ["flash_attention", "ssd"])
def test_interpret_grad_matches_ref(op):
    """The ops the models train through differentiate on the Pallas
    backends too (pallas_call itself has no autodiff rule)."""
    args, kw = _parity_args(op)

    def loss(mode, *a):
        out = dispatch.call(op, *a, mode=mode, **kw)
        return sum(jnp.sum(jnp.sin(o)) for o in jax.tree.leaves(out))

    n = len(args)
    want = jax.grad(lambda *a: loss("ref", *a), argnums=tuple(range(n)))(
        *args)
    got = jax.grad(lambda *a: loss("interpret", *a),
                   argnums=tuple(range(n)))(*args)
    jax.tree.map(
        lambda g, w: np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-4, rtol=1e-4),
        got, want)


# -- autotune -----------------------------------------------------------------

def test_autotune_picks_winner_and_feeds_auto_dispatch():
    args, kw = _parity_args("gae")
    try:
        results, best = dispatch.autotune(
            "gae", *args, impls=(dispatch.REF, dispatch.INTERPRET),
            iters=2, **kw)
        assert set(results) == {dispatch.REF, dispatch.INTERPRET}
        assert best in results
        assert all(r > 0 for r in results.values())
        # the cached winner now drives auto dispatch on this platform
        assert dispatch.resolve("gae") == best
    finally:
        dispatch.clear_autotune()
    assert dispatch.resolve("gae", plat="cpu") == dispatch.REF


def test_autotune_skips_broken_impls(monkeypatch):
    """No impl is skipped: one that fails to lower or run raises out of
    autotune instead of quietly losing to ``ref``, and nothing is cached."""
    def broken(leaves):
        raise RuntimeError("cannot lower")
    monkeypatch.setitem(dispatch._REGISTRY["pack"], "broken",
                        dispatch.Impl("pack", "broken", broken))
    args, kw = _parity_args("pack")
    with pytest.raises(RuntimeError, match="cannot lower"):
        dispatch.autotune("pack", *args, impls=(dispatch.REF, "broken"),
                          iters=1, **kw)
    assert dispatch.resolve("pack", plat="cpu") == dispatch.REF


# -- ops-level round trip through the public wrappers -------------------------

def test_ops_mode_none_equals_auto():
    args, kw = _parity_args("gae")
    a = ops.gae(*args, mode=None, **kw)
    b = ops.gae(*args, mode="auto", **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ops_chunked_matches_ref_flash_attention():
    (q, k, v), kw = _parity_args("flash_attention")
    np.testing.assert_allclose(
        np.asarray(ops.flash_attention(q, k, v, mode="chunked")),
        np.asarray(ref.flash_attention(q, k, v)), atol=2e-5, rtol=2e-5)


# -- named-scope reuse regression (the mlp_apply/moe_apply seed bug) ----------

def _tiny_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny", family="dense", num_layers=1,
                       d_model=16, num_heads=2, num_kv_heads=2, d_ff=32,
                       vocab_size=64, head_dim=8, dtype="float32",
                       param_dtype="float32")


def test_mlp_apply_single_call_enters_scope_twice():
    """mlp_apply enters its named scope twice per call; a reused context
    manager raises AttributeError on the second __enter__."""
    from repro.models import layers as L
    cfg = _tiny_cfg()
    params = {
        "wi": jnp.ones((cfg.d_model, 2 * cfg.d_ff), jnp.float32) * 0.01,
        "wo": jnp.ones((cfg.d_ff, cfg.d_model), jnp.float32) * 0.01,
    }
    x = jnp.ones((2, cfg.d_model), jnp.float32)
    out = L.mlp_apply(params, x, cfg)
    assert out.shape == (2, cfg.d_model)


def test_same_model_entered_twice_in_one_trace():
    """The bug class: tracing a module twice in one jit trace must not
    crash on reused context managers anywhere in the stack."""
    from repro.models import layers as L
    cfg = _tiny_cfg()
    params = {
        "wi": jnp.ones((cfg.d_model, 2 * cfg.d_ff), jnp.float32) * 0.01,
        "wo": jnp.ones((cfg.d_ff, cfg.d_model), jnp.float32) * 0.01,
    }

    @jax.jit
    def twice(p, x):
        return L.mlp_apply(p, x, cfg) + L.mlp_apply(p, x, cfg)

    out = twice(params, jnp.ones((2, cfg.d_model), jnp.float32))
    assert out.shape == (2, cfg.d_model)


def test_moe_apply_entered_twice_in_one_trace():
    from repro.models import moe as moe_mod
    from repro.configs.base import ModelConfig
    from repro.models.params import init_params
    cfg = ModelConfig(name="tiny-moe", family="moe", num_layers=1,
                      d_model=16, num_heads=2, num_kv_heads=2, d_ff=32,
                      vocab_size=64, head_dim=8, num_experts=4, top_k=2,
                      dtype="float32", param_dtype="float32")
    params = init_params(moe_mod.moe_spec(cfg), jax.random.PRNGKey(0),
                        jnp.float32)

    @jax.jit
    def twice(p, x):
        y1, a1 = moe_mod.moe_apply(p, x, cfg)
        y2, a2 = moe_mod.moe_apply(p, x, cfg)
        return y1 + y2, a1 + a2

    y, aux = twice(params, jnp.ones((1, 8, cfg.d_model), jnp.float32))
    assert y.shape == (1, 8, cfg.d_model)
    assert np.isfinite(float(aux))
