"""Compile for a TPU v5e that is described, not attached.

Every Pallas kernel at the widths the chip runs, and one whole fused launch
of the jit tier, go through the chip's compiler here. Nothing runs, so this
says nothing about results or speed; it catches what interpret mode cannot
(unaligned blocks, primitives Mosaic cannot lower, VMEM overruns) at no chip
time. The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and only the worker given this
file does.
"""
import importlib.util

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention, flash_decode, gae_scan, pack, \
    quant_matmul, ssd


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    return lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                          sharding=one_chip)


def _compile(fn, *args, **jit_kw):
    """Compile for the described chip and check a kernel made it in."""
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("T,B", [(64, 64), (128, 4096)])
def test_gae_compiles(shape, T, B):
    """The Ocean preset's 64 envs x 64 steps, and 4096 envs x 128 steps."""
    _compile(lambda r, v, d, lv: gae_scan.gae(r, v, d, lv, 0.99, 0.95),
             shape((T, B)), shape((T, B)), shape((T, B), jnp.bool_),
             shape((B,)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles(shape, dtype):
    """qwen3-0.6b heads (16 q, 8 kv, head_dim 128) at T=4096."""
    _compile(lambda q, k, v: flash_attention.flash_attention(q, k, v),
             shape((1, 4096, 16, 128), dtype), shape((1, 4096, 8, 128), dtype),
             shape((1, 4096, 8, 128), dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_compiles(shape, dtype):
    """qwen3-0.6b heads against a 4096-entry cache."""
    _compile(flash_decode.flash_decode, shape((4, 16, 128), dtype),
             shape((4, 4096, 8, 128), dtype), shape((4, 4096, 8, 128), dtype),
             shape((), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_compiles(shape, dtype):
    """mamba2-1.3b: 64 heads of head_dim 64, state 128, chunk 128; batch 2,
    so the kernel's grid rows run past the 64 per-head decay rates."""
    _compile(lambda x, dt, A, B_, C: ssd.ssd(x, dt, A, B_, C, chunk=128),
             shape((2, 512, 64, 64), dtype), shape((2, 512, 64), dtype),
             shape((64,)), shape((2, 512, 64, 128), dtype),
             shape((2, 512, 64, 128), dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_compiles(shape, dtype):
    """A 1024 x 1024 int8 weight."""
    _compile(quant_matmul.quant_matmul, shape((1024, 1024), dtype),
             shape((1024, 1024), jnp.int8), shape((1024,)))


def test_pack_compiles(shape):
    _compile(lambda *leaves: pack.pack(list(leaves)),
             *(shape((4096, n), jnp.uint8) for n in (4, 36, 128)))


def test_fused_jit_launch_compiles(one_chip):
    """One K=4 fused launch of the jit tier at the pong preset (64 envs x 64
    steps, conv frontend), GAE on compiled Pallas, state donated as the
    engine does."""
    from repro.configs.ocean import ocean_tcfg, preset
    from repro.envs.ocean import OCEAN
    from repro.rl.engine import TrainEngine, _scan_launch
    from repro.rl.trainer import ocean_policy_stack
    p = preset("pong")
    em, dist, pol = ocean_policy_stack(OCEAN["pong"](), hidden=p.hidden,
                                       recurrent=p.recurrent, conv=p.conv)
    eng = TrainEngine(em, pol, ocean_tcfg("pong", updates_per_launch=4), dist,
                      key=jax.random.PRNGKey(0), kernel_mode="pallas")
    abstract = jax.eval_shape(lambda: (eng.ts, eng.rc,
                                       jax.random.PRNGKey(1)))
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        abstract)
    _compile(_scan_launch(eng._update, 4), *args, donate_argnums=(0, 1))
