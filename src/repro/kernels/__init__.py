"""Kernel layer: Pallas TPU kernels behind a dispatch registry.

- ``ops``      — public op functions (what models/rl call)
- ``dispatch`` — registry: (op, platform) → implementation, env/scoped
  overrides, autotune
- ``ref``      — pure-jnp oracles (correctness ground truth)
- one module per Pallas kernel (flash_attention, flash_decode,
  quant_matmul, gae_scan, ssd, pack)

New fused kernels land as registry entries (``dispatch.register``) and
automatically join the interpret-vs-ref parity sweep in tests.
"""
