"""Chip smoke: drive the main path once on a TPU, through the entry points a
user calls, and check what comes out. Weights are random, made from a seed.

    python chip_smoke.py              # one chip: every phase below, in order
    python chip_smoke.py --chips 4    # four chips: the shard_map phase only

Phases, one line each:

  device   the platform must be TPU (any other platform exits non-zero)
  kernels  every dispatch op resolves to compiled Pallas and matches ``ref``
           at real widths
  ocean    Ocean PPO on the jit tier at the preset widths: pong K=1 and K=4,
           memory (LSTM) K=4
  async    the actor-learner tier on bandit with 2 CPU actors
  lm       qwen3-0.6b at its full config, PPO train steps on a 1x1 mesh
  serve    qwen3-0.6b ``generate``, which decodes through ``flash_decode``
  4chips   (``--chips 4`` only) the shard_map tier over 4 devices against the
           seed-matched jit tier with ``num_shards=4`` on one device

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed. These are smoke numbers, not
benchmarks: times include compilation.
"""
import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# the kernel phase's inputs; the tolerance is tests/test_dispatch.py's
KERNEL_ATOL = KERNEL_RTOL = 1e-4
OCEAN_CELLS = (("pong", 1), ("pong", 4), ("memory", 4))
OCEAN_LAUNCHES = 3
LM_ARCH = "qwen3-0.6b"
FOUR_CHIP_ENV = "bandit"
# the parity tolerance of tests/test_engine.py's seed-matched shard_map test
PARITY_RTOL, PARITY_ATOL = 1e-5, 1e-6


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def say(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


def _all_finite(d: dict) -> bool:
    return all(math.isfinite(float(v)) for v in d.values())


# -- device ------------------------------------------------------------------

def device_phase(chips: int):
    import jax
    from repro.utils.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU: the platform is {d.platform!r} "
            f"({len(devs)} device(s)). Run it on a machine with a TPU.")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devs)}")
    say("device", f"platform={d.platform} kind={d.device_kind!r} "
                  f"count={len(devs)} compile_cache={cache}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# -- kernels -----------------------------------------------------------------

def _kernel_cases():
    """op -> (args, kwargs) at the widths the compile tests use."""
    import jax
    import jax.numpy as jnp
    k0 = jax.random.PRNGKey(0)
    r = lambda i, shape, scale=1.0: (
        jax.random.normal(jax.random.fold_in(k0, i), shape, jnp.float32)
        * scale)
    bern = lambda i, shape: jax.random.bernoulli(
        jax.random.fold_in(k0, i), 0.05, shape)

    def gae(T, B):
        return (r(1, (T, B)), r(2, (T, B)), bern(3, (T, B)), r(4, (B,)),
                0.95, 0.95), {}

    # qwen3-0.6b attention: 16 query heads, 8 kv heads, head_dim 128
    T, S = 4096, 4096
    # mamba2-1.3b SSD: 64 heads of 64, state 128, chunk 128
    wq = jax.random.randint(jax.random.fold_in(k0, 6), (1024, 1024), -127,
                            128, jnp.int32).astype(jnp.int8)
    return {
        "gae": [gae(64, 64), gae(128, 4096)],
        "flash_attention": [((r(1, (1, T, 16, 128)), r(2, (1, T, 8, 128)),
                              r(3, (1, T, 8, 128))), dict(causal=True))],
        "flash_decode": [((r(1, (4, 16, 128)), r(2, (4, S, 8, 128)),
                           r(3, (4, S, 8, 128)),
                           jnp.asarray(S - 700, jnp.int32)), {})],
        "ssd": [((r(1, (2, 512, 64, 64), 0.5),
                  jax.nn.softplus(r(2, (2, 512, 64))),
                  -jnp.exp(r(3, (64,), 0.3)),
                  r(4, (2, 512, 64, 128), 0.5),
                  r(5, (2, 512, 64, 128), 0.5)), dict(chunk=128))],
        "quant_matmul": [((r(1, (1024, 1024)), wq,
                           jnp.abs(r(7, (1024,))) * 0.02), {})],
        "pack": [(([jax.random.randint(jax.random.fold_in(k0, i), (4096, n),
                                       0, 256, jnp.int32).astype(jnp.uint8)
                    for i, n in enumerate((4, 36, 128))],), {})],
    }


def kernel_phase(dev: str):
    """Each op, compiled Pallas vs ``ref`` on the same f32 inputs, both at
    ``highest`` matmul precision: the chip's counterpart of the CPU's exact
    f32 interpret-mode parity test. Beside it, against that same ``highest``
    reference, the kernel and ``ref`` each at the default precision, which
    shows which side drops precision on the path training takes."""
    import jax
    import numpy as np
    from repro.kernels import dispatch
    cases = _kernel_cases()
    check(set(dispatch.ops()) == set(cases),
          f"ops {dispatch.ops()} vs smoke cases {sorted(cases)}")

    def leaves(out):
        return [np.asarray(x, np.float32)
                for x in jax.tree.leaves(jax.device_get(out))]

    def max_err(a, b):
        return max(float(np.max(np.abs(x - y))) for x, y in zip(a, b))

    for op in dispatch.ops():
        impl = dispatch.resolve(op)
        check(impl == dispatch.PALLAS,
              f"{op} resolves to {impl!r} on the TPU, not compiled Pallas")
        for args, kw in cases[op]:
            out = {}
            for prec in ("highest", "default"):
                with jax.default_matmul_precision(prec):
                    out["ref", prec] = leaves(
                        dispatch.call(op, *args, mode=dispatch.REF, **kw))
                    out["pallas", prec] = leaves(dispatch.call(op, *args,
                                                               **kw))
            want = out["ref", "highest"]
            for g, w in zip(out["pallas", "highest"], want):
                check(np.all(np.isfinite(g)),
                      f"{op}: non-finite kernel output")
                np.testing.assert_allclose(g, w, atol=KERNEL_ATOL,
                                           rtol=KERNEL_RTOL,
                                           err_msg=f"{op} pallas vs ref")
            err = {k: max_err(v, want) for k, v in out.items()}
            shapes = [tuple(a.shape) for a in jax.tree.leaves(args)
                      if hasattr(a, "shape")]
            say("kernels", f"{op} impl={impl} on {dev} shapes={shapes} "
                           f"max_abs_err={err['pallas', 'highest']:.3e} "
                           f"tol atol={KERNEL_ATOL} rtol={KERNEL_RTOL}; "
                           f"at default precision vs ref@highest: "
                           f"pallas {err['pallas', 'default']:.3e} "
                           f"ref {err['ref', 'default']:.3e}")


# -- Ocean PPO, jit tier -----------------------------------------------------

def _check_history(tr, updates: int, what: str):
    hist = tr.history
    check(len(hist) == updates, f"{what}: {len(hist)} updates, want "
                                f"{updates}")
    for m in hist:
        check(_all_finite(m), f"{what}: non-finite metrics {m}")
    want = updates * tr.steps_per_update
    check(int(hist[-1]["env_steps"]) == want,
          f"{what}: env_steps {hist[-1]['env_steps']} != {want}")
    return hist[-1]


def ocean_phase(dev: str):
    from repro.launch.train import ocean_trainer
    for name, K in OCEAN_CELLS:
        t0 = time.perf_counter()
        tr, p = ocean_trainer(name, engine_backend="jit",
                              updates_per_launch=K)
        try:
            updates = OCEAN_LAUNCHES * K
            tr.train(updates * tr.steps_per_update)
            m = _check_history(tr, updates, f"{name} K={K}")
        finally:
            tr.logger.close()
        say("ocean", f"{name} K={K} tier=jit on {dev} "
                     f"envs={tr.tcfg.num_envs} unroll={tr.tcfg.unroll_length} "
                     f"hidden={p.hidden} recurrent={p.recurrent} "
                     f"launches={OCEAN_LAUNCHES} updates={updates} "
                     f"env_steps={int(m['env_steps'])} loss={m['loss']:.6f} "
                     f"score={m['score']:.4f} "
                     f"seconds={time.perf_counter() - t0:.1f}")


# -- async tier --------------------------------------------------------------

def async_phase(dev: str):
    import jax
    from repro.launch.train import ocean_trainer
    t0 = time.perf_counter()
    tr, _ = ocean_trainer("bandit", engine_backend="async", num_actors=2,
                          updates_per_launch=1)
    try:
        updates = 4
        tr.train(updates * tr.steps_per_update)
        m = _check_history(tr, updates, "async bandit")
        st = tr.engine.stats()["rollouts"]
        check(m["actors_alive"] == 2, f"async: actors alive {m}")
        check(st["actor_platform"] == "cpu", f"async: {st['actor_platform']}")
        learner = {d.platform for leaf in jax.tree.leaves(tr.ts.params)
                   for d in leaf.devices()}
        check(learner == {"tpu"}, f"async: learner params on {learner}")
    finally:
        tr.engine.close()
        tr.logger.close()
    say("async", f"bandit actors=2 actor_platform={st['actor_platform']} "
                 f"learner on {dev} updates={updates} "
                 f"env_steps={int(m['env_steps'])} loss={m['loss']:.6f} "
                 f"dropped={m['dropped_fragments']} "
                 f"seconds={time.perf_counter() - t0:.1f}")


# -- LM-backbone PPO ---------------------------------------------------------

def lm_phase(dev: str):
    import jax
    from repro.launch.train import train_lm
    t0 = time.perf_counter()
    steps, batch, seq = 3, 8, 256
    loop, hist = train_lm(LM_ARCH, mesh="1x1", batch=batch, seq=seq,
                          steps=steps, ckpt_dir=None, save_every=0)
    check(len(hist) == steps and loop.recoveries == 0,
          f"lm: {len(hist)} steps, {loop.recoveries} recoveries")
    for m in hist:
        check(_all_finite(m), f"lm: non-finite metrics {m}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    say("lm", f"{LM_ARCH} full config on {dev} mesh=1x1 batch={batch} "
              f"seq={seq} steps={steps} "
              f"losses={[round(m['loss'], 6) for m in hist]} "
              f"grad_norm={hist[-1]['grad_norm']:.4f} "
              f"peak_bytes={peak} seconds={time.perf_counter() - t0:.1f}")


# -- serve -------------------------------------------------------------------

def serve_phase(dev: str):
    import numpy as np
    from repro.launch.serve import serve
    batch, prompt, tokens = 4, 32, 8
    cfg, out, dt = serve(LM_ARCH, batch=batch, prompt_len=prompt,
                         tokens=tokens)
    out = np.asarray(out)
    check(out.shape == (batch, tokens), f"serve: shape {out.shape}")
    check(bool(np.all((out >= 0) & (out < cfg.vocab_size))),
          f"serve: token ids outside the vocab: {out}")
    say("serve", f"{cfg.name} full config on {dev} batch={batch} "
                 f"prompt={prompt} tokens={tokens} shape={out.shape} "
                 f"first={out[0].tolist()} seconds={dt:.1f}")


# -- four chips --------------------------------------------------------------

def four_chip_phase(dev: str):
    """The seed-matched parity of tests/test_engine.py on real devices, at
    the ``--ocean`` launcher's widths for ``FOUR_CHIP_ENV`` (its preset and
    ``ocean_tcfg``: 64 envs x 64 unroll, 16 envs per chip): the shard_map
    tier over a 4-device mesh and the jit tier emulating the same 4-block
    layout on one device end at the same params. Both run at ``highest``
    matmul precision, so that only the order of the f32 reductions differs,
    as in the CPU test."""
    import jax
    import numpy as np
    from repro.launch.train import ocean_trainer

    K, launches = 3, 2
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        single, p = ocean_trainer(FOUR_CHIP_ENV, engine_backend="jit",
                                  num_shards=4, updates_per_launch=K)
        sharded, _ = ocean_trainer(FOUR_CHIP_ENV, engine_backend="shard_map",
                                   updates_per_launch=K)
        try:
            check(sharded.engine.num_shards == 4,
                  f"mesh gives {sharded.engine.num_shards} shards")
            obs = sharded.rc.obs
            shards = ", ".join(f"{s.device}:{tuple(s.data.shape)}"
                               for s in obs.addressable_shards)
            say("4chips", f"rollout carry obs {obs.shape} shards: {shards}")
            check(len({s.device for s in obs.addressable_shards}) == 4,
                  "the env batch is not spread over 4 devices")
            updates = K * launches
            for tr, what in ((single, "jit num_shards=4"),
                             (sharded, "shard_map")):
                tr.train(updates * tr.steps_per_update)
                _check_history(tr, updates, f"{FOUR_CHIP_ENV} {what}")
        finally:
            single.logger.close()
            sharded.logger.close()
    p1 = jax.tree.leaves(jax.device_get(single.ts.params))
    p4 = jax.tree.leaves(jax.device_get(sharded.ts.params))
    diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(p1, p4))
    for a, b in zip(p1, p4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=PARITY_RTOL, atol=PARITY_ATOL,
                                   err_msg="shard_map vs jit num_shards=4")
    for r1, r4 in zip(single.history, sharded.history):
        check(math.isclose(r1["loss"], r4["loss"], rel_tol=1e-4,
                           abs_tol=1e-5), f"loss {r1['loss']} {r4['loss']}")
    m = sharded.history[-1]
    say("4chips", f"{FOUR_CHIP_ENV} shard_map over 4 devices vs jit "
                  f"num_shards=4 on {dev}: envs={sharded.tcfg.num_envs} "
                  f"unroll={sharded.tcfg.unroll_length} hidden={p.hidden} "
                  f"updates={len(sharded.history)} "
                  f"env_steps={int(m['env_steps'])} "
                  f"max_abs_param_diff={diff:.3e} "
                  f"tol rtol={PARITY_RTOL} atol={PARITY_ATOL} "
                  f"final_loss={m['loss']:.6f} "
                  f"seconds={time.perf_counter() - t0:.1f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip shard_map phase")
    args = ap.parse_args(argv)

    device = device_phase(args.chips)
    dev = device["kind"]
    if args.chips == 4:
        four_chip_phase(dev)
    else:
        kernel_phase(dev)
        ocean_phase(dev)
        async_phase(dev)
        lm_phase(dev)
        serve_phase(dev)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
