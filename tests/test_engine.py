"""TrainEngine: fused multi-update scan, shard_map data-parallel tier,
pool tier, and the launch-boundary run loop."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.core.emulation import Emulated
from repro.models.policy import OceanPolicy
from repro.rl.distributions import Dist
from repro.rl.engine import TrainEngine, METRIC_KEYS, pack_metrics, \
    unpack_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TCFG = TrainConfig(num_envs=16, unroll_length=16, update_epochs=2,
                   num_minibatches=2, learning_rate=1e-3, gamma=0.95)


def _build(env, tcfg=TCFG, backend="jit", recurrent=False, num_shards=1,
           seed=0, updates_per_launch=None):
    em = Emulated(env)
    dist = Dist("categorical", nvec=em.act_spec.nvec)
    pol = OceanPolicy(em.obs_spec.total, dist.nvec, hidden=32,
                      recurrent=recurrent, num_outputs=dist.num_outputs)
    return TrainEngine(em, pol, tcfg, dist, key=jax.random.PRNGKey(seed),
                       backend=backend, kernel_mode="ref",
                       num_shards=num_shards,
                       updates_per_launch=updates_per_launch)


def _sequential_reference(engine, k):
    """Replay engine.run's first-launch key schedule, one jitted update at a
    time (the pre-engine dispatch pattern)."""
    key = jax.random.PRNGKey(0)
    _, sub = jax.random.split(key)
    uks = engine.update_keys(sub, k)
    upd = jax.jit(engine._update)
    ts, rc, rows = engine.ts, engine.rc, []
    for i in range(k):
        ts, rc, m = upd(ts, rc, uks[i])
        rows.append({kk: float(m[kk]) for kk in METRIC_KEYS})
    return ts, rows


def test_fused_scan_matches_sequential_updates():
    """K=8 in one lax.scan launch == 8 one-at-a-time dispatches: identical
    params and identical per-update metrics."""
    from repro.envs.ocean import Bandit
    ref = _build(Bandit())
    ts_ref, rows_ref = _sequential_reference(ref, 8)

    fused = _build(Bandit(), updates_per_launch=8)
    hist, _ = fused.run(8 * fused.steps_per_update)
    assert len(hist) == 8

    for a, b in zip(jax.tree.leaves(ts_ref.params),
                    jax.tree.leaves(fused.ts.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    for r_ref, r in zip(rows_ref, hist):
        for k in METRIC_KEYS:
            np.testing.assert_allclose(r_ref[k], r[k], rtol=1e-5,
                                       atol=1e-6, err_msg=k)


def test_recurrent_engine_carry_threading():
    """Memory env (LSTM policy): the policy carry must thread through the
    K-update scan exactly as through sequential updates."""
    from repro.envs.ocean import Memory
    ref = _build(Memory(), recurrent=True)
    ts_ref, rows_ref = _sequential_reference(ref, 4)

    fused = _build(Memory(), recurrent=True, updates_per_launch=4)
    hist, _ = fused.run(4 * fused.steps_per_update)
    assert len(hist) == 4
    # the carry the next launch would start from is a live (B, hidden) pair
    c, h = fused.rc.policy_carry
    assert c.shape == (TCFG.num_envs, 32) and bool(jnp.all(jnp.isfinite(h)))

    for a, b in zip(jax.tree.leaves(ts_ref.params),
                    jax.tree.leaves(fused.ts.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(rows_ref[-1]["loss"], hist[-1]["loss"],
                               rtol=1e-4, atol=1e-5)


def test_partial_tail_launch_and_accounting():
    """num_updates not divisible by K: the tail compiles a shorter launch
    and the history covers exactly total_steps // steps_per_update rows."""
    from repro.envs.ocean import Bandit
    e = _build(Bandit(), updates_per_launch=4)
    hist, solved = e.run(6 * e.steps_per_update)
    assert solved is None
    assert len(hist) == 6
    assert [h["env_steps"] for h in hist] == \
        [(i + 1) * e.steps_per_update for i in range(6)]
    assert sorted(e._launches) == [2, 4]


def test_target_score_checked_at_launch_boundaries():
    from repro.envs.ocean import Bandit
    e = _build(Bandit(), updates_per_launch=4)
    hist, solved = e.run(400 * e.steps_per_update, target_score=0.5)
    assert solved is not None and solved["score"] >= 0.5
    # stopped at a launch boundary, far short of the full budget
    assert len(hist) < 400 and len(hist) % 4 == 0


def test_pool_tier_runs_and_accounts():
    from repro.envs.ocean import Bandit
    tcfg = TrainConfig(num_envs=16, unroll_length=16, update_epochs=2,
                       num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                       pool_buffers=3)
    e = _build(Bandit(), tcfg=tcfg, backend="pool")
    hist, _ = e.run(6 * e.steps_per_update)
    assert len(hist) == 6
    assert hist[-1]["env_steps"] == 6 * e.steps_per_update
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_pool_tier_recurrent_learns_shapes():
    from repro.envs.ocean import Memory
    tcfg = TrainConfig(num_envs=8, unroll_length=16, update_epochs=1,
                       num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                       pool_buffers=2)
    e = _build(Memory(), tcfg=tcfg, backend="pool", recurrent=True)
    hist, _ = e.run(4 * e.steps_per_update)
    assert len(hist) == 4 and np.isfinite(hist[-1]["loss"])


def test_minibatch_mismatch_raises_value_error():
    """The old bare assert is now a ValueError naming the offending knobs."""
    from repro.envs.ocean import Bandit
    tcfg = TrainConfig(num_envs=10, unroll_length=7, update_epochs=1,
                       num_minibatches=4)
    e = _build(Bandit(), tcfg=tcfg)
    with pytest.raises(ValueError) as ei:
        e.run(e.steps_per_update)
    msg = str(ei.value)
    assert "num_minibatches=4" in msg and "num_envs=10" in msg \
        and "unroll_length=7" in msg


def test_pool_tier_reusable_after_early_exit():
    """Early exit on target_score must leave the pool protocol clean (every
    recv answered by a send) so the engine can keep training."""
    from repro.envs.ocean import Bandit
    tcfg = TrainConfig(num_envs=16, unroll_length=16, update_epochs=2,
                       num_minibatches=2, learning_rate=1e-3, gamma=0.95)
    e = _build(Bandit(), tcfg=tcfg, backend="pool")
    hist, solved = e.run(200 * e.steps_per_update, target_score=0.3)
    assert solved is not None
    hist2, _ = e.run(2 * e.steps_per_update)    # would assert pre-fix
    assert len(hist2) == 2


def test_engine_config_validation():
    from repro.envs.ocean import Bandit
    with pytest.raises(ValueError, match="num_shards"):
        _build(Bandit(), num_shards=3)          # 16 envs % 3 != 0
    with pytest.raises(ValueError, match="pool tier"):
        _build(Bandit(), backend="pool", updates_per_launch=4)
    with pytest.raises(ValueError, match="backend"):
        _build(Bandit(), backend="nope")


def test_trainer_logs_once_per_launch(tmp_path):
    from repro.envs.ocean import Bandit
    from repro.rl.trainer import Trainer
    from repro.utils import metrics as ml
    tcfg = TrainConfig(num_envs=16, unroll_length=16, update_epochs=1,
                       num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                       updates_per_launch=4)
    tr = Trainer(Bandit(), tcfg, hidden=32, kernel_mode="ref",
                 log_dir=str(tmp_path))
    tr.train(8 * tr.steps_per_update)
    rows = ml.read(tr.logger.path)
    assert len(rows) == 8
    assert [r["step"] for r in rows] == \
        [(i + 1) * tr.steps_per_update for i in range(8)]


def test_metrics_ring_pack_unpack_roundtrip():
    m = {k: float(i) for i, k in enumerate(METRIC_KEYS)}
    row = pack_metrics(m)
    assert row.shape == (len(METRIC_KEYS),)
    assert unpack_metrics(np.asarray(row)) == m


@pytest.mark.parametrize("recurrent", [False, True])
def test_op_scopes_maps_leaf_instructions_to_each_scope(recurrent):
    from repro.envs.ocean import Bandit
    eng = _build(Bandit(), recurrent=recurrent)
    scopes = eng.op_scopes()
    assert set(scopes.values()) == {"ppo_rollout", "ppo_env_step",
                                    "ppo_learn", "ppo_minibatch_rows"}
    text = eng._launch_for(1).lower(eng.ts, eng.rc, eng.key).compile() \
        .as_text()
    whiles = {line.strip().removeprefix("ROOT ").split(" = ")[0]
              for line in text.splitlines() if " while(" in line}
    assert whiles and not whiles & {k.split(" = ")[0] for k in scopes}


def test_fused_run_spans_each_drain():
    from repro import telemetry
    from repro.envs.ocean import Bandit
    eng = _build(Bandit())
    telemetry.enable()
    try:
        eng.run(3 * eng.steps_per_update)
        names = [r.name for r in telemetry.get_tracer().records()]
    finally:
        telemetry.disable()
    assert names.count("engine.launch") == names.count("engine.drain") == 3


SHARD_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax, numpy as np
from repro.envs.ocean import Bandit
from repro.core.emulation import Emulated
from repro.models.policy import OceanPolicy
from repro.rl.distributions import Dist
from repro.rl.engine import TrainEngine
from repro.configs.base import TrainConfig

assert jax.device_count() == 8
tcfg = TrainConfig(num_envs=16, unroll_length=16, update_epochs=2,
                   num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                   updates_per_launch=3)

def build(backend, num_shards=1):
    em = Emulated(Bandit())
    dist = Dist("categorical", nvec=em.act_spec.nvec)
    pol = OceanPolicy(em.obs_spec.total, dist.nvec, hidden=32,
                      num_outputs=dist.num_outputs)
    return TrainEngine(em, pol, tcfg, dist, key=jax.random.PRNGKey(0),
                       backend=backend, kernel_mode="ref",
                       num_shards=num_shards)

single = build("jit", num_shards=8)
h1, _ = single.run(6 * single.steps_per_update)
sharded = build("shard_map")
assert sharded.num_shards == 8
h8, _ = sharded.run(6 * sharded.steps_per_update)

for a, b in zip(jax.tree.leaves(jax.device_get(single.ts.params)),
                jax.tree.leaves(jax.device_get(sharded.ts.params))):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)
for r1, r8 in zip(h1, h8):
    np.testing.assert_allclose(r1["loss"], r8["loss"], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(r1["score"], r8["score"], rtol=1e-4, atol=1e-5)
print("SHARD_PARITY_OK")
"""


@pytest.mark.multi_device
def test_shard_map_tier_seed_matched_parity():
    """8-way shard_map data-parallel PPO is seed-matched with the
    single-device run (same rollout randomness via global-env-index keys,
    same minibatch composition via per-block permutations, pmean'd grads):
    final params agree to float reduction order."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SHARD_PARITY],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=560)
    assert "SHARD_PARITY_OK" in out.stdout, out.stderr[-3000:]


def test_shard_map_tier_runs_on_available_devices():
    """Direct (non-subprocess) shard_map run on whatever mesh this process
    has — S=1 degenerates to plain data-parallel over one device; the CI
    multi-device job runs this with 8 forced host devices."""
    from repro.envs.ocean import Squared
    if TCFG.num_envs % jax.device_count():
        pytest.skip("num_envs not divisible by device count")
    e = _build(Squared(), backend="shard_map", updates_per_launch=2)
    hist, _ = e.run(4 * e.steps_per_update)
    assert len(hist) == 4 and np.isfinite(hist[-1]["loss"])


@pytest.mark.parametrize("backend", ["jit", "shard_map", "pool"])
@pytest.mark.parametrize("name", ["pong", "drone", "tagteam", "maze"])
def test_ocean_ii_envs_run_on_every_tier(name, backend):
    """Each Ocean II env steps + learns under all three engine tiers — the
    'plays nice' claim holds for pixel obs (CNN frontend), multi-dim
    Gaussian actions, padded multi-agent rows, and procgen state alike."""
    from repro.envs.ocean import OCEAN
    from repro.rl.trainer import Trainer
    tcfg = TrainConfig(num_envs=8, unroll_length=8, update_epochs=1,
                       num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                       engine_backend=backend)
    if backend == "shard_map" and 8 % jax.device_count():
        pytest.skip("num_envs not divisible by device count")
    tr = Trainer(OCEAN[name](), tcfg, hidden=16, kernel_mode="ref")
    m = tr.train(2 * tr.steps_per_update)
    assert len(tr.history) == 2
    assert np.isfinite(m["loss"]) and np.isfinite(m["entropy"])
    if name == "pong":
        assert tr.policy.conv_shape == (6, 6)   # CNN frontend engaged


# ===================== periodic checkpointing + resume =======================

CKPT_TCFG = TrainConfig(num_envs=16, unroll_length=16, update_epochs=2,
                        num_minibatches=2, learning_rate=1e-3, gamma=0.95,
                        checkpoint_every=3)


def test_resume_parity_jit(tmp_path):
    """Interrupted-then-resumed == uninterrupted, bitwise: the checkpoint
    carries TrainState + RNG key + rollout carry, so the resumed engine
    replays exactly the launches the uninterrupted one would have run."""
    from repro.envs.ocean import Bandit
    a = _build(Bandit(), tcfg=CKPT_TCFG)
    a.run(6 * a.steps_per_update)

    b = _build(Bandit(), tcfg=CKPT_TCFG)
    b.checkpoint_dir = str(tmp_path)
    hist_b, _ = b.run(3 * b.steps_per_update)     # "interrupted" at update 3
    assert len(hist_b) == 3

    c = _build(Bandit(), tcfg=CKPT_TCFG, seed=1)  # seed irrelevant: restored
    c.checkpoint_dir = str(tmp_path)
    assert c.restore() == 3
    hist_c, _ = c.run(6 * c.steps_per_update)
    assert len(hist_c) == 3                       # only the remaining updates
    assert hist_c[0]["env_steps"] == 4 * c.steps_per_update
    for x, y in zip(jax.tree.leaves(a.ts.params), jax.tree.leaves(c.ts.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_resume_parity_recurrent_and_fused(tmp_path):
    """Same bitwise property with an LSTM policy and K=2 fused launches —
    the policy carry and the launch-boundary key schedule both restore."""
    from repro.envs.ocean import Memory
    a = _build(Memory(), tcfg=CKPT_TCFG, recurrent=True,
               updates_per_launch=2)
    a.run(6 * a.steps_per_update)

    b = _build(Memory(), tcfg=CKPT_TCFG, recurrent=True,
               updates_per_launch=2)
    b.checkpoint_dir = str(tmp_path)
    b.run(4 * b.steps_per_update)                 # checkpoints at update 4
    c = _build(Memory(), tcfg=CKPT_TCFG, recurrent=True,
               updates_per_launch=2, seed=9)
    c.checkpoint_dir = str(tmp_path)
    assert c.restore() == 4
    c.run(6 * c.steps_per_update)
    for x, y in zip(jax.tree.leaves(a.ts.params), jax.tree.leaves(c.ts.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_cadence_and_gc(tmp_path):
    """Saves fire every checkpoint_every updates at the launch boundary and
    the ring keeps tcfg.keep_checkpoints newest."""
    import dataclasses
    from repro.envs.ocean import Bandit
    from repro.checkpoint import ckpt
    tcfg = dataclasses.replace(CKPT_TCFG, checkpoint_every=2,
                               keep_checkpoints=2)
    e = _build(Bandit(), tcfg=tcfg)
    e.checkpoint_dir = str(tmp_path)
    e.run(7 * e.steps_per_update)
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    assert steps == [4, 6]                        # 2 kept of 2,4,6
    assert ckpt.latest(str(tmp_path)).endswith("step_6")


def test_pool_tier_checkpoints_and_resumes(tmp_path):
    """The pool tier saves TrainState + key (its env state is host-side)
    and a fresh engine resumes from the restored update count."""
    from repro.envs.ocean import Bandit
    e = _build(Bandit(), tcfg=CKPT_TCFG, backend="pool")
    e.checkpoint_dir = str(tmp_path)
    hist, _ = e.run(4 * e.steps_per_update)
    assert len(hist) == 4 and os.path.isdir(tmp_path / "step_3")

    e2 = _build(Bandit(), tcfg=CKPT_TCFG, backend="pool")
    e2.checkpoint_dir = str(tmp_path)
    assert e2.restore() == 3
    hist2, _ = e2.run(5 * e2.steps_per_update)
    assert len(hist2) == 2                        # updates 3 and 4
    assert hist2[0]["env_steps"] == 4 * e2.steps_per_update


def test_trainer_resume_flag(tmp_path):
    """Trainer.train(checkpoint_dir=..., resume=True) restores the newest
    committed engine checkpoint and continues the update count."""
    from repro.envs.ocean import Bandit
    from repro.rl.trainer import Trainer
    tr = Trainer(Bandit(), CKPT_TCFG, hidden=32, kernel_mode="ref")
    tr.train(3 * tr.steps_per_update, checkpoint_dir=str(tmp_path))

    tr2 = Trainer(Bandit(), CKPT_TCFG, hidden=32, kernel_mode="ref")
    m = tr2.train(6 * tr2.steps_per_update, checkpoint_dir=str(tmp_path),
                  resume=True)
    assert len(tr2.history) == 3                  # updates 3..5 only
    assert m["env_steps"] == 6 * tr2.steps_per_update
