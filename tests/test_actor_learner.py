"""Async actor–learner tier (distributed/actor_learner.py): slab layout,
seqlock param broadcast, fragment stacking, staleness policy, V-trace, and
the process-level fault paths (dead-actor reshard, kill-then-resume)."""
import threading
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.core import shm
from repro.distributed.actor_learner import (
    AsyncLayout, FragSpec, Fragment, SLOT_EMPTY, SLOT_FULL,
    make_param_specs, read_params_seqlock, stack_fragments)


def _spec(**kw):
    leaves = [np.zeros((3, 5), np.float32), np.zeros((7,), np.float32)]
    pspecs, pbytes = make_param_specs(leaves)
    base = dict(num_actors=2, num_shards=2, slots=2, unroll=4,
                envs_per_shard=3, num_agents=1, obs_dim=6, act_dim=1,
                act_dtype="int32", param_specs=pspecs, param_bytes=pbytes)
    base.update(kw)
    return FragSpec(**base)


# ------------------------------ unit layer -----------------------------------

def test_param_specs_aligned_and_disjoint():
    leaves = [np.zeros((3,), np.float32), np.zeros((2, 2), np.float64),
              np.zeros((5,), np.int8), np.zeros((), np.float32)]
    specs, total = make_param_specs(leaves)
    prev_end = 0
    for (shape, dtype, off), leaf in zip(specs, leaves):
        assert off % 8 == 0                      # frombuffer-legal for any dtype
        assert off >= prev_end                   # no overlap
        assert shape == leaf.shape and dtype == str(leaf.dtype)
        prev_end = off + leaf.nbytes
    assert total == prev_end


def test_async_layout_sections_disjoint_and_viewable():
    spec = _spec()
    lay = AsyncLayout(spec)
    spans = sorted((start, start + np.dtype(dt).itemsize *
                    int(np.prod(shape, dtype=np.int64)), name)
                   for name, (start, shape, dt) in lay.sections.items())
    for (_, e0, n0), (s1, _, n1) in zip(spans, spans[1:]):
        assert e0 <= s1, (n0, n1)
    buf = bytearray(lay.nbytes)
    v = lay.views(buf)
    assert v["obs"].shape == (2, 2, 4, 3, 6)
    assert v["fctrl"].shape == (2, 2)
    v["obs"][1, 1, 3, 2, 5] = 7.0               # writes land in the buffer
    assert lay.views(buf)["obs"][1, 1, 3, 2, 5] == 7.0
    pv = lay.param_views(buf)
    assert [p.shape for p in pv] == [(3, 5), (7,)]


def test_seqlock_publish_read_roundtrip():
    spec = _spec()
    lay = AsyncLayout(spec)
    buf = bytearray(lay.nbytes)
    v, pviews = lay.views(buf), lay.param_views(buf)
    w = np.arange(15, dtype=np.float32).reshape(3, 5)
    b = np.arange(7, dtype=np.float32)
    v["pseq"][0] += 1
    pviews[0][:] = w
    pviews[1][:] = b
    v["pver"][0] = 3
    v["pseq"][0] += 1
    leaves, ver = read_params_seqlock(v, pviews, shm.SpinConfig())
    assert ver == 3
    np.testing.assert_array_equal(leaves[0], w)
    np.testing.assert_array_equal(leaves[1], b)


def test_seqlock_torn_read_retries_until_commit():
    """A reader that arrives mid-write (odd counter) must spin until the
    write commits and then see the *new* leaves, never a torn mix."""
    spec = _spec()
    lay = AsyncLayout(spec)
    buf = bytearray(lay.nbytes)
    v, pviews = lay.views(buf), lay.param_views(buf)
    v["pseq"][0] = 1                             # writer mid-flight
    pviews[0][:] = 1.0

    def finish_write():
        time.sleep(0.05)
        pviews[0][:] = 2.0
        pviews[1][:] = 2.0
        v["pver"][0] = 9
        v["pseq"][0] = 2                         # commit

    t = threading.Thread(target=finish_write)
    t.start()
    leaves, ver = read_params_seqlock(v, pviews, shm.SpinConfig())
    t.join()
    assert ver == 9
    assert np.all(leaves[0] == 2.0) and np.all(leaves[1] == 2.0)


def _frag(shard, version, seq, fill, T=3, R=2, obs_dim=4):
    a = lambda *s: np.full(s, fill, np.float32)
    return Fragment(
        shard=shard, actor=0, version=version, seq=seq,
        obs=a(T, R, obs_dim), actions=np.full((T, R, 1), fill, np.int32),
        logprobs=a(T, R), values=a(T, R), rewards=a(T, R),
        dones=np.zeros((T, R), bool), resets=np.zeros((T, R), bool),
        infos={"score": a(T, R), "episode_return": a(T, R),
               "episode_length": np.full((T, R), fill, np.int32),
               "valid": np.zeros((T, R), bool)},
        boot=a(R))


def test_stack_fragments_batches_along_rows():
    traj, last = stack_fragments([_frag(0, 0, 0, 1.0), _frag(1, 0, 0, 2.0)])
    assert traj.obs.shape == (3, 4, 4)           # (T, 2 frags × R, obs_dim)
    assert np.all(traj.obs[:, :2] == 1.0) and np.all(traj.obs[:, 2:] == 2.0)
    assert traj.actions.shape == (3, 4, 1)
    assert traj.infos["score"].shape == (3, 4)
    np.testing.assert_array_equal(last, [1.0, 1.0, 2.0, 2.0])


def test_staleness_drop_filter():
    """Drop mode discards fragments older than max_staleness learner
    versions and keeps pulling until the batch is full."""
    from repro.rl.engine import TrainEngine
    frags = [SimpleNamespace(version=v) for v in (2, 5, 3, 4)]

    class FakeRollouts:
        def wait_fragments(self, n, *, timeout):
            assert timeout > 0
            return [frags.pop(0) for _ in range(min(n, len(frags)))]

    fake = SimpleNamespace(
        tcfg=TrainConfig(max_staleness=1, staleness_mode="drop"),
        rollouts=FakeRollouts(), _version=5, _dropped=0)
    out = TrainEngine._collect_fragments(fake, 2)
    assert [f.version for f in out] == [5, 4]    # ages 0 and 1 survive
    assert fake._dropped == 2                    # ages 3 and 2 dropped


def test_vtrace_adv_matches_numpy_reference():
    from repro.core.emulation import Emulated
    from repro.envs.ocean import Bandit
    from repro.models.policy import OceanPolicy
    from repro.rl.distributions import Dist
    from repro.rl.learner import make_vtrace_adv
    from repro.rl.rollout import Trajectory

    em = Emulated(Bandit())
    dist = Dist("categorical", nvec=em.act_spec.nvec)
    pol = OceanPolicy(em.obs_spec.total, dist.nvec, hidden=16,
                      num_outputs=dist.num_outputs)
    params = pol.init(jax.random.PRNGKey(0))
    tcfg = TrainConfig(gamma=0.9)
    T, B = 5, 4
    rng = np.random.default_rng(0)
    obs = rng.normal(size=(T, B, em.obs_spec.total)).astype(np.float32)
    actions = rng.integers(0, int(em.act_spec.nvec[0]),
                           size=(T, B, 1)).astype(np.int32)
    behavior_logp = rng.normal(scale=0.3, size=(T, B)).astype(np.float32)
    rewards = rng.normal(size=(T, B)).astype(np.float32)
    dones = (rng.random((T, B)) < 0.2)
    traj = Trajectory(obs=obs, actions=actions, logprobs=behavior_logp,
                      values=np.zeros((T, B), np.float32), rewards=rewards,
                      dones=dones, resets=np.zeros((T, B), bool), infos={})
    last_value = rng.normal(size=(B,)).astype(np.float32)

    rho_bar, c_bar = 1.0, 1.0
    adv, vs = make_vtrace_adv(pol, dist, tcfg, rho_bar, c_bar)(
        params, traj, last_value)

    # numpy reference: same forward pass, explicit reverse recursion
    logits, values, _ = pol.seq(params, traj.obs, None, traj.resets)
    newlogp = np.asarray(dist.log_prob(logits, traj.actions))
    values = np.asarray(values)
    rho = np.exp(newlogp - behavior_logp)
    rho_c, c = np.minimum(rho, rho_bar), np.minimum(rho, c_bar)
    nd = 1.0 - dones.astype(np.float32)
    v_next = np.concatenate([values[1:], last_value[None]], axis=0)
    delta = rho_c * (rewards + tcfg.gamma * v_next * nd - values)
    vs_ref = np.zeros_like(values)
    acc = np.zeros((B,), np.float32)
    for t in reversed(range(T)):
        acc = delta[t] + tcfg.gamma * nd[t] * c[t] * acc
        vs_ref[t] = acc
    vs_ref = values + vs_ref
    vs_next = np.concatenate([vs_ref[1:], last_value[None]], axis=0)
    adv_ref = rho_c * (rewards + tcfg.gamma * vs_next * nd - values)
    np.testing.assert_allclose(np.asarray(vs), vs_ref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(adv), adv_ref, atol=1e-5)

    # on-policy fragments (behavior == current) give rho = c = 1 exactly
    traj1 = traj._replace(logprobs=newlogp)
    adv1, vs1 = make_vtrace_adv(pol, dist, tcfg)(params, traj1, last_value)
    assert np.all(np.isfinite(np.asarray(adv1)))


# --------------------------- integration layer -------------------------------

def _async_engine(tmpdir=None, **overrides):
    from repro.configs.ocean import ocean_tcfg
    from repro.envs.ocean import Bandit
    from repro.rl.engine import TrainEngine
    from repro.rl.trainer import ocean_policy_stack
    em, dist, policy = ocean_policy_stack(Bandit(), hidden=32,
                                          recurrent=False, conv=None)
    kw = dict(num_envs=8, unroll_length=8, num_actors=2, checkpoint_every=0)
    kw.update(overrides)
    tcfg = ocean_tcfg("bandit", **kw)
    return TrainEngine(em, policy, tcfg, dist, key=jax.random.PRNGKey(0),
                       backend="async",
                       checkpoint_dir=str(tmpdir) if tmpdir else None)


def test_async_config_validation():
    from repro.envs.ocean import Bandit
    with pytest.raises(ValueError):               # 8 envs % 3 shards != 0
        _async_engine(num_actors=3)
    with pytest.raises(ValueError):
        _async_engine(staleness_mode="nope")
    from repro.rl.trainer import ocean_policy_stack
    em, dist, policy = ocean_policy_stack(Bandit(), hidden=32,
                                          recurrent=True, conv=None)
    from repro.configs.ocean import ocean_tcfg
    from repro.rl.engine import TrainEngine
    with pytest.raises(ValueError):               # no recurrent carries in slab
        TrainEngine(em, policy, ocean_tcfg("bandit", num_envs=8,
                                           unroll_length=8),
                    dist, key=jax.random.PRNGKey(0), backend="async",
                    checkpoint_dir=None)


@pytest.mark.timeout(300)
def test_async_tier_runs_and_accounts():
    eng = _async_engine()
    spu = 8 * 8
    try:
        hist, solved = eng.run(total_steps=spu * 4)
        assert len(hist) == 4
        assert hist[-1]["env_steps"] == 4 * spu
        for k in ("frag_age_mean", "frag_age_max", "dropped_fragments",
                  "stragglers", "actors_alive", "reshards", "sps"):
            assert k in hist[-1], k
        assert hist[-1]["actors_alive"] == 2
        assert hist[-1]["reshards"] == 0
    finally:
        eng.close()


@pytest.mark.timeout(300)
def test_async_kill_actor_reshards_without_hang():
    """Acceptance: killing one actor mid-run reassigns its shards to the
    survivor and the run completes (bounded by the pytest timeout)."""
    eng = _async_engine()
    spu = 8 * 8
    killed = {"done": False}

    def on_update(u, md):
        if u >= 1 and not killed["done"]:
            eng.rollouts._procs[1].terminate()
            # wait until it is gone: fragments already queued could
            # otherwise finish the run before the death is observable
            eng.rollouts._procs[1].join(timeout=30)
            killed["done"] = True

    try:
        hist, _ = eng.run(total_steps=spu * 6, on_update=on_update)
        assert len(hist) == 6                    # no updates lost
        assert len(eng.rollouts.events) == 1
        ev = eng.rollouts.events[0]
        assert ev.actor == 1 and ev.new_owners == (0,)
        st = eng.rollouts.stats()
        assert st["assign"] == [0, 0] and st["dead"] == [1]
        assert st["epoch"][ev.shards[0]] == 1    # new owner re-seeds
        assert hist[-1]["actors_alive"] == 1
    finally:
        eng.close()


@pytest.mark.timeout(600)
def test_async_kill_then_resume_step_count(tmp_path):
    """Acceptance: a learner killed mid-run resumes from its checkpoint and
    ends at the same step count as an uninterrupted run."""
    from repro.checkpoint import ckpt

    spu = 8 * 8
    eng = _async_engine(tmp_path, checkpoint_every=2)

    class Kill(BaseException):                   # not caught by ResilientLoop
        pass

    def on_update(u, md):
        if u >= 2:                               # updates 1..3 done, ckpt at 2
            raise Kill

    try:
        with pytest.raises(Kill):
            eng.run(total_steps=spu * 6, on_update=on_update)
    finally:
        eng.close()
    time.sleep(0.5)                              # async ckpt thread lands
    assert ckpt.step_of(ckpt.latest(str(tmp_path))) == 2

    eng2 = _async_engine(tmp_path, checkpoint_every=2)
    try:
        assert eng2.restore() == 2
        hist, _ = eng2.run(total_steps=spu * 6)
    finally:
        eng2.close()
    assert len(hist) == 4                        # updates 3..6 only
    assert hist[-1]["env_steps"] == 6 * spu
    assert ckpt.step_of(ckpt.latest(str(tmp_path))) == 6


@pytest.mark.slow
@pytest.mark.timeout(1800)
def test_async_tier_trains_bandit_two_actors():
    """Acceptance: the async tier actually trains — bandit to >= 0.9 with 2
    actors under the committed preset budget."""
    from repro.configs.ocean import preset
    p = preset("bandit")
    eng = _async_engine(num_envs=64, unroll_length=64, num_actors=2)
    try:
        hist, solved = eng.run(total_steps=p.total_steps, target_score=0.9)
    finally:
        eng.close()
    best = max(m["score"] for m in hist if m["episodes"] > 0)
    assert solved is not None or best >= 0.9, (
        f"async tier failed to train bandit: best score {best:.3f} over "
        f"{len(hist)} updates")
