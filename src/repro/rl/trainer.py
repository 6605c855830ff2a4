"""Host-side training driver: Ocean / small-env PPO with checkpoint-restart.

Now a thin facade over ``rl.engine.TrainEngine`` — the engine owns the
device-resident state, the fused K-updates-per-dispatch launch, and the
execution tier (jit / shard_map / pool); the Trainer keeps the stable
user-facing API (construction from a raw env, ``train``, ``save``/
``restore``, history, logging) that tests, examples, and the CLI use.

The old per-update ``{k: float(v)}`` host sync is gone: metrics are fetched
with one ``jax.device_get`` per launch, one launch late when no
``target_score`` is requested, so JAX async dispatch actually overlaps.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.core.emulation import Emulated
from repro.models.policy import OceanPolicy
from repro.rl.distributions import Dist
from repro.rl.engine import TrainEngine
from repro.rl.learner import TrainState


def ocean_policy_stack(env, hidden: int = 128, recurrent: bool = False,
                       conv: bool = None):
    """Derive ``(Emulated, Dist, OceanPolicy)`` from a raw Ocean-protocol
    env — the ONE place the env→policy derivation lives (action kind from
    the emulated action spec, the CNN frontend from ``obs_frontend``).
    Used by the Trainer, the league (build_league, CLI), and benchmarks."""
    from repro.core import spaces as sp
    em = Emulated(env)
    if em.act_spec.kind == "discrete":
        dist = Dist("categorical", nvec=em.act_spec.nvec)
    else:       # continuous actions — paper §8 extension
        dist = Dist("gaussian", cont_dim=em.act_spec.cont_dim)
    # pixel envs opt in to the CNN frontend via `obs_frontend = "conv"`;
    # the policy then restores the emulated-flat obs to its 2D layout
    if conv is None:
        conv = getattr(env, "obs_frontend", None) == "conv"
    conv_shape = None
    if conv:
        space = env.observation_space
        if not (isinstance(space, sp.Box) and len(space.shape) == 2):
            raise ValueError(
                f"conv frontend needs a single 2D Box observation, got "
                f"{space}")
        conv_shape = space.shape
    policy = OceanPolicy(em.obs_spec.total, dist.nvec, hidden=hidden,
                         recurrent=recurrent,
                         num_outputs=dist.num_outputs,
                         conv_shape=conv_shape)
    return em, dist, policy


class Trainer:
    def __init__(self, env, tcfg: TrainConfig = None, hidden: int = 128,
                 recurrent: bool = False, seed: int = 0,
                 kernel_mode: str = None, log_dir: str = None,
                 backend: str = None, updates_per_launch: int = None,
                 mesh=None, conv: bool = None, num_shards: int = 1):
        from repro.utils.metrics import MetricsLogger
        self.logger = MetricsLogger(log_dir,
                                    run_name=type(env).__name__.lower())
        self.tcfg = tcfg or TrainConfig()
        self.em, self.dist, self.policy = ocean_policy_stack(
            env, hidden=hidden, recurrent=recurrent, conv=conv)
        self.engine = TrainEngine(self.em, self.policy, self.tcfg, self.dist,
                                  key=jax.random.PRNGKey(seed),
                                  backend=backend,
                                  updates_per_launch=updates_per_launch,
                                  mesh=mesh, kernel_mode=kernel_mode,
                                  num_shards=num_shards)
        self.history = []

    # engine state, exposed under the historical names ------------------------
    @property
    def ts(self) -> TrainState:
        return self.engine.ts

    @property
    def rc(self):
        return self.engine.rc

    @property
    def vec(self):
        return self.engine.vec

    @property
    def steps_per_update(self) -> int:
        return self.engine.steps_per_update

    def train(self, total_steps: int, log_every: int = 0,
              target_score: Optional[float] = None,
              checkpoint_dir: Optional[str] = None, resume: bool = False,
              on_launch=None):
        """Run until total env interactions ≥ total_steps (or solved).
        ``target_score`` is checked at launch boundaries (identical to
        per-update for K = 1). With ``checkpoint_dir`` the engine saves its
        full resumable state every ``tcfg.checkpoint_every`` updates
        (async, at the launch boundary); ``resume=True`` restores the
        newest committed checkpoint first and continues from its update
        count. Metrics stream through the engine into ``self.logger``
        (one flush per launch, crash-safe final flush in the engine)."""
        from repro.checkpoint import ckpt
        if checkpoint_dir:
            self.engine.checkpoint_dir = checkpoint_dir
            if resume and ckpt.latest(checkpoint_dir) is not None:
                u0 = self.engine.restore(checkpoint_dir)
                print(f"  resumed at update {u0}")

        def on_update(u, m):
            self.history.append(m)
            if log_every and (u % log_every == 0):
                print(f"  upd {u:4d} steps {m['env_steps']:7d} "
                      f"score {m['score']:.3f} "
                      f"ret {m['episode_return']:.3f} "
                      f"kl {m['approx_kl']:.4f} "
                      f"sps {m['sps']:.0f}")

        _, solved = self.engine.run(total_steps, target_score=target_score,
                                    on_update=on_update,
                                    on_launch=on_launch, logger=self.logger)
        if solved is not None:
            return solved
        # a fully-resumed run may have no new updates to report
        return self.history[-1] if self.history else {}

    def save(self, ckpt_dir: str):
        from repro.checkpoint import ckpt
        ckpt.save(ckpt_dir, {"params": self.ts.params,
                             "opt": self.ts.opt, "step": self.ts.step})

    def restore(self, ckpt_dir: str):
        from repro.checkpoint import ckpt
        tree = ckpt.restore(ckpt_dir, {"params": self.ts.params,
                                       "opt": self.ts.opt,
                                       "step": self.ts.step})
        self.engine.set_train_state(
            TrainState(tree["params"], tree["opt"], tree["step"]))
