"""Drives Ocean Squared PPO through the ``--ocean`` trainer
(``launch.train.ocean_trainer`` and ``Trainer.train``) on the jit or
shard_map tier, as the launcher does, with the traffic file's env count.

Set-up builds one trainer, runs its first ``READ_STEPS`` updates one
``train`` call each and keeps host copies of what the check compares; the
window then continues the same trainer. Checkpoints and the target-score
stop are off: the window measures steady training, with the engine's one
launch in flight.
"""
from __future__ import annotations

import time
from pathlib import Path

from bench import faults

READ_STEPS = 3
HERE = Path(__file__).resolve().parent


def _reference():
    from bench import harness
    return harness.load_module(HERE / "reference.py", "bench_ref_ocean_squared")


class Cell:
    unit = "agent_steps"

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        self.cfg, self.traffic, self.seed, self.chips = (config, traffic,
                                                         seed, chips)
        self.tier = traffic["tier"]
        self.num_envs = traffic["num_envs"]
        self.tr = None

    # -- the program ---------------------------------------------------------
    def setup(self):
        import jax
        from bench import compare
        from repro.launch.train import ocean_trainer
        t0 = time.perf_counter()
        self.tr, _ = ocean_trainer(self.cfg["env"], seed=self.seed,
                                   engine_backend=self.tier,
                                   num_envs=self.num_envs)
        tr = self.tr
        self.spu = tr.steps_per_update
        p0 = jax.device_get(tr.ts.params)
        self.phases = {"build": time.perf_counter() - t0}
        losses, times = [], []
        for u in range(READ_STEPS):
            t0 = time.perf_counter()
            tr.train(self.spu)
            jax.block_until_ready(tr.ts)
            times.append(time.perf_counter() - t0)
            losses.append(tr.history[-1]["loss"])
            if u == 0:
                m1 = jax.device_get(tr.ts.opt.m)
        t0 = time.perf_counter()
        self.prog = compare.side(losses, compare.leaf_norms(m1), p0,
                                 jax.device_get(tr.ts.params))
        self.phases.update(first_update=times[0], next_updates=sum(times[1:]),
                           host_copies=time.perf_counter() - t0)
        self.t_update = min(times[1:])

    def window(self, seconds: float):
        """Train for about ``seconds``; returns (agent steps, seconds,
        updates). ``self.intervals`` keeps the host time between launches:
        with one launch in flight, about one update's device time each."""
        import jax
        n = max(1, round(seconds / self.t_update))
        stamps = []
        t0 = time.perf_counter()
        self.tr.train(n * self.spu,
                      on_launch=lambda u: stamps.append(time.perf_counter()))
        jax.block_until_ready(self.tr.ts)
        t1 = time.perf_counter()
        self.intervals = [b - a for a, b in zip(stamps, stamps[1:])]
        return n * self.spu, t1 - t0, n

    def release(self):
        if self.tr is not None:
            self.tr.engine.close()
            self.tr.logger.close()
        self.tr = None

    # -- what the per-layer readers need -------------------------------------
    def info(self) -> dict:
        from bench import flops, trace
        c = self.cfg
        return {
            "flops_per_unit": flops.ocean_flops_per_agent_step(
                c["obs_dim"], c["hidden"], c["num_actions"],
                c["update_epochs"], c["unroll_length"]),
            "kernels": {"gae": trace.kernel_pattern("gae")},
            "kernel_cost": {"gae": flops.gae_cost(
                c["unroll_length"], self.num_envs // self.chips)},
        }

    # -- correct -------------------------------------------------------------
    def numbers(self, ref: dict = None) -> dict:
        """The compared numbers: the program's first steps against the
        reference's side (``reference_side()`` unless given)."""
        from bench import compare
        return compare.training_numbers(self.prog,
                                        ref or self.reference_side())

    def reference_side(self, control: bool = False) -> dict:
        """The reference's side of the comparison; ``control`` runs it in
        bfloat16."""
        import jax.numpy as jnp
        from bench import compare
        shards = self.chips if self.tier == "shard_map" else 1
        r = _reference().run(self.cfg, self.seed, self.num_envs,
                             shards=shards, updates=READ_STEPS,
                             dtype=jnp.bfloat16 if control else jnp.float32)
        return compare.side(r["losses"], compare.leaf_norms(r["m1"]),
                            r["p0"], r["p_last"])


# -- faults planted under the timed path, for the control tests ---------------

def _learner_jax(permutation=None, pmean=None):
    """Patch what ``rl/learner.py`` sees as ``jax``: a stand-in module that
    forwards everything to JAX except the functions given. Nothing outside
    the learner, the reference included, is touched."""
    import types

    import jax
    from repro.rl import learner

    class Proxy(types.ModuleType):
        def __init__(self, real, **over):
            super().__init__(real.__name__)
            self._real = real
            self.__dict__.update({k: v for k, v in over.items() if v})

        def __getattr__(self, name):
            return getattr(self._real, name)

    proxy = Proxy(jax, random=Proxy(jax.random, permutation=permutation),
                  lax=Proxy(jax.lax, pmean=pmean))
    return faults.patched(learner, "jax", proxy)


def fault_half_batch():
    """Every minibatch the learner draws holds its first half twice: the
    other half is left out and the mean is taken over the rest."""
    import jax
    import jax.numpy as jnp

    def permutation(key, n, *a, **kw):
        q = jax.random.permutation(key, n, *a, **kw).reshape(4, -1)
        h = q.shape[1] // 2
        return jnp.concatenate([q[:, :h], q[:, :h]], axis=1).reshape(-1)
    return _learner_jax(permutation=permutation)


def fault_no_exchange():
    """The learner's gradient and loss mean across chips is left out: each
    chip steps on its own shard's gradient."""
    return _learner_jax(pmean=lambda x, axis_name: x)


def fault_action_altered():
    """Every sampled action is replaced by the next one where the rollout
    draws it."""
    from repro.rl import distributions as D
    real = D.sample

    def sample(key, logits, nvec):
        a = real(key, logits, nvec)
        return (a + 1) % nvec[0]
    return faults.patched(D, "sample", sample)


FAULTS = {"state_unchanged": faults.state_unchanged,
          "half_batch": fault_half_batch,
          "action_altered": fault_action_altered,
          "no_exchange": fault_no_exchange}
