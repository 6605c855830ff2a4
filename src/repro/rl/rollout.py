"""On-device rollout: the paper's actor loop as one fused scan.

The entire unroll (policy step + env step + auto-reset, T times) lives inside
jit — the endpoint of the paper's trajectory away from per-step host IPC
("only one step per episode requires communication" → zero). The EnvPool
double-buffered host loop in core/pool.py covers host-bound envs; this path
covers JAX-native ones.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.rl import distributions as D


class Trajectory(NamedTuple):
    obs: jax.Array        # (T, B, obs)
    actions: jax.Array    # (T, B, n_comp)
    logprobs: jax.Array   # (T, B)
    values: jax.Array     # (T, B)
    rewards: jax.Array    # (T, B)
    dones: jax.Array      # (T, B)  done AT this step
    resets: jax.Array     # (T, B)  obs at this step began an episode
    infos: dict           # (T, num_envs) pytree


class RolloutCarry(NamedTuple):
    env_state: object
    obs: jax.Array
    policy_carry: object
    done_prev: jax.Array


def rollout(policy, params, step_fn, carry: RolloutCarry, key,
            unroll: int, dist, keyed=None):
    """Returns (carry', Trajectory, last_value (B,)). ``dist`` is a
    distributions.Dist (categorical or gaussian — paper §8 extension).

    ``keyed``: None → legacy randomness (one key per step, split per env
    inside ``step_fn``). Otherwise ``(num_envs, env_offset)``: per-env keys
    derived from the *global* env index ``env_offset + arange(num_envs)``,
    and ``step_fn`` must accept ``(state, actions, keys)`` with one key per
    env (``VecEnv.step_keyed_fn``). This makes the rollout bitwise
    independent of how envs are sharded across devices — device d of an
    S-way data-parallel run passes ``env_offset = d * (B // S)`` and draws
    exactly the keys the single-device run draws for those envs.
    """

    # named scopes mark the device ops of each phase in the compiled
    # program's metadata (``TrainEngine.op_scopes``); one fresh context per
    # ``with``, as ``jax.named_scope`` objects are single-use
    def one(c: RolloutCarry, k):
        k_act, k_env = jax.random.split(k)
        logits, value, pc = policy.step(params, c.obs, c.policy_carry,
                                        reset=c.done_prev)
        if keyed is None:
            action = dist.sample(k_act, logits)
            logp = dist.log_prob(logits, action)
            with jax.named_scope("ppo_env_step"):
                env_state, obs, rew, done, info = step_fn(c.env_state,
                                                          action, k_env)
        else:
            num_envs, off = keyed
            batch = logits.shape[0]
            agents = batch // num_envs
            act_idx = off * agents + jnp.arange(batch)
            act_keys = jax.vmap(lambda i: jax.random.fold_in(k_act, i))(
                act_idx)
            action = jax.vmap(dist.sample)(act_keys, logits)
            logp = dist.log_prob(logits, action)
            env_keys = jax.vmap(lambda i: jax.random.fold_in(k_env, i))(
                off + jnp.arange(num_envs))
            with jax.named_scope("ppo_env_step"):
                env_state, obs, rew, done, info = step_fn(c.env_state,
                                                          action, env_keys)
        out = Trajectory(c.obs, action, logp, value, rew, done,
                         c.done_prev, info)
        return RolloutCarry(env_state, obs, pc, done), out

    with jax.named_scope("ppo_rollout"):
        keys = jax.random.split(key, unroll)
        carry, traj = jax.lax.scan(one, carry, keys)

        # bootstrap value for GAE
        _, last_value, _ = policy.step(params, carry.obs, carry.policy_carry,
                                       reset=carry.done_prev)
    return carry, traj, last_value
