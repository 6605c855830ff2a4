"""Learners (Clean PuffeRL): the fused Ocean PPO update and the LM-backbone
PPO ``train_step`` that the multi-pod dry-run lowers.

Ocean path: rollout → GAE → minibatched clipped-PPO epochs, all one jit'd
program per update. Recurrent policies recompute hidden states through whole
stored sequences with per-step reset masking (the LSTM-state handling the
paper singles out as the common bug).

LM path: one PPO update on a (B, T) token rollout — the paper's actor/learner
loop at datacenter scale. GAE runs the Pallas kernel; policy terms use the
chunked-vocab loss; AdamW states stay ZeRO-sharded.

Kernel backends (GAE, flash attention, …) come from the kernels.dispatch
registry: ``kernel_mode``/``gae_mode`` of ``None`` means the registry picks
(Pallas on TPU, ref on CPU, env/``dispatch.using`` overrides respected).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig, ModelConfig
from repro.kernels import ops as kops
from repro.models import transformer as tr
from repro.optim import adamw, schedule
from repro.rl import distributions as D
from repro.rl import ppo
from repro.rl.rollout import rollout, RolloutCarry, Trajectory


class TrainState(NamedTuple):
    params: object
    opt: adamw.AdamWState
    step: jax.Array


def init_train_state(params, state_dtype=jnp.float32) -> TrainState:
    return TrainState(params, adamw.init(params, state_dtype),
                      jnp.zeros((), jnp.int32))


# =============================== Ocean =======================================

def _shard_index(axis_name):
    """Global shard index over (possibly multiple) data axes, row-major."""
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    idx = jnp.zeros((), jnp.int32)
    for a in names:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def _check_divisible(n, M, num_envs, num_minibatches, unroll_length, what):
    if n % M != 0:
        raise ValueError(
            f"{what} ({n}) is not divisible by num_minibatches="
            f"{num_minibatches} (num_envs={num_envs}, "
            f"num_minibatches={num_minibatches}, "
            f"unroll_length={unroll_length}); pick num_envs / unroll_length "
            f"so each PPO minibatch has the same size")


def make_vtrace_adv(policy, dist, tcfg: TrainConfig,
                    rho_clip: float = 1.0, c_clip: float = 1.0):
    """V-trace advantage/target computation (IMPALA) for the async tier's
    off-policy fragments: truncated importance weights correct for the
    policy-version lag between the actor that produced a fragment and the
    learner consuming it. Plugs into ``make_ocean_learn(adv_fn=...)``.

    rho/c are computed per sample as exp(logpi_current − logpi_behavior)
    and clamped at ``rho_clip`` / ``c_clip``; on-policy fragments give
    rho = c = 1 exactly, so the estimator degrades to one-step-λ=1 GAE-like
    targets as staleness → 0. Non-recurrent policies only (the fragment
    slab does not ship carries)."""
    if policy.recurrent:
        raise ValueError("make_vtrace_adv supports non-recurrent policies "
                         "(fragments carry no recurrent state)")

    def adv_fn(params, traj, last_value):
        # one forward pass under the *current* policy over the whole batch
        logits, values, _ = policy.seq(params, traj.obs, None, traj.resets)
        newlogp = dist.log_prob(logits, traj.actions)
        rho = jnp.exp(newlogp - traj.logprobs)
        rho_c = jnp.minimum(rho, rho_clip)
        c = jnp.minimum(rho, c_clip)
        nd = 1.0 - traj.dones.astype(jnp.float32)     # no bootstrap across
        v_next = jnp.concatenate([values[1:], last_value[None]], axis=0)
        delta = rho_c * (traj.rewards + tcfg.gamma * v_next * nd - values)

        def back(acc, x):
            d_t, c_t, nd_t = x
            acc = d_t + tcfg.gamma * nd_t * c_t * acc
            return acc, acc

        _, vs_minus_v = jax.lax.scan(back, jnp.zeros_like(last_value),
                                     (delta, c, nd), reverse=True)
        vs = values + vs_minus_v
        vs_next = jnp.concatenate([vs[1:], last_value[None]], axis=0)
        adv = rho_c * (traj.rewards + tcfg.gamma * vs_next * nd - values)
        # vs are the value targets; both are fixed targets for the PPO
        # epochs (computed once from pre-update params, like GAE)
        return jax.lax.stop_gradient(adv), jax.lax.stop_gradient(vs)

    return adv_fn


def make_ocean_learn(policy, tcfg: TrainConfig, dist,
                     kernel_mode: str = None, axis_name=None,
                     num_shards: int = 1, adv_fn=None):
    """The post-rollout half of the fused update: GAE → minibatched
    clipped-PPO epochs. Returns jit-able
    ``learn(ts, carry0, traj, last_value, key) → (ts, metrics)``.

    Factored out of ``make_ocean_update`` so the TrainEngine's pool tier
    (host-collected trajectories) reuses the exact same learning math as the
    fused jit / shard_map tiers.

    ``axis_name`` — set when running inside ``shard_map``: ``traj`` then
    holds this device's env shard, minibatch permutations are drawn per
    shard, gradients/stats are pmean'd and advantage normalization uses
    global (psum) statistics.

    ``num_shards`` — the S of the data-parallel layout. With
    ``axis_name=None`` and S > 1 the single device *emulates* the S-way
    block structure: envs are permuted within S contiguous blocks and global
    minibatch m is the union of every block's m-th slice. That makes the
    update semantically identical (up to float reduction order) whether it
    runs on 1 device or S — the seed-matched multi-device parity the
    engine's tests and benchmark rely on.

    ``adv_fn`` — optional ``(params, traj, last_value) -> (adv, returns)``
    replacing the on-policy GAE (e.g. ``make_vtrace_adv`` for the async
    tier's off-policy fragments). Computed once per update from the
    pre-update params, exactly where GAE runs.
    """
    E, M = tcfg.update_epochs, tcfg.num_minibatches
    S = num_shards

    def learn(ts: TrainState, carry0, traj: Trajectory, last_value, key):
        T, B = traj.rewards.shape                       # local shapes
        B_global = B * (S if axis_name is not None else 1)

        if adv_fn is None:
            adv = kops.gae(traj.rewards, traj.values, traj.dones,
                           last_value, tcfg.gamma, tcfg.gae_lambda,
                           mode=kernel_mode)                   # (T, B)
            returns = adv + traj.values
        else:
            adv, returns = adv_fn(ts.params, traj, last_value)

        if policy.recurrent:
            # minibatch over envs; recompute through full sequences
            n_block = B if axis_name is not None else B // S
            _check_divisible(n_block, M, B_global, M, T,
                             f"envs per data shard ({S} shards)")

            def loss_fn(params, idx):
                obs = traj.obs[:, idx]
                logits, newv, _ = policy.seq(
                    params, obs,
                    jax.tree.map(lambda c: c[idx], carry0)
                    if carry0 is not None else None,
                    traj.resets[:, idx])
                newlogp = dist.log_prob(logits, traj.actions[:, idx])
                ent = dist.entropy(logits)
                a = ppo.normalize_adv(adv[:, idx], tcfg.norm_adv, axis_name)
                pg, kl, cf = ppo.ppo_terms(newlogp, traj.logprobs[:, idx],
                                           a, tcfg)
                vl = ppo.value_loss(newv, traj.values[:, idx],
                                    returns[:, idx], tcfg)
                loss = pg - tcfg.ent_coef * jnp.mean(ent) + tcfg.vf_coef * vl
                return loss, ppo.PPOStats(pg, vl, jnp.mean(ent), kl, cf)

            n_loc = n_block
            to_global = lambda p, s: s * n_block + p
        else:
            flat = jax.tree.map(
                lambda x: x.reshape((T * B,) + x.shape[2:]),
                Trajectory(traj.obs, traj.actions, traj.logprobs, traj.values,
                           traj.rewards, traj.dones, traj.resets, {}))
            flat_adv = adv.reshape(-1)
            flat_ret = returns.reshape(-1)
            n_block = B if axis_name is not None else B // S
            _check_divisible(T * n_block, M, B_global, M, T,
                             f"samples per data shard ({S} shards)")

            def loss_fn(params, idx):
                logits, newv, _ = policy.step(params, flat.obs[idx], None)
                newlogp = dist.log_prob(logits, flat.actions[idx])
                ent = dist.entropy(logits)
                a = ppo.normalize_adv(flat_adv[idx], tcfg.norm_adv, axis_name)
                pg, kl, cf = ppo.ppo_terms(newlogp, flat.logprobs[idx], a, tcfg)
                vl = ppo.value_loss(newv, flat.values[idx], flat_ret[idx], tcfg)
                loss = pg - tcfg.ent_coef * jnp.mean(ent) + tcfg.vf_coef * vl
                return loss, ppo.PPOStats(pg, vl, jnp.mean(ent), kl, cf)

            n_loc = T * n_block
            # block-local flat index (t * n_block + e) → global (t * B + env)
            to_global = lambda p, s: ((p // n_block) * B + s * n_block
                                      + p % n_block)

        def mb_step(ts: TrainState, idx):
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(ts.params, idx)
            if axis_name is not None:
                grads = jax.lax.pmean(grads, axis_name)
                loss, stats = jax.lax.pmean((loss, stats), axis_name)
            params, opt, gstats = adamw.update(
                grads, ts.opt, ts.params, lr=tcfg.learning_rate,
                b1=tcfg.adam_b1, b2=tcfg.adam_b2, eps=tcfg.adam_eps,
                weight_decay=tcfg.weight_decay,
                max_grad_norm=tcfg.max_grad_norm)
            ts = TrainState(params, opt, ts.step + 1)
            return ts, (loss, stats, gstats["grad_norm"])

        # epochs × minibatches of shuffled indices, one scan. Per-block keys
        # (fold_in of the shard index) keep the index stream identical
        # between a real S-device run and the single-device S-block emulation.
        def epoch_perm(k):
            if axis_name is not None:
                s = _shard_index(axis_name)
                p = jax.random.permutation(jax.random.fold_in(k, s), n_loc)
                return p.reshape(M, n_loc // M)
            if S == 1:
                return jax.random.permutation(k, n_loc).reshape(M, n_loc // M)
            blocks = []
            for s in range(S):
                p = jax.random.permutation(jax.random.fold_in(k, s), n_loc)
                blocks.append(to_global(p, s).reshape(M, n_loc // M))
            return jnp.concatenate(blocks, axis=1)

        idxs = jnp.concatenate(
            [epoch_perm(jax.random.fold_in(key, e)) for e in range(E)])
        ts, (losses, stats, gnorms) = jax.lax.scan(mb_step, ts, idxs)

        # episode stats from infos (paper: aggregate once per episode)
        psum = ((lambda x: jax.lax.psum(x, axis_name))
                if axis_name is not None else (lambda x: x))
        valid = traj.infos["valid"]
        nv = jnp.maximum(1.0, psum(jnp.sum(valid)))
        metrics = {
            "loss": losses[-1],
            "pg_loss": stats.pg_loss[-1],
            "v_loss": stats.v_loss[-1],
            "entropy": stats.entropy[-1],
            "approx_kl": stats.approx_kl[-1],
            "clipfrac": stats.clipfrac[-1],
            "grad_norm": gnorms[-1],
            "score": psum(jnp.sum(traj.infos["score"] * valid)) / nv,
            "episode_return":
                psum(jnp.sum(traj.infos["episode_return"] * valid)) / nv,
            "episodes": psum(jnp.sum(valid)),
        }
        return ts, metrics

    return learn


def make_ocean_update(policy, step_fn, tcfg: TrainConfig, dist,
                      num_envs: int, kernel_mode: str = None,
                      axis_name=None, num_shards: int = 1,
                      keyed_step: bool = False):
    """Returns jit-able ``update(ts, rollout_carry, key)``. ``dist`` is a
    distributions.Dist (categorical or gaussian).

    ``keyed_step`` — ``step_fn`` takes per-env keys (``step_keyed_fn``) and
    the rollout derives them from global env indices; required for the
    shard-invariant randomness of the engine's shard_map tier (``num_envs``
    is then the *local* env count of one shard).
    """
    T = tcfg.unroll_length
    learn = make_ocean_learn(policy, tcfg, dist, kernel_mode=kernel_mode,
                             axis_name=axis_name, num_shards=num_shards)

    def update(ts: TrainState, rc: RolloutCarry, key):
        k_roll, k_perm = jax.random.split(key)
        carry0 = rc.policy_carry
        if keyed_step:
            off = (_shard_index(axis_name) * num_envs
                   if axis_name is not None else jnp.zeros((), jnp.int32))
            keyed = (num_envs, off)
        else:
            keyed = None
        rc, traj, last_value = rollout(policy, ts.params, step_fn, rc,
                                       k_roll, T, dist, keyed=keyed)
        ts, metrics = learn(ts, carry0, traj, last_value, k_perm)
        return ts, rc, metrics

    return update


# =============================== LM backbone =================================

def lm_batch_fields(cfg: ModelConfig, batch_size: int, seq_len: int):
    """ShapeDtypeStruct fields of one LM PPO rollout batch (used by both the
    data pipeline and launch.dryrun input_specs)."""
    P = cfg.frontend_prefix if cfg.frontend else 0
    f = {
        "tokens": ((batch_size, seq_len - P), jnp.int32),
        "actions": ((batch_size, seq_len), jnp.int32),
        "old_logprob": ((batch_size, seq_len), jnp.float32),
        "old_values": ((batch_size, seq_len), jnp.float32),
        "rewards": ((batch_size, seq_len), jnp.float32),
        "dones": ((batch_size, seq_len), jnp.bool_),
        "last_value": ((batch_size,), jnp.float32),
    }
    if P:
        f["prefix"] = ((batch_size, P, cfg.d_model), jnp.bfloat16)
    return f


def make_lm_train_step(policy, tcfg: TrainConfig, total_steps: int = 10_000,
                       gae_mode: str = None, loss_chunk: int = 256,
                       num_microbatches: int = 1):
    """One PPO update on a token rollout — the train_4k dry-run program.

    ``num_microbatches > 1``: gradient accumulation over batch slices (scan),
    dividing activation residency by m at the cost of re-gathering FSDP
    weights per microbatch — the HBM-fit lever for the 400B-class cells
    (EXPERIMENTS.md §Perf)."""
    cfg = policy.cfg

    def loss_fn(params, batch):
        inputs = {"tokens": batch["tokens"]}
        if "prefix" in batch:
            inputs["prefix"] = batch["prefix"]
        hidden, aux = tr.forward(params["backbone"], inputs, cfg,
                                 policy.tp, kernel=policy.kernel)
        values = policy._value(params, hidden)              # (B, T)

        # the token batch is (B, T); GAE runs time-major
        adv = kops.gae(batch["rewards"].T, batch["old_values"].T,
                       batch["dones"].T, batch["last_value"],
                       tcfg.gamma, tcfg.gae_lambda, mode=gae_mode).T
        returns = adv + batch["old_values"]
        adv = ppo.normalize_adv(adv, tcfg.norm_adv)

        pg, ent, kl, cf = ppo.chunked_token_loss(
            params["backbone"], hidden, batch["actions"],
            batch["old_logprob"], adv, cfg, tcfg, chunk=loss_chunk)
        vl = ppo.value_loss(values, batch["old_values"], returns, tcfg)
        loss = (pg - tcfg.ent_coef * ent + tcfg.vf_coef * vl
                + 0.01 * aux["moe_aux"])
        return loss, {"pg_loss": pg, "v_loss": vl, "entropy": ent,
                      "approx_kl": kl, "clipfrac": cf,
                      "moe_aux": aux["moe_aux"]}

    def train_step(ts: TrainState, batch):
        if num_microbatches > 1:
            m = num_microbatches
            mb = jax.tree.map(
                lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]),
                batch)

            def acc(gacc, one):
                (l, st), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    ts.params, one)
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), gacc, g)
                return gacc, (l, st)

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              ts.params)
            gacc, (ls, sts) = jax.lax.scan(acc, g0, mb)
            grads = jax.tree.map(lambda g: g / m, gacc)
            loss = jnp.mean(ls)
            stats = jax.tree.map(jnp.mean, sts)
        else:
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(ts.params, batch)
        lr = schedule.warmup_cosine(ts.step, peak_lr=tcfg.learning_rate,
                                    warmup_steps=tcfg.warmup_steps,
                                    total_steps=total_steps)
        params, opt, gstats = adamw.update(
            grads, ts.opt, ts.params, lr=lr, b1=tcfg.adam_b1, b2=tcfg.adam_b2,
            eps=tcfg.adam_eps, weight_decay=tcfg.weight_decay,
            max_grad_norm=tcfg.max_grad_norm)
        metrics = dict(stats, loss=loss, lr=lr, grad_norm=gstats["grad_norm"])
        return TrainState(params, opt, ts.step + 1), metrics

    return train_step
