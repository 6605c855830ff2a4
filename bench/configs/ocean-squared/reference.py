"""Plain reference for the ``ocean-squared`` configuration: the Squared
grid env, the two-layer tanh MLP policy with action and value heads, the
categorical rollout, GAE, clipped PPO with minibatch advantage
normalization and a clipped value loss, and AdamW with global-norm
clipping, written out in ``jax.numpy`` from the configuration file alone,
with matrix multiplications at the precision the configuration states
(``matmul_precision``).

It reproduces the training run's random stream from the seed: parameter
init, the per-update keys, per-env action keys and per-epoch minibatch
permutations, with ``shards`` data-parallel blocks where the run has them.
It imports nothing of the program and takes nothing the program made.
``dtype`` is the precision the whole computation runs in (float32, or
bfloat16 for the control)."""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

LEAVES = ("act", "b1", "b2", "b_act", "b_val", "enc1", "enc2", "val")


def init_params(cfg, key, dtype):
    """The policy's parameters from ``key``: one key per leaf in sorted
    leaf order, normal / sqrt(fan_in) for the weights, zero biases."""
    d, h, a = cfg["obs_dim"], cfg["hidden"], cfg["num_actions"]
    shapes = {"act": (h, a), "b1": (h,), "b2": (h,), "b_act": (a,),
              "b_val": (1,), "enc1": (d, h), "enc2": (h, h), "val": (h, 1)}
    keys = jax.random.split(key, len(LEAVES))
    out = {}
    for name, k in zip(LEAVES, keys):
        shape = shapes[name]
        if name.startswith("b"):
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         / np.sqrt(shape[0])).astype(dtype)
    return out


def forward(p, obs):
    h = jnp.tanh(obs @ p["enc1"] + p["b1"])
    h = jnp.tanh(h @ p["enc2"] + p["b2"])
    return h @ p["act"] + p["b_act"], (h @ p["val"] + p["b_val"])[..., 0]


class Squared:
    """g x g grid, agent starts in the centre, targets on the perimeter;
    reward 1 - L-inf distance to the nearest unhit target / (g // 2)."""

    MOVES = np.array([[0, 0], [-1, 0], [1, 0], [0, -1], [0, 1]])

    def __init__(self, g, horizon):
        self.g, self.horizon = g, horizon
        per = np.zeros((g, g), bool)
        per[0, :] = per[-1, :] = per[:, 0] = per[:, -1] = True
        self.per = jnp.asarray(per)
        ii, jj = np.meshgrid(np.arange(g), np.arange(g), indexing="ij")
        self.coords = jnp.asarray(np.stack([ii, jj], -1))

    def init(self, n):
        g = self.g
        return {"pos": jnp.full((n, 2), g // 2, jnp.int32),
                "hit": jnp.zeros((n, g, g), bool),
                "t": jnp.zeros((n,), jnp.int32)}

    def obs(self, s, dtype):
        grid = jnp.where(self.per & ~s["hit"], 0.5, 0.0)
        n = grid.shape[0]
        grid = grid.at[jnp.arange(n), s["pos"][:, 0], s["pos"][:, 1]].set(1.0)
        return grid.reshape(n, -1).astype(dtype)

    def step(self, s, action):
        g = self.g
        pos = jnp.clip(s["pos"] + jnp.asarray(self.MOVES)[action], 0, g - 1)
        active = self.per & ~s["hit"]
        dist = jnp.max(jnp.abs(self.coords[None] - pos[:, None, None]), -1)
        d = jnp.min(jnp.where(active, dist, 2 * g), axis=(1, 2))
        left = jnp.any(active, axis=(1, 2))
        reward = jnp.where(left, 1.0 - d.astype(jnp.float32) / (g // 2), 0.0)
        at = jnp.all(self.coords[None] == pos[:, None, None], -1)
        hit = s["hit"] | (active & at)
        t = s["t"] + 1
        done = (t >= self.horizon) | jnp.all(hit | ~self.per, axis=(1, 2))
        s2 = {"pos": pos, "hit": hit, "t": t}
        fresh = self.init(pos.shape[0])
        s2 = jax.tree.map(
            lambda a, b: jnp.where(done.reshape((-1,) + (1,) * (a.ndim - 1)),
                                   b, a), s2, fresh)
        return s2, reward, done


def log_softmax_at(logits, a):
    lp = jax.nn.log_softmax(logits)
    return jnp.take_along_axis(lp, a[:, None], axis=-1)[:, 0], lp


def gae(rew, val, done, last, gamma, lam):
    nt = 1.0 - done.astype(rew.dtype)

    def back(carry, x):
        adv_n, v_n = carry
        r, v, n = x
        adv = r + gamma * v_n * n - v + gamma * lam * n * adv_n
        return (adv, v), adv

    _, adv = jax.lax.scan(back, (jnp.zeros_like(last), last),
                          (rew, val, nt), reverse=True)
    return adv


def adamw(cfg, p, g, m, v, step):
    """One AdamW step with global-norm clipping; returns (p, m, v)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                      for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, cfg["max_grad_norm"] / jnp.maximum(gn, 1e-12))
    b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
    c1 = 1.0 - b1 ** step
    c2 = 1.0 - b2 ** step
    lr, eps, wd = cfg["learning_rate"], cfg["adam_eps"], cfg["weight_decay"]
    newp, newm, newv = {}, {}, {}
    for k in p:
        gk = g[k] * scale.astype(g[k].dtype)
        mk = b1 * m[k] + (1 - b1) * gk
        vk = b2 * v[k] + (1 - b2) * jnp.square(gk)
        u = (mk / c1) / (jnp.sqrt(vk / c2) + eps) + wd * p[k]
        newp[k] = (p[k] - lr * u).astype(p[k].dtype)
        newm[k], newv[k] = mk.astype(m[k].dtype), vk.astype(v[k].dtype)
    return newp, newm, newv


def make_update(cfg, num_envs, shards, dtype):
    """One PPO update: rollout, GAE, epochs x minibatches of AdamW."""
    env = Squared(cfg["grid"], cfg["horizon"])
    T, E, M = cfg["unroll_length"], cfg["update_epochs"], cfg["num_minibatches"]
    B, S = num_envs, shards
    nb = B // S                       # envs per data-parallel block
    n_loc = T * nb
    gamma, lam = cfg["gamma"], cfg["gae_lambda"]

    def rollout(p, s, key):
        def one(c, k):
            s, obs = c
            k_act, _ = jax.random.split(k)
            logits, value = forward(p, obs)
            keys = jax.vmap(lambda i: jax.random.fold_in(k_act, i))(
                jnp.arange(B))
            a = jax.vmap(lambda kk, lg: jax.random.categorical(
                jax.random.fold_in(kk, 0), lg))(keys, logits)
            a = a.astype(jnp.int32)
            logp, _ = log_softmax_at(logits, a)
            s2, rew, done = env.step(s, a)
            return (s2, env.obs(s2, dtype)), (obs, a, logp, value,
                                              rew.astype(dtype), done)
        (s, obs), tr = jax.lax.scan(one, (s, env.obs(s, dtype)),
                                    jax.random.split(key, T))
        _, last = forward(p, obs)
        return s, tr, last

    def perms(key):
        out = []
        for e in range(E):
            k = jax.random.fold_in(key, e)
            if S == 1:
                out.append(jax.random.permutation(k, n_loc).reshape(M, -1))
                continue
            blocks = []
            for s in range(S):
                q = jax.random.permutation(jax.random.fold_in(k, s), n_loc)
                glob = (q // nb) * B + s * nb + q % nb
                blocks.append(glob.reshape(M, -1))
            out.append(jnp.concatenate(blocks, axis=1))
        return jnp.concatenate(out)

    def loss_fn(p, batch):
        obs, a, old_lp, old_v, adv, ret = batch
        logits, v = forward(p, obs)
        lp, lps = log_softmax_at(logits, a)
        ent = -jnp.sum(jnp.exp(lps) * lps, axis=-1)
        if cfg["norm_adv"]:
            adv = (adv - jnp.mean(adv)) / (jnp.std(adv) + 1e-8)
        ratio = jnp.exp(lp - old_lp)
        c = cfg["clip_coef"]
        pg = jnp.mean(jnp.maximum(-adv * ratio,
                                  -adv * jnp.clip(ratio, 1 - c, 1 + c)))
        vc = old_v + jnp.clip(v - old_v, -cfg["vf_clip"], cfg["vf_clip"])
        vl = 0.5 * jnp.mean(jnp.maximum(jnp.square(v - ret),
                                        jnp.square(vc - ret)))
        return pg - cfg["ent_coef"] * jnp.mean(ent) + cfg["vf_coef"] * vl

    def update(state, key):
        p, m, v, step, s = state
        k_roll, k_perm = jax.random.split(key)
        s, (obs, a, logp, val, rew, done), last = rollout(p, s, k_roll)
        adv = gae(rew, val, done, last, gamma, lam)
        flat = [x.reshape((T * B,) + x.shape[2:])
                for x in (obs, a, logp, val, adv, adv + val)]

        def mb(c, idx):
            p, m, v, step = c
            loss, g = jax.value_and_grad(loss_fn)(p, [x[idx] for x in flat])
            step = step + 1
            p, m, v = adamw(cfg, p, g, m, v, step.astype(jnp.float32))
            return (p, m, v, step), loss

        (p, m, v, step), losses = jax.lax.scan(mb, (p, m, v, step),
                                               perms(k_perm))
        return (p, m, v, step, s), losses[-1]

    return update


def run(cfg, seed, num_envs, shards=1, updates=3, dtype=jnp.float32,
        precision=None):
    """The first ``updates`` PPO updates from ``seed``, with matmuls at
    ``precision`` (default: the configuration's ``matmul_precision``).
    Returns host copies: the initial params, the losses, the AdamW first
    moment after update 1 and the params after the last update."""
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision(precision or cfg["matmul_precision"]):
        p = init_params(cfg, jax.random.fold_in(key, 0), dtype)
        zeros = {k: jnp.zeros_like(x) for k, x in p.items()}
        env = Squared(cfg["grid"], cfg["horizon"])
        state = (p, zeros, dict(zeros), jnp.zeros((), jnp.int32),
                 env.init(num_envs))
        upd = jax.jit(make_update(cfg, num_envs, shards, dtype))
        p0 = jax.device_get(p)
        losses, m1 = [], None
        for u in range(updates):
            key, sub = jax.random.split(key)
            state, loss = upd(state, jax.random.split(sub, 1)[0])
            losses.append(float(loss))
            if u == 0:
                m1 = jax.device_get(state[1])
        return {"p0": p0, "losses": losses, "m1": m1,
                "p_last": jax.device_get(state[0])}
