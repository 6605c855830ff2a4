"""Repo-wide audit targets for ``analysis.jaxpr_audit``.

Enumerates the three surfaces whose compiled programs must stay clean —

  * every op in the kernel dispatch registry, traced under the canonical
    shapes the test suite sweeps (plus a second batch size for the retrace
    check),
  * all four TrainEngine tiers' device programs: the fused jit and
    shard_map launches (with ``donate_argnums=(0, 1)``, checked against the
    compiled HLO's input/output aliasing), and the pool/host tiers'
    ``learn`` / ``act`` / ``bootstrap`` functions on a real rollout
    trajectory,
  * every registered Ocean env's ``step`` under an emulated random action.

``audit_all()`` is what ``python -m repro.analysis --self`` and the CI
analysis lane run; each target returns an ``AuditResult`` whose violations
gate the build. Enumeration is registry-driven: registering a new kernel op
without adding canonical shapes here fails the audit loudly rather than
silently shrinking coverage.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_audit import AuditResult, AuditViolation, audit_fn


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# kernel ops

def _kernel_cases(mode: str) -> Dict[str, tuple]:
    """op name -> (fn, canonical args, variant args) under ``mode``.
    Shapes mirror tests/test_kernels.py's sweeps, scaled down."""
    from repro.kernels import ops
    k0 = jax.random.PRNGKey(0)
    k = lambda i: jax.random.fold_in(k0, i)

    def attn(B):
        return (_rand(k(1), (B, 32, 2, 16)), _rand(k(2), (B, 32, 2, 16)),
                _rand(k(3), (B, 32, 2, 16)))

    def decode(B):
        return (_rand(k(1), (B, 4, 16)), _rand(k(2), (B, 64, 2, 16)),
                _rand(k(3), (B, 64, 2, 16)), jnp.asarray(17, jnp.int32))

    def ssd(B):
        return (_rand(k(1), (B, 16, 1, 8), scale=0.5),
                jax.nn.softplus(_rand(k(2), (B, 16, 1))),
                -jnp.exp(_rand(k(3), (1,), scale=0.3)),
                _rand(k(4), (B, 16, 1, 8), scale=0.5),
                _rand(k(5), (B, 16, 1, 8), scale=0.5))

    def gae(B):
        return (_rand(k(1), (32, B)), _rand(k(2), (32, B)),
                jax.random.bernoulli(k(3), 0.1, (32, B)),
                _rand(k(4), (B,)), 0.99, 0.95)

    def quant(M):
        wq = jax.random.randint(k(2), (32, 32), -127, 128,
                                jnp.int32).astype(jnp.int8)
        return (_rand(k(1), (M, 32)), wq,
                jnp.abs(_rand(k(3), (32,))) * 0.02)

    def pack(B):
        return ([jax.random.randint(k(i), (B, n), 0, 256,
                                    jnp.int32).astype(jnp.uint8)
                 for i, n in enumerate((3, 7))],)

    return {
        "flash_attention": (partial(ops.flash_attention, causal=True,
                                    mode=mode), attn(1), attn(2)),
        "flash_decode": (partial(ops.flash_decode, mode=mode),
                         decode(2), decode(1)),
        "ssd": (partial(ops.ssd, chunk=4, mode=mode), ssd(1), ssd(2)),
        "gae": (partial(ops.gae, mode=mode), gae(4), gae(2)),
        "quant_matmul": (partial(ops.quant_matmul, mode=mode),
                         quant(16), quant(8)),
        "pack": (partial(ops.pack, mode=mode), pack(4), pack(2)),
    }


def audit_kernel_ops(mode: str = "ref") -> List[AuditResult]:
    """Audit every op in the dispatch registry. A registered op with no
    canonical case here is itself a violation (coverage must not silently
    shrink)."""
    from repro.kernels import dispatch
    cases = _kernel_cases(mode)
    out: List[AuditResult] = []
    for op in sorted(dispatch.ops()):
        name = f"kernel:{op}[{mode}]"
        if op not in cases:
            r = AuditResult(target=name)
            r.violations.append(AuditViolation(
                "coverage", name,
                f"op '{op}' is registered in kernels.dispatch but has no "
                f"canonical audit shapes in analysis.targets — add a case "
                f"so the audit keeps covering every registered op"))
            out.append(r)
            continue
        fn, args, variant = cases[op]
        out.append(audit_fn(fn, args, name=name, variants=[variant]))
    return out


# ---------------------------------------------------------------------------
# engine tiers

def _engine_fixture(backend: str, recurrent: bool = False):
    from repro.configs.base import TrainConfig
    from repro.core.emulation import Emulated
    from repro.envs.ocean import Bandit
    from repro.models.policy import OceanPolicy
    from repro.rl.distributions import Dist
    from repro.rl.engine import TrainEngine

    em = Emulated(Bandit())
    dist = Dist("categorical", nvec=em.act_spec.nvec)
    pol = OceanPolicy(em.obs_spec.total, dist.nvec, hidden=16,
                      recurrent=recurrent, num_outputs=dist.num_outputs)
    tcfg = TrainConfig(num_envs=8, unroll_length=8, update_epochs=1,
                       num_minibatches=2, learning_rate=1e-3)
    eng = TrainEngine(em, pol, tcfg, dist, key=jax.random.PRNGKey(0),
                      backend=backend, kernel_mode="ref")
    return eng, em, pol, dist, tcfg


def _host_trajectory(em, pol, dist, tcfg, params, recurrent: bool):
    """A real rollout trajectory for auditing the pool/host learn fn."""
    from repro.core.vector import VecEnv
    from repro.rl.rollout import RolloutCarry, rollout

    key = jax.random.PRNGKey(1)
    vec = VecEnv(em, tcfg.num_envs)
    env_state, obs = vec.init(jax.random.fold_in(key, 0))
    B = vec.batch_size
    rc = RolloutCarry(env_state, obs, pol.initial_carry(B),
                      jnp.zeros((B,), jnp.bool_))
    _, traj, last_value = rollout(pol, params, vec.step_fn(), rc,
                                  jax.random.fold_in(key, 1),
                                  tcfg.unroll_length, dist)
    return traj, last_value, obs, pol.initial_carry(B)


def audit_engine_tiers() -> List[AuditResult]:
    from jax.sharding import PartitionSpec as P
    from repro.rl.engine import _scan_launch
    from repro.rl.learner import make_ocean_learn

    out: List[AuditResult] = []
    key = jax.random.PRNGKey(2)

    # jit tier: the fused K-update launch, state buffers donated
    eng, em, pol, dist, tcfg = _engine_fixture("jit")
    out.append(audit_fn(_scan_launch(eng._update, 2),
                        (eng.ts, eng.rc, key), name="engine:jit:launch",
                        donate_argnums=(0, 1)))

    # shard_map tier: same launch through the mesh wrapper (1-device CPU
    # mesh in CI; the program structure — collectives, specs — is identical)
    sm, *_ = _engine_fixture("shard_map")
    fn = jax.shard_map(_scan_launch(sm._update, 1), mesh=sm.mesh,
                       in_specs=(P(), sm._rc_spec, P()),
                       out_specs=(P(), sm._rc_spec, P()), check_vma=False)
    out.append(audit_fn(fn, (sm.ts, sm.rc, key),
                        name="engine:shard_map:launch",
                        donate_argnums=(0, 1)))

    # pool tier: learn on a real trajectory + act + bootstrap (the three
    # device programs _run_pool dispatches)
    traj, last_value, obs, carry0 = _host_trajectory(
        em, pol, dist, tcfg, eng.ts.params, recurrent=False)
    learn = make_ocean_learn(pol, tcfg, dist, kernel_mode="ref")
    out.append(audit_fn(learn, (eng.ts, carry0, traj, last_value, key),
                        name="engine:pool:learn"))
    B = tcfg.num_envs
    reset = jnp.zeros((B,), jnp.bool_)
    out.append(audit_fn(eng._make_act(),
                        (eng.ts.params, obs, carry0, reset, key),
                        name="engine:pool:act"))
    out.append(audit_fn(eng._make_bootstrap(),
                        (eng.ts.params, obs, carry0, reset),
                        name="engine:pool:bootstrap"))

    # host tier: same learn/act pair but through the recurrent path the
    # bridged first-finisher loop exercises (carry is a live pytree)
    enr, emr, polr, distr, tcfgr = _engine_fixture("jit", recurrent=True)
    traj, last_value, obs, carry0 = _host_trajectory(
        emr, polr, distr, tcfgr, enr.ts.params, recurrent=True)
    learn = make_ocean_learn(polr, tcfgr, distr, kernel_mode="ref")
    out.append(audit_fn(learn, (enr.ts, carry0, traj, last_value, key),
                        name="engine:host:learn"))
    reset = jnp.zeros((tcfgr.num_envs,), jnp.bool_)
    out.append(audit_fn(enr._make_act(),
                        (enr.ts.params, obs, carry0, reset, key),
                        name="engine:host:act"))
    return out


# ---------------------------------------------------------------------------
# Ocean envs

def audit_ocean_envs(names: Sequence[str] = ()) -> List[AuditResult]:
    from repro.core import spaces as sp
    from repro.envs.ocean import OCEAN, make

    out: List[AuditResult] = []
    for name in (names or sorted(OCEAN)):
        env = make(name)
        key = jax.random.PRNGKey(3)
        s = env.init(jax.random.fold_in(key, 0))
        s, _obs = env.reset(s, jax.random.fold_in(key, 1))
        a = sp.sample(env.action_space, jax.random.fold_in(key, 2))
        if env.num_agents > 1:           # agent-major action rows
            a = jax.tree.map(
                lambda x: jnp.stack([x] * env.num_agents), a)
        out.append(audit_fn(env.step, (s, a, jax.random.fold_in(key, 3)),
                            name=f"env:{name}"))
    return out


def audit_all(include: Sequence[str] = ("kernels", "engine", "envs")
              ) -> List[AuditResult]:
    out: List[AuditResult] = []
    if "kernels" in include:
        out.extend(audit_kernel_ops())
    if "engine" in include:
        out.extend(audit_engine_tiers())
    if "envs" in include:
        out.extend(audit_ocean_envs())
    return out
