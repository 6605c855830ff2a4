"""Async actor–learner topology: the fifth engine tier (IMPALA-shaped).

N actor processes (spawn context, one fresh interpreter each) run *jitted*
inference + env stepping and stream fixed-size rollout fragments through a
shared-memory slab; the learner consumes fragments at its own rate, applies
staleness policy (drop, or V-trace importance clamps — rl/learner.py), and
broadcasts refreshed params through a versioned seqlock region of the same
slab. This breaks the rollout/learn coupling of the other four tiers: a slow
actor (latency jitter, preemption) no longer stalls the update cadence —
the paper's EnvPool "never wait for the slowest" insight applied at the
process level instead of the env level.

Slab layout (core/shm.py idiom — numpy views over one segment, one-writer
ctrl bytes, no locks):

  * param region — seqlock (u64 counter, odd while the learner writes) +
    version + the flattened param leaves. Actors re-read only when the
    version changes; a torn read is detected by the counter and retried.
  * fragment rings — per env-shard, ``actor_slots`` slots of
    EMPTY → WRITING → FULL (actor) → EMPTY (learner after copy-out). The
    small ring depth is deliberate backpressure: an actor that gets ahead
    of the learner blocks on a full ring, bounding how stale its next
    fragment can be.
  * assignment table — ``assign[shard] -> actor`` + an epoch counter per
    shard. When the ctrl handshake detects a dead actor (process gone
    without an EXIT status) the learner reassigns its shards to the
    least-loaded survivors and bumps the epochs; the new owner re-seeds
    those shards' env states from (shard, epoch), so training proceeds
    instead of hanging (elastic, like the checkpoint layer).
  * per-actor heartbeat / status / error rows — an actor that *raises*
    (poisoned env) reports through its error row and the learner surfaces
    an ``ActorError``; an actor that *dies* (kill, OOM) is reassigned.

This module is the spawn-worker entrypoint, so its import chain must stay
jax-free (same rule as core/shm.py): actors import jax themselves after the
fork guard, and all learner-side jax use is deferred to method bodies.

One process per chip: actors run their rollouts on the CPU on purpose. An
accelerator belongs to one process at a time and the learner holds it, so
each actor pins ``JAX_PLATFORMS=cpu`` before its own ``import jax``.
"""
from __future__ import annotations

import os
import pickle
import sys
import time
from collections import deque
from dataclasses import dataclass, field
from multiprocessing import get_context, shared_memory
from typing import NamedTuple, Optional, Tuple

import numpy as np

from repro.core import shm
from repro.telemetry import span as _span
from repro.telemetry import traceprop as _traceprop
from repro.telemetry.procstats import (ACTOR_FIELDS, STALENESS_EDGES,
                                       StatSlab)

# fragment-slot states (one writer per state transition, like shm ctrl bytes)
SLOT_EMPTY = 0     # learner-owned: actor may claim
SLOT_WRITING = 1   # actor mid-write (reset by the learner if the actor dies)
SLOT_FULL = 2      # complete fragment; learner copies out then EMPTYs

# actor status bytes
A_BOOT = 0
A_RUN = 1
A_ERR = 2          # actor raised; error row holds the message
A_EXIT = 3         # clean exit after stop

INFO_KEYS = ("score", "episode_return", "episode_length", "valid")

ACTOR_PLATFORM = "cpu"   # actors never touch the learner's accelerator


class ActorError(RuntimeError):
    """An actor process raised inside its rollout loop (poisoned env/policy;
    the same failure would occur on any actor, so it propagates instead of
    triggering reassignment)."""

    def __init__(self, actor: int, op: str, message: str):
        super().__init__(f"actor {actor} failed during {op}: {message}")
        self.actor, self.op, self.message = actor, op, message


@dataclass(frozen=True)
class ReshardEvent:
    """One dead-actor recovery: which shards moved where."""
    actor: int
    shards: Tuple[int, ...]
    new_owners: Tuple[int, ...]


@dataclass(frozen=True)
class FragSpec:
    """Geometry of the shared slab, pickled into every actor (jax-free)."""
    num_actors: int
    num_shards: int          # disjoint env shards, assign[]-mapped to actors
    slots: int               # fragment ring depth per shard (backpressure)
    unroll: int              # T steps per fragment
    envs_per_shard: int      # E
    num_agents: int          # A (rows per env)
    obs_dim: int
    act_dim: int             # action components per agent row
    act_dtype: str           # "int32" | "float32"
    # flattened param leaves: ((shape, dtype, byte offset), ...) + total size
    param_specs: Tuple[Tuple[Tuple[int, ...], str, int], ...]
    param_bytes: int

    @property
    def rows(self) -> int:   # agent rows per fragment
        return self.envs_per_shard * self.num_agents


class AsyncLayout:
    """Byte layout of the actor–learner slab (SlabLayout idiom)."""

    def __init__(self, spec: FragSpec):
        self.spec = spec
        S, Q, T = spec.num_shards, spec.slots, spec.unroll
        R, E, N = spec.rows, spec.envs_per_shard, spec.num_actors
        shapes = {
            "stop": ((1,), np.uint8),
            "pseq": ((1,), np.int64),     # seqlock counter (odd = writing)
            "pver": ((1,), np.int64),     # published params version
            "params": ((spec.param_bytes,), np.uint8),
            "assign": ((S,), np.int32),   # shard -> owning actor
            "epoch": ((S,), np.int64),    # bumped on reassignment
            "hbeat": ((N,), np.int64),
            "astat": ((N,), np.uint8),
            "err": ((N, shm.ERR_BYTES), np.uint8),
            "fctrl": ((S, Q), np.uint8),
            "fver": ((S, Q), np.int64),   # policy version that acted
            "fseq": ((S, Q), np.int64),   # per-shard fragment counter
            "factor": ((S, Q), np.int32),
            "obs": ((S, Q, T, R, spec.obs_dim), np.float32),
            "act": ((S, Q, T, R, spec.act_dim), np.dtype(spec.act_dtype)),
            "logp": ((S, Q, T, R), np.float32),
            "val": ((S, Q, T, R), np.float32),
            "rew": ((S, Q, T, R), np.float32),
            "done": ((S, Q, T, R), np.uint8),
            "reset": ((S, Q, T, R), np.uint8),
            "i_score": ((S, Q, T, E), np.float32),
            "i_ret": ((S, Q, T, E), np.float32),
            "i_len": ((S, Q, T, E), np.int32),
            "i_valid": ((S, Q, T, E), np.uint8),
            "boot": ((S, Q, R), np.float32),   # bootstrap value rows
        }
        self.sections = {}
        end = 0
        for name, (shape, dtype) in shapes.items():
            start, end = shm._section(end, shape, dtype)
            self.sections[name] = (start, shape, dtype)
        self.nbytes = end

    def views(self, buf) -> dict:
        out = {}
        for name, (start, shape, dtype) in self.sections.items():
            n = int(np.prod(shape, dtype=np.int64))
            out[name] = np.frombuffer(
                buf, dtype=dtype, count=n, offset=start).reshape(shape)
        return out

    def param_views(self, buf) -> list:
        """One numpy view per flattened param leaf, in pytree-flatten order
        (both sides flatten the same dict structure → same sorted-key
        order)."""
        base = self.sections["params"][0]
        return [np.frombuffer(buf, dtype=np.dtype(dt),
                              count=int(np.prod(shape, dtype=np.int64)),
                              offset=base + off).reshape(shape)
                for shape, dt, off in self.spec.param_specs]


def make_param_specs(leaves) -> Tuple[Tuple, int]:
    """((shape, dtype, offset), ...) for host copies of param leaves, each
    offset aligned so ``np.frombuffer`` is legal for its dtype."""
    specs, off = [], 0
    for leaf in leaves:
        a = np.asarray(leaf)
        off = ((off + 7) // 8) * 8
        specs.append((tuple(a.shape), str(a.dtype), off))
        off += a.nbytes
    return tuple(specs), off


def read_params_seqlock(v: dict, pviews: list, spin: shm.SpinConfig,
                        srow=None):
    """Torn-read-safe copy of the published leaves: retry while the seqlock
    counter is odd (write in progress) or changed across the copy.
    ``srow`` (a telemetry ``StatRow``) counts the retries when given."""
    w = shm.SpinWait(spin)
    while True:
        s1 = int(v["pseq"][0])
        if s1 % 2 == 0:
            leaves = [pv.copy() for pv in pviews]
            ver = int(v["pver"][0])
            if int(v["pseq"][0]) == s1:
                return leaves, ver
        if srow is not None:
            srow.add("seqlock_retries")
        w.pause()


@dataclass(frozen=True)
class ActorConfig:
    """Everything one spawn actor needs (small and picklable)."""
    shm_name: str
    actor_id: int
    spec: FragSpec
    seed: int                # shared base seed; streams are keyed by shard
    spin: shm.SpinConfig = field(default_factory=shm.SpinConfig)
    payload_env: bytes = b""
    payload_policy: bytes = b""
    payload_dist: bytes = b""
    jitter_ms: float = 0.0   # injected per-step latency (bench/fault tests)
    stats: object = None     # telemetry.procstats.StatSpec | None
    trace: object = None     # telemetry.traceprop.TraceConfig | None


class Fragment(NamedTuple):
    """One copied-out rollout fragment (numpy, learner-side)."""
    shard: int
    actor: int
    version: int             # params version that produced it
    seq: int
    obs: np.ndarray          # (T, R, obs_dim)
    actions: np.ndarray      # (T, R, act_dim)
    logprobs: np.ndarray     # (T, R)
    values: np.ndarray
    rewards: np.ndarray
    dones: np.ndarray        # (T, R) bool
    resets: np.ndarray
    infos: dict              # {key: (T, E)}
    boot: np.ndarray         # (R,) bootstrap values


# =============================== actor side ==================================

def actor_main(cfg: ActorConfig) -> None:
    """Spawn-actor entrypoint: claim an EMPTY slot per owned shard, run one
    jitted T-step rollout, write the fragment, repeat. Params refresh via
    the seqlock whenever the published version changes; ownership is
    re-read every pass so reassignment takes effect without coordination."""
    if "jax" in sys.modules:
        # same enforcement as shm.worker_main: a forked child inherits the
        # parent's jax/XLA state and deadlocks — actors must be spawned
        raise RuntimeError(
            "actor started with jax already imported — it was forked, not "
            "spawned. AsyncRollouts must use the 'spawn' start method")
    # the learner holds the accelerator; actors run on the CPU
    os.environ["JAX_PLATFORMS"] = ACTOR_PLATFORM
    import jax
    import jax.numpy as jnp
    from repro.core.vector import VecEnv
    from repro.rl.rollout import RolloutCarry, rollout

    spec = cfg.spec
    me = cfg.actor_id
    seg = shm.attach_untracked(cfg.shm_name)
    lay = AsyncLayout(spec)
    v = lay.views(seg.buf)
    pviews = lay.param_views(seg.buf)
    slab = srow = None
    if cfg.stats is not None:
        # lock-free per-actor stat row: steps / fragments / ring stalls /
        # seqlock retries / staleness histogram, aggregated by the learner
        slab = StatSlab.attach(cfg.stats)
        srow = slab.row(me)
    # per-process tracing: spans flush to this actor's own spans-<pid>.jsonl
    # (meta header written eagerly, so a killed actor still leaves a
    # mergeable file); CachedSpans are no-ops when the parent shipped no
    # trace config
    from repro.telemetry.spans import CachedSpan
    tracer = None
    if cfg.trace is not None:
        from repro.telemetry import traceprop
        tracer = traceprop.init_worker(cfg.trace, role=f"actor-{me}")
    rollout_span = CachedSpan("actor.rollout")
    refresh_span = CachedSpan("actor.param_refresh")
    t_flush = time.monotonic()
    try:
        env = pickle.loads(cfg.payload_env)
        policy = pickle.loads(cfg.payload_policy)
        dist = pickle.loads(cfg.payload_dist)
        vec = VecEnv(env, spec.envs_per_shard)
        step_fn = vec.step_fn()
        T, R = spec.unroll, spec.rows

        def frag(params, carry, key):
            return rollout(policy, params, step_fn, carry, key, T, dist)
        jfrag = jax.jit(frag)

        base = jax.random.PRNGKey(cfg.seed)
        tmpl = jax.tree.structure(policy.abstract())
        leaves, pver = read_params_seqlock(v, pviews, cfg.spin, srow)
        params = jax.tree.unflatten(tmpl, [jnp.asarray(l) for l in leaves])
        rng = np.random.default_rng(cfg.seed * 7919 + me + 1)
        shard_state = {}      # shard -> [carry, epoch, seq]
        spin = shm.SpinWait(cfg.spin)
        v["astat"][me] = A_RUN
        while not v["stop"][0]:
            v["hbeat"][me] += 1
            if srow is not None:
                # wall-clock liveness beat: /healthz reads its age to tell a
                # slow actor from a dead one (idle passes still beat)
                srow.set("last_beat_ns", time.time_ns())
            produced = False
            t_pass = time.monotonic_ns()
            for s in range(spec.num_shards):
                if v["stop"][0] or int(v["assign"][s]) != me:
                    continue
                if int(v["pver"][0]) != pver:
                    with refresh_span:
                        leaves, pver = read_params_seqlock(v, pviews,
                                                           cfg.spin, srow)
                        params = jax.tree.unflatten(
                            tmpl, [jnp.asarray(l) for l in leaves])
                    if srow is not None:
                        srow.add("param_loads")
                ep = int(v["epoch"][s])
                st = shard_state.get(s)
                if st is None or st[1] != ep:
                    # (shard, epoch)-keyed env state: a reassigned shard
                    # restarts from a deterministic seed on its new owner
                    k0 = jax.random.fold_in(
                        jax.random.fold_in(base, 1_000 + s), ep)
                    env_state, obs = vec.init(k0)
                    carry = RolloutCarry(env_state, obs,
                                         policy.initial_carry(R),
                                         jnp.zeros((R,), jnp.bool_))
                    st = shard_state[s] = [carry, ep, 0]
                slot = None
                for q in range(spec.slots):
                    if int(v["fctrl"][s, q]) == SLOT_EMPTY:
                        slot = q
                        break
                if slot is None:          # ring full: learner is behind —
                    if srow is not None:  # backpressure bounds staleness
                        srow.add("ring_full")
                    continue
                with rollout_span:   # claim → jitted rollout → commit
                    v["fctrl"][s, slot] = SLOT_WRITING
                    kroll = jax.random.fold_in(jax.random.fold_in(
                        jax.random.fold_in(jax.random.fold_in(base, 2), s),
                        ep), st[2])
                    carry, traj, last_value = jfrag(params, st[0], kroll)
                    if cfg.jitter_ms > 0.0:
                        # emulate jitter_ms/step of host latency, ±50%
                        time.sleep(T * cfg.jitter_ms / 1e3
                                   * rng.uniform(0.5, 1.5))
                    v["obs"][s, slot] = np.asarray(traj.obs, np.float32)
                    v["act"][s, slot] = np.asarray(traj.actions,
                                                   v["act"].dtype)
                    v["logp"][s, slot] = np.asarray(traj.logprobs,
                                                    np.float32)
                    v["val"][s, slot] = np.asarray(traj.values, np.float32)
                    v["rew"][s, slot] = np.asarray(traj.rewards, np.float32)
                    v["done"][s, slot] = np.asarray(traj.dones, np.uint8)
                    v["reset"][s, slot] = np.asarray(traj.resets, np.uint8)
                    v["i_score"][s, slot] = np.asarray(traj.infos["score"],
                                                       np.float32)
                    v["i_ret"][s, slot] = np.asarray(
                        traj.infos["episode_return"], np.float32)
                    v["i_len"][s, slot] = np.asarray(
                        traj.infos["episode_length"], np.int32)
                    v["i_valid"][s, slot] = np.asarray(traj.infos["valid"],
                                                       np.uint8)
                    v["boot"][s, slot] = np.asarray(last_value, np.float32)
                    v["fver"][s, slot] = pver
                    v["fseq"][s, slot] = st[2]
                    v["factor"][s, slot] = me
                    st[0], st[2] = carry, st[2] + 1
                    v["fctrl"][s, slot] = SLOT_FULL  # commit (written last)
                produced = True
                if srow is not None:
                    srow.add("fragments")
                    srow.add("steps", T * R)
                    # learner-updates-behind at commit time
                    srow.observe(int(v["pver"][0]) - pver)
            if srow is not None:
                srow.add("busy_ns" if produced else "wait_ns",
                         time.monotonic_ns() - t_pass)
            if produced:
                spin.reset()
            else:
                spin.pause()
            if tracer is not None and time.monotonic() - t_flush > 0.25:
                tracer.flush()
                t_flush = time.monotonic()
        v["astat"][me] = A_EXIT
    except Exception as e:    # noqa: BLE001 — forwarded to the learner
        shm._write_error(v, me, "step", e)
        v["astat"][me] = A_ERR
        if srow is not None:
            srow.add("errors")
    finally:
        if tracer is not None:
            # crash-safe: the error path above and clean exits both flush
            # whatever the periodic flush hasn't written yet
            try:
                tracer.flush()
            except Exception:
                pass
        del v, pviews, srow
        seg.close()
        if slab is not None:
            slab.close()


# =============================== learner side ================================

class AsyncRollouts:
    """Learner-side handle: owns the slab, the actor processes, the param
    broadcast, and dead-actor/straggler monitoring. All jax use is lazy —
    see the module docstring."""

    def __init__(self, env, policy, dist, tcfg, *, params0, seed: int,
                 jitter_ms: float = None, spin: shm.SpinConfig = None):
        import jax
        from repro.distributed.fault import StragglerMonitor

        N = tcfg.num_actors
        S = N * tcfg.shards_per_actor
        if N < 1:
            raise ValueError(f"num_actors must be >= 1, got {N}")
        if tcfg.num_envs % S:
            raise ValueError(
                f"num_envs={tcfg.num_envs} not divisible by num_shards={S} "
                f"(num_actors={N} × shards_per_actor="
                f"{tcfg.shards_per_actor})")
        if policy.recurrent:
            raise ValueError(
                "the async tier does not ship recurrent carries through the "
                "fragment slab yet; use the jit/host tiers for LSTM policies")
        A = getattr(env, "num_agents", 1)
        leaves = jax.tree.leaves(params0)
        pspecs, pbytes = make_param_specs(leaves)
        self.spec = FragSpec(
            num_actors=N, num_shards=S, slots=max(1, tcfg.actor_slots),
            unroll=tcfg.unroll_length, envs_per_shard=tcfg.num_envs // S,
            num_agents=A, obs_dim=policy.obs_dim,
            act_dim=dist.action_dim,
            act_dtype=np.dtype(dist.action_dtype).name,
            param_specs=pspecs, param_bytes=pbytes)
        self.layout = AsyncLayout(self.spec)
        self.spin = spin or shm.default_spin(workers=N + 1)
        jitter = tcfg.actor_jitter_ms if jitter_ms is None else jitter_ms

        self._seg = shared_memory.SharedMemory(
            create=True, size=self.layout.nbytes)
        self._v = self.layout.views(self._seg.buf)
        self._pviews = self.layout.param_views(self._seg.buf)
        self._v["assign"][:] = np.arange(S, dtype=np.int32) % N
        self._v["pver"][0] = -1
        self.publish(params0, 0)

        self._fifo = deque()
        self._dead = set()
        self.events = []
        self._monitors = [StragglerMonitor(window=16, min_samples=4)
                          for _ in range(N)]
        self._last_arrival = [None] * N
        self.straggler_flags = [0] * N
        self._last_liveness = 0.0

        env_p = shm.dumps_env_fn(env)
        pol_p = shm.dumps_env_fn(policy)
        dist_p = shm.dumps_env_fn(dist)
        # per-actor telemetry rows (separate tiny segment, learner-owned):
        # written lock-free by actors, aggregated in stats() — and readable
        # for dead actors, whose rows freeze at their last write
        self._stats_slab = StatSlab.create(N, ACTOR_FIELDS, STALENESS_EDGES)
        # cross-process trace propagation: ship the learner's TraceConfig
        # (None when tracing is off) so each actor flushes its own
        # spans-<pid>.jsonl into the same run dir
        trace_cfg = _traceprop.current()
        ctx = get_context("spawn")
        self._procs = []
        try:
            with _span("async.spawn"):
                for a in range(N):
                    p = ctx.Process(
                        target=actor_main,
                        args=(ActorConfig(
                            shm_name=self._seg.name, actor_id=a,
                            spec=self.spec, seed=seed, spin=self.spin,
                            payload_env=env_p, payload_policy=pol_p,
                            payload_dist=dist_p, jitter_ms=jitter,
                            stats=self._stats_slab.spec, trace=trace_cfg),),
                        daemon=True, name=f"repro-actor-{a}")
                    p.start()
                    self._procs.append(p)
        except Exception:
            self.close()
            raise

    # -- param broadcast -------------------------------------------------------
    def publish(self, params, version: int) -> None:
        """Seqlock-publish new params. Leaves are materialized to host
        *before* the lock window opens, so a poisoned array (failed update)
        raises here without ever touching the slab — actors keep acting on
        the previous version."""
        import jax
        host = [np.asarray(l) for l in jax.tree.leaves(params)]
        with _span("async.publish"):
            v = self._v
            v["pseq"][0] += 1          # odd: readers retry
            for dst, src in zip(self._pviews, host):
                np.copyto(dst, src.astype(dst.dtype, copy=False))
            v["pver"][0] = version
            v["pseq"][0] += 1          # even: committed
            self.version = version

    # -- fragment harvest ------------------------------------------------------
    def poll(self) -> int:
        """Copy out every FULL slot (ordered by per-shard sequence number)
        into the FIFO; returns how many arrived. Also surfaces actor
        errors."""
        v = self._v
        self._check_errors()
        found = []
        S, Q = self.spec.num_shards, self.spec.slots
        for s in range(S):
            for q in range(Q):
                if int(v["fctrl"][s, q]) == SLOT_FULL:
                    found.append((int(v["fseq"][s, q]), s, q))
        found.sort()
        now = time.monotonic()
        for seq, s, q in found:
            actor = int(v["factor"][s, q])
            frag = Fragment(
                shard=s, actor=actor, version=int(v["fver"][s, q]), seq=seq,
                obs=v["obs"][s, q].copy(),
                actions=v["act"][s, q].copy(),
                logprobs=v["logp"][s, q].copy(),
                values=v["val"][s, q].copy(),
                rewards=v["rew"][s, q].copy(),
                dones=v["done"][s, q].astype(bool),
                resets=v["reset"][s, q].astype(bool),
                infos={"score": v["i_score"][s, q].copy(),
                       "episode_return": v["i_ret"][s, q].copy(),
                       "episode_length": v["i_len"][s, q].copy(),
                       "valid": v["i_valid"][s, q].astype(bool)},
                boot=v["boot"][s, q].copy())
            v["fctrl"][s, q] = SLOT_EMPTY         # hand the slot back
            self._fifo.append(frag)
            if 0 <= actor < self.spec.num_actors:
                last = self._last_arrival[actor]
                if last is not None:
                    if self._monitors[actor].record(now - last):
                        self.straggler_flags[actor] += 1
                self._last_arrival[actor] = now
        return len(found)

    def wait_fragments(self, n: int, *, timeout: float) -> list:
        """Block (spin ladder) until ``n`` fragments are buffered; FIFO
        order. Dead actors are detected and resharded *while waiting*, so a
        kill never hangs the learner — only a genuinely fragment-less
        ``timeout`` raises."""
        deadline = time.monotonic() + timeout
        w = shm.SpinWait(self.spin)
        # liveness is checked unconditionally once per call: a fast surviving
        # actor that keeps the FIFO full must not mask a dead peer (its
        # shards would silently stop contributing). The throttle below only
        # bounds waitpid traffic inside the hot spin loop.
        self._check_actors()
        self._last_liveness = time.monotonic()
        with _span("async.wait_fragments"):
            while True:
                if self.poll():
                    w.reset()
                now = time.monotonic()
                if now - self._last_liveness > 0.05:
                    self._last_liveness = now
                    self._check_actors()
                if len(self._fifo) >= n:
                    return [self._fifo.popleft() for _ in range(n)]
                if now > deadline:
                    raise TimeoutError(
                        f"async tier: {n} fragment(s) not produced within "
                        f"{timeout}s (have {len(self._fifo)}; alive="
                        f"{self.alive_actors()}, assign="
                        f"{self._v['assign'].tolist()})")
                w.pause()

    # -- fault handling --------------------------------------------------------
    def _check_errors(self) -> None:
        for a in range(self.spec.num_actors):
            if int(self._v["astat"][a]) == A_ERR and a not in self._dead:
                self._dead.add(a)
                op, msg = shm.read_error(self._v, a)
                raise ActorError(a, op, msg)

    def _check_actors(self) -> None:
        """Ctrl-handshake liveness: a process that is gone without a clean
        EXIT status is dead — harvest nothing from it, reset its half-written
        slots, and reassign its shards to the least-loaded survivors."""
        stopping = bool(self._v["stop"][0])
        for a, p in enumerate(self._procs):
            if a in self._dead or p.is_alive():
                continue
            if stopping and int(self._v["astat"][a]) == A_EXIT:
                continue
            self._dead.add(a)
            self._reshard(a)

    def _reshard(self, dead: int) -> None:
        survivors = [b for b in range(self.spec.num_actors)
                     if b not in self._dead]
        if not survivors:
            # raised before binding any slab view locally: a view captured
            # in this traceback would pin the buffer and break close()
            raise RuntimeError(
                f"all {self.spec.num_actors} actors are dead (last: actor "
                f"{dead}); nothing left to reassign shards to")
        v = self._v
        loads = {b: int(np.sum(np.asarray(v["assign"]) == b))
                 for b in survivors}
        moved, owners = [], []
        for s in range(self.spec.num_shards):
            if int(v["assign"][s]) != dead:
                continue
            b = min(survivors, key=lambda x: (loads[x], x))
            loads[b] += 1
            for q in range(self.spec.slots):
                # the dead writer's half-written slot is garbage; FULL slots
                # were committed before death and stay consumable
                if int(v["fctrl"][s, q]) == SLOT_WRITING:
                    v["fctrl"][s, q] = SLOT_EMPTY
            v["epoch"][s] += 1         # new owner re-seeds (shard, epoch)
            v["assign"][s] = b         # ownership handoff (written last)
            moved.append(s)
            owners.append(b)
        self.events.append(ReshardEvent(actor=dead, shards=tuple(moved),
                                        new_owners=tuple(owners)))

    # -- introspection ---------------------------------------------------------
    def alive_actors(self) -> list:
        return [a for a, p in enumerate(self._procs)
                if a not in self._dead and p.is_alive()]

    def liveness(self) -> dict:
        """Per-actor liveness for /healthz: wall-clock ``last_beat_ns``
        from the stat slab (actors beat every pass, even idle ones) plus
        dead detection that does NOT wait for the learner's next
        ``wait_fragments`` — a killed process shows up here immediately."""
        beats = []
        slab = getattr(self, "_stats_slab", None)
        if slab is not None and slab.counters is not None:
            col = slab.spec.fields.index("last_beat_ns")
            beats = [int(b) for b in slab.counters[:, col]]
        v = getattr(self, "_v", None)
        dead = set(self._dead)
        stopping = v is None or bool(v["stop"][0])
        for a, p in enumerate(self._procs):
            if p.is_alive():
                continue
            if stopping and (v is None or int(v["astat"][a]) == A_EXIT):
                continue                # clean shutdown, not a death
            dead.add(a)
        return {"now_ns": time.time_ns(),
                "workers": self.spec.num_actors,
                "last_beat_ns": beats, "dead": sorted(dead)}

    def stats(self) -> dict:
        out = {
            # actors run on the CPU on purpose: only the learner holds the
            # accelerator (one process per chip)
            "actor_platform": ACTOR_PLATFORM,
            "assign": self._v["assign"].tolist(),
            "epoch": self._v["epoch"].tolist(),
            "heartbeats": self._v["hbeat"].tolist(),
            "dead": sorted(self._dead),
            "straggler_flags": list(self.straggler_flags),
            "reshards": len(self.events),
            "liveness": self.liveness(),
            # staleness age per actor: seconds since its last fragment
            # arrived (None before the first one) + the monitor medians
            "stragglers": [m.stats() for m in self._monitors],
        }
        if self._stats_slab is not None:
            # per-actor shared-memory rows: steps/fragments/ring stalls/
            # seqlock retries + the staleness histogram, zero pickling.
            # Dead actors' rows stay readable (learner-owned segment).
            out["actors"] = self._stats_slab.aggregate()
        return out

    # -- lifecycle -------------------------------------------------------------
    def close(self) -> None:
        if getattr(self, "_seg", None) is None:
            return
        self._v["stop"][0] = 1
        shm.spin_until(
            lambda: all(not p.is_alive() for p in self._procs),
            self.spin, timeout=5.0)
        for p in self._procs:
            if p.is_alive():
                p.terminate()
            p.join(timeout=5.0)
        if getattr(self, "_stats_slab", None) is not None:
            self._stats_slab.close()
            self._stats_slab = None
        del self._v, self._pviews
        try:
            self._seg.close()
        except BufferError:
            # a propagating exception's traceback frames can still pin slab
            # views (e.g. poll() locals on the ActorError path); the segment
            # is unlinked below regardless and the mapping goes with the
            # process
            pass
        try:
            self._seg.unlink()
        except FileNotFoundError:
            pass
        self._seg = None


def stack_fragments(frags: list):
    """n fragments → one (T, n·R)-batched Trajectory + bootstrap row — the
    async twin of TrainEngine._stack_fragments (fragments arrive already
    time-major, so this is pure concatenation along the batch axis)."""
    from repro.rl.rollout import Trajectory
    cat = lambda key: np.concatenate([getattr(f, key) for f in frags],
                                     axis=1)
    infos = {k: np.concatenate([f.infos[k] for f in frags], axis=1)
             for k in INFO_KEYS}
    traj = Trajectory(
        obs=cat("obs"), actions=cat("actions"), logprobs=cat("logprobs"),
        values=cat("values"), rewards=cat("rewards"), dones=cat("dones"),
        resets=cat("resets"), infos=infos)
    last_value = np.concatenate([f.boot for f in frags])
    return traj, last_value
