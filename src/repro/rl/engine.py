"""Device-resident TrainEngine: K fused PPO updates per host dispatch.

The paper's trajectory is IPC-count reduction — per-step → per-episode → (in
this JAX port) zero host syncs inside one update. This module takes the last
step: zero host round-trips per *K updates*. One launch is a single
``jax.lax.scan`` over the fused update with donated ``TrainState`` /
``RolloutCarry`` buffers, and metrics land in an on-device ring buffer of
shape ``(K, n_metrics)`` that is fetched once per launch — dispatch latency
and host sync amortize K-fold, which is exactly what dominates the
small-unroll Ocean regime the paper benchmarks.

Five execution tiers behind one ``run(total_steps)`` API:

  * ``jit``       — single device; K = 1 is the classic one-update-per-
                    dispatch loop, K > 1 the fused multi-update scan.
  * ``shard_map`` — data-parallel over the mesh's data axes (envs and PPO
                    batch sharded, gradients pmean'd, advantage stats
                    psum'd). Randomness is keyed by *global* env index and
                    minibatch permutations are drawn per shard-block, so an
                    S-device run is seed-matched with the single-device
                    ``num_shards=S`` emulation (same final params up to
                    float reduction order). Testable on CPU via
                    ``--xla_force_host_platform_device_count``.
  * ``pool``      — the double-buffered async host loop (core/pool.py) for
                    host-bound envs: while the learner consumes buffer i,
                    buffer i+1's env step is already on the device queue.
  * ``host``      — bridged third-party host envs (bridge/): a first-
                    finisher ``HostVecEnv`` steps M = 2N envs on workers —
                    threads, or shared-memory spawn processes when built
                    with ``backend="proc"`` (``tcfg.host_backend``; the
                    engine is agnostic, the pool protocol is identical) —
                    while jitted inference + the same ``make_ocean_learn``
                    update stay device-resident.
                    Rollout fragments accumulate *per env* keyed by the
                    pool's ``env_ids``, so GAE bootstraps and recurrent
                    carries stay per-env correct even though every batch is
                    a different first-finisher subset.
  * ``async``     — decoupled actor–learner (distributed/actor_learner.py):
                    N spawn-actor processes run jitted rollouts over
                    disjoint env shards and stream version-tagged fragments
                    through a shared-memory slab; the learner batches one
                    fragment per shard, applies the staleness policy
                    (``tcfg.staleness_mode``: drop stale fragments, or keep
                    them under V-trace rho/c clamps), learns, and
                    seqlock-publishes the new params version. The loop runs
                    through distributed/fault.ResilientLoop (checkpointed
                    kill-and-resume), dead actors are resharded to
                    survivors, and slow actors are straggler-flagged.

Checkpointing, ``target_score`` early-exit, and metric logging fire at
launch boundaries: with ``checkpoint_dir`` set, every
``tcfg.checkpoint_every`` updates the full resumable state (TrainState +
RNG key + rollout carry where device-resident) saves asynchronously at the
``on_launch`` hook point, and ``restore()`` resumes a run so that
interrupted-then-resumed is bitwise-identical to uninterrupted (jit and
shard_map tiers; the pool/host tiers resume the learner but re-seed their
host-side env state).

Self-play (league/): construct with ``selfplay=SelfPlay(next_opponent, L)``
on a multi-agent env and agent rows [0, L) train while rows [L, A) act
under frozen params that ``next_opponent()`` samples from the PolicyStore
once per launch — jit and shard_map tiers only, since the opponent swap is
a host decision at the launch boundary.
"""
from __future__ import annotations

import re
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import ckpt
from repro.configs.base import TrainConfig
from repro.core.vector import VecEnv
from repro.distributed import sharding as shd
from repro.telemetry import TierTimer
from repro.telemetry import enabled as tel_enabled
from repro.telemetry import flush as tel_flush
from repro.telemetry import registry as tel_registry
from repro.telemetry import span as tel_span
from repro.rl.learner import (TrainState, init_train_state, make_ocean_learn,
                              make_ocean_update, make_vtrace_adv)
from repro.rl.rollout import RolloutCarry, Trajectory


METRIC_KEYS = ("loss", "pg_loss", "v_loss", "entropy", "approx_kl",
               "clipfrac", "grad_norm", "score", "episode_return", "episodes")


def pack_metrics(m: dict) -> jax.Array:
    """Metrics dict → one f32 row of the on-device ring buffer."""
    return jnp.stack([jnp.asarray(m[k], jnp.float32) for k in METRIC_KEYS])


def unpack_metrics(row) -> dict:
    return {k: float(v) for k, v in zip(METRIC_KEYS, row)}


_SCOPE = re.compile(r"\bppo_[a-z_]+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSED = re.compile(r"\bcalls=(%[^ ,)]+)")
_CALLEE = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=(%[^ ,]+)|"
                     r"\bbranch_computations=\{([^}]*)\}")
# control flow: its body's ops are trace events of their own
_CONTROL = ("while", "conditional", "call")


def _split_instruction(line: str):
    """``%fusion.4 = f32[8]{0} fusion(%p), ...`` -> (``%fusion.4 =
    f32[8]{0}``, ``fusion``): the instruction's text through its result
    shape, which is how the profiler's op events begin, and its opcode.
    The key stops before the opcode because an async op prints it two ways
    (``slice-start`` from a fresh compile, ``async-start`` in the profiler
    and from the compile cache)."""
    name, rest = line.split(" = ", 1)
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    return f"{name} = {rest[:i]}", rest[i + 1:].split("(", 1)[0]


def hlo_op_scopes(hlo_text: str) -> dict:
    """Each leaf instruction of an optimized HLO module's text mapped to
    the innermost ``ppo_*`` named scope in its ``op_name`` metadata, keyed
    by its text through the result shape (``_split_instruction``). An
    instruction the compiler made without an ``op_name`` (a sunk clone, a
    layout copy) takes the scope of the control-flow instruction whose
    body holds it. Instructions of fusion bodies run inside their fusion
    and control-flow instructions time their bodies' ops, so neither is a
    key; instructions under no ``ppo_*`` scope are left out."""
    fused = set(_FUSED.findall(hlo_text))
    comps, comp = [], None          # [(computation, [instruction line])]
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            head = line.removeprefix("ENTRY ").split(" ", 1)[0]
            comp = None
            if head.startswith("%") and head not in fused:
                comp = (head, [])
                comps.append(comp)
            continue
        inst = line.strip().removeprefix("ROOT ")
        if comp is not None and inst.startswith("%") and " = " in inst:
            comp[1].append(inst)
    # the text lists callees before callers, the entry computation last
    inherited, out = {}, {}
    for name, insts in reversed(comps):
        for inst in insts:
            m = _OP_NAME.search(inst)
            scopes = _SCOPE.findall(m.group(1)) if m else ()
            scope = (scopes[-1] if scopes else "") if m \
                else inherited.get(name, "")
            for one, branches in _CALLEE.findall(inst):
                for callee in (one or branches).split(","):
                    inherited.setdefault(callee.strip(), scope)
            key, opcode = _split_instruction(inst)
            if scope and opcode not in _CONTROL:
                out[key] = scope
    return out


def _scan_launch(update, k: int, selfplay: bool = False):
    """K sequential updates as one traced program; returns the (K, n_metrics)
    metrics ring alongside the threaded state. In selfplay mode the launch
    carries the frozen opponent params as an extra (non-donated) operand —
    all K fused updates face the same opponent; the swap is per launch."""
    if selfplay:
        def launch(ts: TrainState, rc, opp, key):
            def body(carry, uk):
                ts, rc = carry
                ts, rc, m = update(ts, rc, opp, uk)
                return (ts, rc), pack_metrics(m)
            (ts, rc), ring = jax.lax.scan(body, (ts, rc),
                                          jax.random.split(key, k))
            return ts, rc, ring
        return launch

    def launch(ts: TrainState, rc: RolloutCarry, key):
        def body(carry, uk):
            ts, rc = carry
            ts, rc, m = update(ts, rc, uk)
            return (ts, rc), pack_metrics(m)
        (ts, rc), ring = jax.lax.scan(body, (ts, rc),
                                      jax.random.split(key, k))
        return ts, rc, ring
    return launch


class TrainEngine:
    """Owns the device-resident training state and the launch programs.

    ``env`` is a (usually ``Emulated``) pure-functional env; ``policy`` an
    OceanPolicy; ``dist`` a distributions.Dist. ``key`` seeds params
    (fold_in 0), env states (fold_in 1), and the per-launch update keys.

    ``num_shards`` (jit tier only) emulates the S-way block structure of a
    data-parallel run on one device — used by the seed-match parity tests;
    leave at 1 for normal training.
    """

    def __init__(self, env, policy, tcfg: TrainConfig, dist, *, key,
                 backend: str = None, updates_per_launch: int = None,
                 mesh: Optional[Mesh] = None, kernel_mode: str = None,
                 num_shards: int = 1, selfplay=None,
                 checkpoint_dir: Optional[str] = None):
        self.env, self.policy, self.tcfg, self.dist = env, policy, tcfg, dist
        self.backend = backend or tcfg.engine_backend
        self.K = updates_per_launch or tcfg.updates_per_launch
        if self.backend not in ("jit", "shard_map", "pool", "host", "async"):
            raise ValueError(f"unknown engine backend {self.backend!r}; "
                             f"expected jit | shard_map | pool | host | "
                             f"async")
        if self.K < 1:
            raise ValueError(f"updates_per_launch must be >= 1, got {self.K}")
        self.key = key
        self.mesh = mesh
        self._launches = {}
        self.selfplay = selfplay
        self.checkpoint_dir = checkpoint_dir
        self._ckpt_thread = None
        self._resume_update = 0
        self._saved_upto = 0

        self.ts = init_train_state(policy.init(jax.random.fold_in(key, 0)))

        if self.backend != "shard_map" and mesh is not None:
            raise ValueError(f"mesh is only meaningful for the shard_map "
                             f"tier, not backend={self.backend!r}")
        if selfplay is not None:
            if self.backend not in ("jit", "shard_map"):
                raise ValueError(
                    f"selfplay runs on the jit and shard_map tiers (the "
                    f"opponent swap is a launch-boundary decision), not "
                    f"backend={self.backend!r}")
            A = getattr(env, "num_agents", 1)
            if A < 2:
                raise ValueError(
                    f"selfplay needs a multi-agent env to split rows "
                    f"between learner and opponent; num_agents={A}")
            self._sp_agents = selfplay.learner_agents or A // 2
            if not 0 < self._sp_agents < A:
                raise ValueError(
                    f"learner_agents={self._sp_agents} must split "
                    f"num_agents={A} into two non-empty sides")
        if self.backend == "host":
            if self.K != 1:
                raise ValueError(
                    f"updates_per_launch={self.K} is a fused-scan knob; the "
                    f"host tier dispatches one update per collected "
                    f"trajectory (K=1)")
            for attr in ("recv", "send", "batch_envs", "num_agents"):
                if not hasattr(env, attr):
                    raise ValueError(
                        "backend='host' takes a bridge.HostVecEnv (see "
                        "bridge.wrap / bridge.make_host_engine), got "
                        f"{type(env).__name__} without {attr!r}")
            if env.batch_envs != tcfg.num_envs:
                raise ValueError(
                    f"HostVecEnv batches {env.batch_envs} envs but "
                    f"tcfg.num_envs={tcfg.num_envs}; size the bridge batch "
                    f"to the training config")
            self.hvec = self.vec = env
            self.rc = None
            self.num_shards = 1
            self._learn = jax.jit(make_ocean_learn(
                policy, tcfg, dist, kernel_mode=kernel_mode))
            self._act = jax.jit(self._make_act())
            return
        if self.backend == "async":
            if self.K != 1:
                raise ValueError(
                    f"updates_per_launch={self.K} is a fused-scan knob; the "
                    f"async tier dispatches one update per fragment batch "
                    f"(K=1)")
            if tcfg.staleness_mode not in ("drop", "vtrace"):
                raise ValueError(
                    f"staleness_mode={tcfg.staleness_mode!r}; expected "
                    f"'drop' (discard fragments older than max_staleness) "
                    f"or 'vtrace' (importance-clip them)")
            for attr in ("init", "step", "reset"):
                if not hasattr(env, attr):
                    raise ValueError(
                        "backend='async' takes a pure-functional (Emulated) "
                        f"env whose actors rebuild it in-process, got "
                        f"{type(env).__name__} without {attr!r}")
            from types import SimpleNamespace
            from repro.distributed.actor_learner import AsyncRollouts
            A = getattr(env, "num_agents", 1)
            # host-side batch bookkeeping only — the real VecEnvs live in
            # the actor processes, one per shard
            self.vec = SimpleNamespace(batch_size=tcfg.num_envs * A,
                                       num_envs=tcfg.num_envs, num_agents=A)
            self.rc = None
            self.num_shards = 1
            adv = (make_vtrace_adv(policy, dist, tcfg,
                                   rho_clip=tcfg.vtrace_rho,
                                   c_clip=tcfg.vtrace_c)
                   if tcfg.staleness_mode == "vtrace" else None)
            self._learn = jax.jit(make_ocean_learn(
                policy, tcfg, dist, kernel_mode=kernel_mode, adv_fn=adv))
            seed = int(np.asarray(jax.random.randint(
                jax.random.fold_in(key, 1), (), 0, 2**31 - 1)))
            self.rollouts = AsyncRollouts(env, policy, dist, tcfg,
                                          params0=self.ts.params, seed=seed)
            self._dropped = 0
            self._version = 0
            return
        if self.backend == "pool":
            if self.K != 1:
                raise ValueError(
                    f"updates_per_launch={self.K} is a fused-scan knob; the "
                    f"pool tier dispatches one update per trajectory (K=1)")
            from repro.core.pool import Pool
            self.pool = Pool(env, tcfg.num_envs,
                             num_buffers=tcfg.pool_buffers,
                             key=jax.random.fold_in(key, 1))
            self.vec = self.pool.vec
            self.rc = None
            self.num_shards = 1
            self._learn = jax.jit(make_ocean_learn(
                policy, tcfg, dist, kernel_mode=kernel_mode))
            self._act = jax.jit(self._make_act())
            self._boot = jax.jit(self._make_bootstrap())
            return

        self.vec = VecEnv(env, tcfg.num_envs)
        env_state, obs = self.vec.init(jax.random.fold_in(key, 1))
        B = self.vec.batch_size
        if self.selfplay is not None:
            from repro.league.selfplay import SelfPlayCarry
            N, A, L = tcfg.num_envs, self.vec.num_agents, self._sp_agents
            self.rc = SelfPlayCarry(env_state, obs,
                                    policy.initial_carry(N * L),
                                    policy.initial_carry(N * (A - L)),
                                    jnp.zeros((B,), jnp.bool_))
        else:
            self.rc = RolloutCarry(env_state, obs, policy.initial_carry(B),
                                   jnp.zeros((B,), jnp.bool_))

        if self.backend == "shard_map":
            if num_shards != 1:
                raise ValueError("num_shards is derived from the mesh on "
                                 "the shard_map tier; pass a mesh instead")
            if self.mesh is None:
                from repro.launch.mesh import make_mesh
                self.mesh = make_mesh((jax.device_count(),), ("data",))
            axes = shd.data_axes(self.mesh)
            if not axes:
                raise ValueError(
                    f"mesh {self.mesh.axis_names} has no data axes "
                    f"('pod'/'data') to shard Ocean PPO over")
            S = shd.dp_size(self.mesh)
            if self.vec.num_envs % S:
                raise ValueError(
                    f"num_envs={self.vec.num_envs} not divisible by the "
                    f"mesh data-parallel size {S}")
            self._axis = axes if len(axes) > 1 else axes[0]
            self._rc_spec = shd.ocean_batch_spec(self.mesh)
            self.num_shards = S
            self._update = self._make_update(
                self.vec.num_envs // S, kernel_mode,
                axis_name=self._axis, num_shards=S)
            # place state once: params/opt replicated, env batch sharded
            self.ts = jax.device_put(self.ts,
                                     NamedSharding(self.mesh, P()))
            self.rc = jax.device_put(self.rc,
                                     NamedSharding(self.mesh, self._rc_spec))
        else:
            if num_shards < 1 or self.vec.num_envs % num_shards:
                raise ValueError(
                    f"num_envs={self.vec.num_envs} not divisible by "
                    f"num_shards={num_shards}: the S-block emulation would "
                    f"silently drop the tail envs from every minibatch")
            self.num_shards = num_shards
            self._update = self._make_update(self.vec.num_envs, kernel_mode,
                                             num_shards=num_shards)

    def _make_update(self, num_envs_local: int, kernel_mode,
                     axis_name=None, num_shards: int = 1):
        """The per-update program of the fused tiers: the ordinary keyed-step
        Ocean update, or its self-play twin with split agent rows."""
        if self.selfplay is not None:
            from repro.league.selfplay import make_selfplay_update
            return make_selfplay_update(
                self.policy, self.vec.step_keyed_fn(), self.tcfg, self.dist,
                num_envs_local, self.vec.num_agents, self._sp_agents,
                kernel_mode=kernel_mode, axis_name=axis_name,
                num_shards=num_shards)
        return make_ocean_update(
            self.policy, self.vec.step_keyed_fn(), self.tcfg, self.dist,
            num_envs_local, kernel_mode=kernel_mode, axis_name=axis_name,
            num_shards=num_shards, keyed_step=True)

    # -- program cache ---------------------------------------------------------
    def _launch_for(self, k: int):
        """The compiled k-update launch (cached; at most two sizes per run —
        K and the tail). State buffers are donated: the launch consumes its
        inputs and the engine only ever holds the newest generation."""
        if k not in self._launches:
            sp = self.selfplay is not None
            fn = _scan_launch(self._update, k, selfplay=sp)
            if self.backend == "shard_map":
                in_specs = ((P(), self._rc_spec, P(), P()) if sp
                            else (P(), self._rc_spec, P()))
                fn = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                   out_specs=(P(), self._rc_spec, P()),
                                   check_vma=False)
            self._launches[k] = jax.jit(fn, donate_argnums=(0, 1))
        return self._launches[k]

    def op_scopes(self, k: int = 1) -> dict:
        """The named scope (``ppo_rollout``, ``ppo_env_step``, ``ppo_learn``,
        ``ppo_minibatch_rows``) of each leaf instruction of the compiled
        k-update launch (``hlo_op_scopes``). A profiler op event belongs to
        the scope of the key its text begins with. The launch is compiled
        again through the persistent cache, so call it after a timed
        window, never inside one."""
        if self.backend not in ("jit", "shard_map"):
            raise ValueError(f"op_scopes maps the fused launch of the jit "
                             f"and shard_map tiers, not {self.backend!r}")
        opp = () if self.selfplay is None else (self.ts.params,)
        lowered = self._launch_for(k).lower(self.ts, self.rc, *opp, self.key)
        return hlo_op_scopes(lowered.compile().as_text())

    def update_keys(self, key, k: int = None):
        """Per-update keys of one launch keyed by ``key`` — exposed so the
        fused-vs-sequential parity test can replay the exact schedule."""
        return jax.random.split(key, k or self.K)

    # -- state management (checkpoint save/restore) ----------------------------
    def set_train_state(self, ts: TrainState):
        if self.backend == "shard_map":
            ts = jax.device_put(ts, NamedSharding(self.mesh, P()))
        self.ts = ts

    def _ckpt_like(self):
        sds = lambda t: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        like = {"ts": sds(self.ts), "key": sds(self.key),
                "update": jax.ShapeDtypeStruct((), np.int64)}
        if self.rc is not None:
            like["rc"] = sds(self.rc)
        return like

    def save_checkpoint(self, update: int = None, async_: bool = False):
        """Save the full resumable state (TrainState, RNG key, update count,
        and — on the device-resident tiers — the rollout carry) as one
        elastic checkpoint under ``checkpoint_dir``. Async mode snapshots to
        host synchronously and writes on a background thread; overlapping
        saves are serialized (the previous write joins first)."""
        if self.checkpoint_dir is None:
            raise ValueError("engine has no checkpoint_dir")
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None
        update = self._saved_upto if update is None else update
        tree = {"ts": self.ts, "key": self.key,
                "update": np.asarray(update, np.int64)}
        if self.rc is not None:
            tree["rc"] = self.rc
        out = ckpt.save(self.checkpoint_dir, tree, step=update,
                        async_=async_, keep=self.tcfg.keep_checkpoints)
        if async_:
            self._ckpt_thread = out
        return out

    def restore(self, directory: Optional[str] = None) -> int:
        """Restore the newest committed checkpoint and return the update
        count it was taken at; ``run`` then continues from there. On the jit
        and shard_map tiers the rollout carry restores too, so an
        interrupted-then-resumed run is bitwise-identical to an
        uninterrupted one; pool/host resume the learner + key only (their
        env state lives host-side)."""
        directory = directory or self.checkpoint_dir
        if directory is None:
            raise ValueError("engine has no checkpoint_dir to restore from")
        tree = ckpt.restore(directory, self._ckpt_like())
        self.set_train_state(tree["ts"])
        if self.rc is not None:
            rc = tree["rc"]
            if self.backend == "shard_map":
                rc = jax.device_put(rc,
                                    NamedSharding(self.mesh, self._rc_spec))
            self.rc = rc
        self.key = tree["key"]
        self._resume_update = self._saved_upto = int(tree["update"])
        return self._resume_update

    def _maybe_checkpoint(self, updates_done: int):
        """The launch-boundary checkpoint hook (all four tiers)."""
        ce = self.tcfg.checkpoint_every
        if self.checkpoint_dir is None or ce <= 0:
            return
        if updates_done // ce > self._saved_upto // ce:
            self._saved_upto = updates_done
            self.save_checkpoint(updates_done, async_=True)

    def _join_checkpoint(self):
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None

    @property
    def batch_size(self) -> int:
        return self.vec.batch_size

    @property
    def steps_per_update(self) -> int:
        return self.tcfg.unroll_length * self.vec.batch_size

    def stats(self) -> dict:
        """Live snapshot for monitoring endpoints: the backend name plus
        whatever worker-pool stats the tier exposes (host tier: the
        HostPool slab aggregates + liveness; async tier: actor slab
        aggregates, liveness, straggler monitors). Cheap enough to call
        from an HTTP request thread mid-run."""
        out = {"backend": self.backend}
        if self.backend == "host":
            pool = getattr(self.hvec, "pool", None)
            if pool is not None:
                out["pool"] = pool.stats()
        if self.backend == "async":
            out["rollouts"] = self.rollouts.stats()
        return out

    # -- the unified run loop --------------------------------------------------
    def run(self, total_steps: int, *, target_score: Optional[float] = None,
            on_update: Optional[Callable] = None,
            on_launch: Optional[Callable] = None, logger=None):
        """Train until env interactions ≥ total_steps (or solved).

        Returns ``(history, solved)``: per-update metric dicts (with the
        unified ``env_steps``/``sps``/``launch_ms``/``fetch_ms`` telemetry
        keys — same semantics on every tier, stamped by one shared
        ``TierTimer``). ``on_update(u, metrics)`` fires per update once its
        launch's ring is fetched; ``on_launch(updates_dispatched)`` fires
        right after each dispatch (host-side, no device sync) — checkpoint
        hooks go there. With ``target_score`` set, every launch is drained
        eagerly so the check happens at each launch boundary; otherwise the
        engine keeps one launch in flight and fetches the ring one launch
        late, so JAX async dispatch overlaps host work with device compute.

        ``logger`` (a ``utils.metrics.MetricsLogger``) streams every drained
        record as it lands and is flushed on *any* exit — an interrupted run
        keeps every fetched record on disk, nothing truncates. With span
        tracing enabled (``telemetry.enable``) a final telemetry-registry
        snapshot is appended on clean completion; disabled runs leave the
        metrics stream exactly one record per update.
        """
        runner = {"pool": self._run_pool, "host": self._run_host,
                  "async": self._run_async}.get(self.backend,
                                                self._run_fused)
        try:
            with tel_span("engine.run"):
                history, solved = runner(
                    total_steps, target_score=target_score,
                    on_update=on_update, on_launch=on_launch, logger=logger)
            if logger is not None and history and tel_enabled():
                tel_registry().emit(logger,
                                    int(history[-1]["env_steps"]))
            return history, solved
        finally:
            if logger is not None:
                logger.flush()
            tel_flush()

    def _run_fused(self, total_steps, *, target_score=None, on_update=None,
                   on_launch=None, logger=None):
        """The jit / shard_map tiers: K fused updates per dispatch."""
        spu = self.steps_per_update
        num_updates = max(1, total_steps // spu)
        history, pending, solved = [], deque(), None
        # resumed runs: sps counts only this process's work
        timer = TierTimer(spu, self._resume_update * spu)
        upd_ctr = tel_registry().counter("engine.updates",
                                         tier=self.backend)

        def drain_one():
            nonlocal solved
            u0, kk, ring = pending.popleft()
            with timer.fetch():
                rows = np.asarray(jax.device_get(ring))
            with timer.drain():
                for i in range(kk):
                    md = unpack_metrics(rows[i])
                    timer.stamp(md, (u0 + i + 1) * spu)
                    history.append(md)
                    upd_ctr.inc()
                    if logger is not None:
                        logger.log(md["env_steps"], md, flush=False)
                    if on_update is not None:
                        on_update(u0 + i, md)
                    if (target_score is not None and solved is None
                            and md["episodes"] > 0
                            and md["score"] >= target_score):
                        solved = md
                if logger is not None:
                    logger.flush()

        u = self._resume_update
        while u < num_updates:
            k = min(self.K, num_updates - u)
            self.key, sub = jax.random.split(self.key)
            with timer.launch():
                if self.selfplay is not None:
                    opp = self.selfplay.next_opponent()
                    self.ts, self.rc, ring = self._launch_for(k)(
                        self.ts, self.rc, opp, sub)
                else:
                    self.ts, self.rc, ring = self._launch_for(k)(
                        self.ts, self.rc, sub)
            pending.append((u, k, ring))
            u += k
            self._maybe_checkpoint(u)
            if on_launch is not None:
                on_launch(u)
            if target_score is not None:
                while pending:
                    drain_one()
                if solved is not None:
                    break
            elif len(pending) > 1:
                drain_one()
        while pending:
            drain_one()
        self._join_checkpoint()
        return history, solved

    # -- pool tier -------------------------------------------------------------
    def _make_act(self):
        policy, dist = self.policy, self.dist

        def act(params, obs, carry, reset, key):
            logits, value, pc = policy.step(params, obs, carry, reset=reset)
            action = dist.sample(key, logits)
            logp = dist.log_prob(logits, action)
            return action, logp, value, pc
        return act

    def _make_bootstrap(self):
        policy = self.policy

        def boot(params, obs, carry, reset):
            _, value, _ = policy.step(params, obs, carry, reset=reset)
            return value
        return boot

    def _metrics_drainer(self, pending, history, timer, on_update,
                         target_score, st, logger=None):
        """Shared pool/host-tier drain: fetch one update's metrics (blocks
        only on that update's learn, not on later dispatched work), stamp
        the unified telemetry keys, fire ``on_update``, and latch the
        solving update into ``st["solved"]``."""
        upd_ctr = tel_registry().counter("engine.updates",
                                         tier=self.backend)

        def drain_one():
            uu, m = pending.popleft()
            with timer.fetch():
                md = {k: float(v) for k, v in
                      zip(METRIC_KEYS, jax.device_get([m[k] for k in
                                                       METRIC_KEYS]))}
            with timer.drain():
                timer.stamp(md, (uu + 1) * timer.spu)
                history.append(md)
                upd_ctr.inc()
                if logger is not None:
                    logger.log(md["env_steps"], md)
                if on_update is not None:
                    on_update(uu, md)
                if (target_score is not None and st["solved"] is None
                        and md["episodes"] > 0
                        and md["score"] >= target_score):
                    st["solved"] = md
        return drain_one

    def _run_pool(self, total_steps, *, target_score=None, on_update=None,
                  on_launch=None, logger=None):
        """Host loop over the double-buffered pool. The trajectory for each
        buffer accumulates as in-flight device arrays; when a buffer reaches
        T steps its update runs while the other buffers' env steps stay
        queued on the device — the paper's EnvPool overlap, learner edition.
        """
        tcfg, pool = self.tcfg, self.pool
        T, B = tcfg.unroll_length, pool.batch_size
        spu = T * B
        num_updates = max(1, total_steps // spu)
        nb = pool.num_buffers
        carry = [self.policy.initial_carry(B) for _ in range(nb)]
        carry0 = [self.policy.initial_carry(B) for _ in range(nb)]
        recs = [[] for _ in range(nb)]
        history, pending, st = [], deque(), {"solved": None}
        timer = TierTimer(spu, self._resume_update * spu)
        drain_one = self._metrics_drainer(pending, history, timer,
                                          on_update, target_score, st,
                                          logger)

        u = self._resume_update
        while u < num_updates and st["solved"] is None:
            with tel_span("pool.recv"):
                obs, rew, done, info, b = pool.recv()
            if recs[b]:
                recs[b][-1] = recs[b][-1] + (rew, done, info)
            if len(recs[b]) == T and len(recs[b][-1]) == 8:
                last_value = self._boot(self.ts.params, obs, carry[b], done)
                cols = list(zip(*recs[b]))
                stk = lambda xs: jnp.stack(xs)
                traj = Trajectory(
                    obs=stk(cols[0]), actions=stk(cols[1]),
                    logprobs=stk(cols[2]), values=stk(cols[3]),
                    rewards=stk(cols[5]), dones=stk(cols[6]),
                    resets=stk(cols[4]),
                    infos=jax.tree.map(lambda *x: jnp.stack(x), *cols[7]))
                self.key, kp = jax.random.split(self.key)
                with timer.launch():
                    self.ts, m = self._learn(self.ts, carry0[b], traj,
                                             last_value, kp)
                carry0[b] = carry[b]
                recs[b] = []
                pending.append((u, m))
                u += 1
                self._maybe_checkpoint(u)
                if on_launch is not None:
                    on_launch(u)
                # sync each update only when early-exit needs the score;
                # otherwise stay one update behind so the learn and the other
                # buffers' env steps keep the device queue full
                if target_score is not None:
                    while pending:
                        drain_one()
                elif len(pending) > 1:
                    drain_one()
            # act before checking solved so the recv'd buffer is always
            # sent back — the pool stays reusable after an early exit
            self.key, ka = jax.random.split(self.key)
            action, logp, value, pc = self._act(self.ts.params, obs,
                                                carry[b], done, ka)
            recs[b].append((obs, action, logp, value, done))
            carry[b] = pc
            pool.send(action, b)
        while pending:
            drain_one()
        self._join_checkpoint()
        return history, st["solved"]

    # -- async actor–learner tier ----------------------------------------------
    def _collect_fragments(self, nf: int) -> list:
        """``nf`` fresh-enough fragments from the actor pool. In drop mode,
        fragments older than ``max_staleness`` learner versions are
        discarded before batching (the actors keep producing, so this
        converges); in vtrace mode every fragment batches and the
        importance clamps in the learn program do the correcting."""
        tcfg = self.tcfg
        out = []
        while len(out) < nf:
            got = self.rollouts.wait_fragments(
                nf - len(out), timeout=tcfg.async_recv_timeout)
            for f in got:
                if (tcfg.staleness_mode == "drop"
                        and self._version - f.version > tcfg.max_staleness):
                    self._dropped += 1
                    continue
                out.append(f)
        return out

    def _run_async(self, total_steps, *, target_score=None, on_update=None,
                   on_launch=None, logger=None):
        """The learner half of the actor–learner split, run through the
        (recovery-correct) ResilientLoop: collect one update's worth of
        fragments from the slab, learn, publish the new params version.
        Fragments are a live stream — ResilientLoop's iterator contract —
        so recovery retries the current batch and only restores a
        checkpoint that sits exactly at ``steps_done``. Checkpoints are the
        engine's standard {ts, key, update} tree, so ``restore()`` +
        ``run()`` resumes a killed learner step-count-correctly (actors
        re-seed from the published params like the pool/host tiers
        re-seed their env state)."""
        from repro.distributed.actor_learner import stack_fragments
        from repro.distributed.fault import ResilientLoop
        tcfg, ro = self.tcfg, self.rollouts
        spu = self.steps_per_update
        num_updates = max(1, total_steps // spu)
        nf = ro.spec.num_shards           # fragments per update = one pass
                                          # over every env shard's batch rows
        history, st = [], {"solved": None}
        timer = TierTimer(spu, self._resume_update * spu)
        reg = tel_registry()
        upd_ctr = reg.counter("engine.updates", tier="async")
        age_hist = reg.histogram("async.frag_age",
                                 edges=(0.0, 1.0, 2.0, 4.0, 8.0))

        self._version = self._resume_update
        ro.publish(self.ts.params, self._version)

        def step_fn(state, frags):
            with tel_span("engine.stack_fragments"):
                traj, last_value = stack_fragments(frags)
            key, kp = jax.random.split(state["key"])
            with timer.launch():
                ts, m = self._learn(state["ts"], None, traj, last_value, kp)
            u = int(state["update"]) + 1
            # publish inside the step: np.asarray on a poisoned update
            # raises *before* the slab is touched (see AsyncRollouts
            # .publish), so actors only ever see committed params
            ro.publish(ts.params, u)
            return ({"ts": ts, "key": key,
                     "update": np.asarray(u, np.int64)}, m)

        loop = ResilientLoop(
            step_fn, self.checkpoint_dir,
            save_every=(tcfg.checkpoint_every
                        if self.checkpoint_dir is not None else 0),
            async_save=True, keep=tcfg.keep_checkpoints)
        loop.steps_done = self._resume_update
        state = {"ts": self.ts, "key": self.key,
                 "update": np.asarray(self._resume_update, np.int64)}

        def frag_stream():
            while loop.steps_done < num_updates and st["solved"] is None:
                with tel_span("engine.collect"):
                    batch = self._collect_fragments(nf)
                self._last_ages = [self._version - f.version for f in batch]
                for a in self._last_ages:
                    age_hist.observe(a)
                yield batch

        def on_metrics(u, m):
            self._version = ro.version    # published by step_fn
            with timer.fetch():
                md = {k: float(np.asarray(v)) for k, v in m.items()}
            timer.stamp(md, u * spu)
            ages = getattr(self, "_last_ages", [])
            md["frag_age_mean"] = (float(np.mean(ages)) if ages else 0.0)
            md["frag_age_max"] = (float(np.max(ages)) if ages else 0.0)
            md["dropped_fragments"] = self._dropped
            md["stragglers"] = int(np.sum(ro.straggler_flags))
            md["actors_alive"] = len(ro.alive_actors())
            md["reshards"] = len(ro.events)
            history.append(md)
            upd_ctr.inc()
            if logger is not None:
                logger.log(md["env_steps"], md)
            if on_update is not None:
                on_update(u - 1, md)
            if on_launch is not None:
                on_launch(u)
            if (target_score is not None and st["solved"] is None
                    and md["episodes"] > 0 and md["score"] >= target_score):
                st["solved"] = md

        state = loop.run(state, frag_stream(), on_metrics=on_metrics)
        self.ts, self.key = state["ts"], state["key"]
        self._resume_update = self._saved_upto = int(state["update"])
        if self.checkpoint_dir is not None:
            # final commit: kill-then-resume ends at the same step count
            # (and params) as an uninterrupted run
            self.save_checkpoint(self._resume_update, async_=False)
        return history, st["solved"]

    # -- host tier -------------------------------------------------------------
    def close(self):
        """Release host-side resources (the host tier's worker threads or
        processes, or the async tier's actor processes + slab)."""
        if self.backend == "host":
            self.hvec.close()
        if self.backend == "async":
            self.rollouts.close()

    def _run_host(self, total_steps, *, target_score=None, on_update=None,
                  on_launch=None, logger=None):
        """First-finisher loop over the bridged ``HostVecEnv``: each recv is
        the N (of M = pool_buffers·N) envs that finished stepping first;
        while the device computes their actions, the other M−N envs keep
        stepping on worker threads — the paper's EnvPool overlap with the
        learner on device. Rollout fragments accumulate per env (keyed by
        ``env_ids``), so every fragment is a contiguous T-step slice of one
        env's experience with its own recurrent carry and GAE bootstrap; an
        update fires whenever N fragments are ready, batching whichever envs
        filled first."""
        tcfg, hv = self.tcfg, self.hvec
        T = tcfg.unroll_length
        Nb, A = hv.batch_envs, hv.num_agents
        spu = T * Nb * A
        num_updates = max(1, total_steps // spu)
        M = hv.num_envs
        recurrent = self.policy.recurrent
        carry = [self.policy.initial_carry(A) for _ in range(M)]
        carry0 = [self.policy.initial_carry(A) for _ in range(M)]
        recs = [[] for _ in range(M)]
        ready = deque()
        history, pending, st = [], deque(), {"solved": None}
        timer = TierTimer(spu, self._resume_update * spu)
        drain_one = self._metrics_drainer(pending, history, timer,
                                          on_update, target_score, st,
                                          logger)

        u = self._resume_update
        while u < num_updates and st["solved"] is None:
            obs, rew, done, info, ids = hv.recv(
                timeout=tcfg.host_recv_timeout)
            obs_e = obs.reshape(Nb, A, -1)
            rew_e = rew.reshape(Nb, A)
            done_e = done.reshape(Nb, A)
            # complete each env's previous record with its step outcome
            for j, i in enumerate(ids):
                if recs[i]:
                    inf = {k: info[k][j] for k in info}
                    recs[i][-1] = recs[i][-1] + (rew_e[j], done_e[j], inf)
            # act on the batch (device) while the other envs step (host)
            cb = (jax.tree.map(lambda *xs: jnp.concatenate(xs),
                               *[carry[i] for i in ids])
                  if recurrent else None)
            self.key, ka = jax.random.split(self.key)
            action, logp, value, pc = self._act(self.ts.params, obs, cb,
                                                done, ka)
            action = np.asarray(action)
            act_e = action.reshape((Nb, A) + action.shape[1:])
            logp_e = np.asarray(logp).reshape(Nb, A)
            val_e = np.asarray(value).reshape(Nb, A)
            # harvest full fragments (bootstrapped by this batch's values),
            # then start each env's next fragment with this step
            for j, i in enumerate(ids):
                if len(recs[i]) == T and len(recs[i][-1]) == 8:
                    ready.append((recs[i], carry0[i], val_e[j]))
                    recs[i] = []
                    carry0[i] = carry[i]
                recs[i].append((obs_e[j], act_e[j], logp_e[j], val_e[j],
                                done_e[j]))
                if recurrent:
                    carry[i] = jax.tree.map(
                        lambda x, j=j: x[j * A:(j + 1) * A], pc)
            hv.send(action, ids)
            # one PPO update per Nb collected fragments
            while (len(ready) >= Nb and u < num_updates
                   and st["solved"] is None):
                frags = [ready.popleft() for _ in range(Nb)]
                traj, c0, last_value = self._stack_fragments(frags, T, A,
                                                             recurrent)
                self.key, kp = jax.random.split(self.key)
                with timer.launch():
                    self.ts, m = self._learn(self.ts, c0, traj, last_value,
                                             kp)
                pending.append((u, m))
                u += 1
                self._maybe_checkpoint(u)
                if on_launch is not None:
                    on_launch(u)
                if target_score is not None:
                    while pending:
                        drain_one()
                elif len(pending) > 1:
                    drain_one()
        while pending:
            drain_one()
        self._join_checkpoint()
        return history, st["solved"]

    @staticmethod
    def _stack_fragments(frags, T, A, recurrent):
        """N per-env fragments (each T steps of (A, …) rows) → one
        (T, N·A)-batched Trajectory + per-row carry0 + bootstrap values."""
        Nb = len(frags)
        cols = [list(zip(*rec)) for rec, _c0, _bv in frags]

        def field(k, dtype=None):
            x = np.stack([np.stack(c[k]) for c in cols], axis=1)
            x = x.reshape((T, Nb * A) + x.shape[3:])
            return x if dtype is None else x.astype(dtype)

        infos = {key: np.stack([np.stack([r[key] for r in c[7]])
                                for c in cols], axis=1)
                 for key in cols[0][7][0]}               # (T, Nb) per key
        traj = Trajectory(
            obs=field(0, np.float32), actions=field(1),
            logprobs=field(2, np.float32), values=field(3, np.float32),
            rewards=field(5, np.float32), dones=field(6, bool),
            resets=field(4, bool), infos=infos)
        c0 = (jax.tree.map(lambda *xs: jnp.concatenate(xs),
                           *[f[1] for f in frags]) if recurrent else None)
        last_value = np.concatenate([np.asarray(f[2]) for f in frags])
        return traj, c0, last_value
