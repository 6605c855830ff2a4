"""Training launcher: Ocean suite PPO or LM-backbone PPO, with fault
tolerance, checkpoint/restart, elastic re-mesh, and straggler monitoring.

  # the paper's coffee-break sanity suite
  PYTHONPATH=src python -m repro.launch.train --ocean all

  # LM-backbone PPO on a (possibly fake-device) mesh
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
      --batch 8 --seq 256 --steps 20 --mesh 1x1
"""
import argparse
import os


def ocean_trainer(name: str, *, seed: int = 0, log_dir: str = None,
                  num_shards: int = 1, **tcfg_overrides):
    """The ``--ocean`` trainer for one env: its preset's policy widths and
    ``ocean_tcfg`` defaults plus ``tcfg_overrides``. ``num_shards`` is the
    jit tier's S-block emulation of a data-parallel run (see
    ``TrainEngine``). Returns ``(Trainer, preset)``."""
    from repro.configs.ocean import ocean_tcfg, preset
    from repro.envs.ocean import OCEAN
    from repro.rl.trainer import Trainer
    p = preset(name)
    tcfg = ocean_tcfg(name, **tcfg_overrides)
    tr = Trainer(OCEAN[name](), tcfg, hidden=p.hidden, recurrent=p.recurrent,
                 conv=p.conv, seed=seed, log_dir=log_dir,
                 num_shards=num_shards)
    return tr, p


def train_lm(arch: str, *, smoke: bool = False, mesh: str = "1x1",
             batch: int = 8, seq: int = 256, steps: int = 100, seed: int = 0,
             ckpt_dir: str = None, save_every: int = 50,
             resume: bool = False):
    """LM-backbone PPO (the ``--arch`` path): ``steps`` train steps of
    ``arch`` on random token rollouts over a ``DxM`` mesh, through the
    fault-tolerant loop. ``ckpt_dir=None`` turns checkpointing off.
    Returns ``(loop, history)``, one dict of float metrics per step."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, get_smoke_config
    from repro.configs.base import TrainConfig
    from repro.data.buffer import random_batch
    from repro.distributed import sharding as shd
    from repro.distributed.fault import ResilientLoop
    from repro.launch.mesh import make_mesh
    from repro.models.params import set_fsdp_axes
    from repro.models.policy import BackbonePolicy
    from repro.rl.learner import init_train_state, make_lm_train_step

    shape = tuple(int(x) for x in mesh.split("x"))
    axes = ("data", "model")[:len(shape)] if len(shape) == 2 \
        else ("pod", "data", "model")
    dev_mesh = make_mesh(shape, axes)
    set_fsdp_axes(tuple(a for a in ("pod", "data") if a in axes))
    rules = shd.make_rules(dev_mesh)
    tp = dict(zip(axes, shape)).get("model", 1)

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    policy = BackbonePolicy(cfg, tp=tp, kernel="auto")
    tcfg = TrainConfig(checkpoint_dir=ckpt_dir)
    key = jax.random.PRNGKey(seed)
    history = []

    with dev_mesh:
        state_sh = shd.named(dev_mesh, shd.train_state_pspecs(policy, rules))
        step = jax.jit(make_lm_train_step(policy, tcfg,
                                          loss_chunk=min(256, seq)),
                       out_shardings=(state_sh, None))
        loop = ResilientLoop(step, ckpt_dir, save_every=save_every,
                             shardings=state_sh)

        def initial_state():
            # no name here outlives the call: a step's input and output
            # states are the only two alive at once (a third copy of
            # qwen3-0.6b's params and Adam moments overflows a 16 GB chip)
            state = init_train_state(policy.init(key),
                                     jnp.dtype(tcfg.optimizer_state_dtype))
            if resume:
                state, start = loop.resume_or_init(state)
                loop.steps_done = start
                print(f"resumed at step {start}")
            return state

        def batches():
            for i in range(steps - loop.steps_done):
                yield random_batch(cfg, batch, seq,
                                   jax.random.fold_in(key, 1000 + i))

        def on_metrics(i, m):
            history.append({k: float(v) for k, v in m.items()})
            if i % 5 == 0 or i == 1:
                print(f"step {i:5d} loss {float(m['loss']):+.4f} "
                      f"kl {float(m['approx_kl']):.4f} "
                      f"gnorm {float(m['grad_norm']):.2f} "
                      f"median_step {loop.monitor.median*1e3:.0f}ms")

        loop.run(initial_state(), batches(), on_metrics)
    return loop, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ocean", default=None,
                    help="ocean env name(s, comma-separated) or 'all'")
    ap.add_argument("--conformance", action="store_true",
                    help="run the env-conformance harness on the --ocean "
                         "env(s) instead of training; exit 1 on violations")
    ap.add_argument("--engine-backend", default=None,
                    choices=("jit", "shard_map", "pool", "host", "async"),
                    help="TrainEngine tier (default: jit for --ocean; "
                         "--host-env always runs the host tier; 'async' is "
                         "the actor–learner split: spawn actors stream "
                         "rollout fragments, the learner consumes at its "
                         "own rate)")
    ap.add_argument("--num-actors", type=int, default=None,
                    help="async tier: spawn actor processes (default 2)")
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="async tier: max learner-version lag before a "
                         "fragment is dropped/importance-clipped (default 2)")
    ap.add_argument("--staleness-mode", default=None,
                    choices=("drop", "vtrace"),
                    help="async tier: stale-fragment policy — 'drop' "
                         "discards, 'vtrace' keeps them under truncated "
                         "importance weights (default drop)")
    ap.add_argument("--host-env", default=None,
                    help="host-mirror env name(s, comma-separated) or 'all' "
                         "(envs/ocean_host.py registry), trained through "
                         "bridge.wrap on the host tier")
    ap.add_argument("--host-backend", default=None,
                    choices=("thread", "proc"),
                    help="host-tier worker backend: 'thread' (default; env "
                         "steps that release the GIL) or 'proc' (shared-"
                         "memory spawn processes; pure-Python env steps "
                         "parallelize across cores)")
    ap.add_argument("--updates-per-launch", "-K", type=int, default=1,
                    help="fused updates per host dispatch (engine K)")
    ap.add_argument("--selfplay", action="store_true",
                    help="train --ocean env(s) under league self-play: "
                         "frozen opponents sampled from the policy store "
                         "in --league-dir (multi-agent envs only)")
    ap.add_argument("--league-dir", default=None,
                    help="policy-league directory (store + ratings); "
                         "required with --selfplay")
    ap.add_argument("--snapshot-every", type=int, default=10,
                    help="selfplay: updates between store snapshots")
    ap.add_argument("--strategy", default="prioritized",
                    choices=("latest", "uniform", "prioritized"),
                    help="selfplay opponent sampling strategy")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for --arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--total-env-steps", type=int, default=0,
                    help="env-step budget for --ocean (0 → the env preset)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM (e.g. 16x16); device count must match")
    ap.add_argument("--devices", type=int, default=0,
                    help="force host platform device count (dry runs)")
    ap.add_argument("--run-dir", default=None,
                    help="telemetry for --ocean runs: spans + metrics "
                         "stream into this directory; inspect with "
                         "`python -m repro.telemetry summarize <dir>`")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics, /healthz, /spans on "
                         "127.0.0.1:<port> for the duration of --ocean "
                         "training (0 = pick a free ephemeral port; "
                         "default: no server)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the first --profile-launches engine "
                         "launches in a jax.profiler trace written to DIR "
                         "(view in Perfetto/TensorBoard)")
    ap.add_argument("--profile-launches", type=int, default=3,
                    help="launches to capture under --profile (default 3)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count="
                                   f"{args.devices}")

    import jax
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.conformance and not args.ocean:
        ap.error("--conformance requires --ocean <name(s)|all>")

    async_overrides = {
        k: v for k, v in (("num_actors", args.num_actors),
                          ("max_staleness", args.max_staleness),
                          ("staleness_mode", args.staleness_mode))
        if v is not None}
    if async_overrides and args.engine_backend != "async":
        ap.error("--num-actors/--max-staleness/--staleness-mode are async-"
                 "tier knobs; pass --engine-backend async")
    if args.engine_backend == "async":
        if args.updates_per_launch != 1:
            ap.error("-K/--updates-per-launch is a fused-scan knob; the "
                     "async tier's learner consumes one fragment batch per "
                     "update (K=1)")
        if args.selfplay:
            ap.error("--selfplay drives the device-resident tiers (frozen "
                     "opponents live in the fused update); the async tier "
                     "does not ship opponent params through the slab")

    if args.host_env or args.engine_backend == "host":
        # third-party host envs through the bridge, async host tier
        from repro.bridge import make_host_engine
        from repro.configs.ocean import ocean_tcfg, preset
        from repro.envs.ocean_host import OCEAN_HOST
        if not args.host_env:
            ap.error("--engine-backend host requires --host-env "
                     "<name(s)|all>")
        if args.engine_backend not in (None, "host"):
            ap.error(f"--host-env runs on the host tier; got "
                     f"--engine-backend {args.engine_backend} (bridged "
                     f"host envs cannot run inside jit/shard_map/pool)")
        if args.updates_per_launch != 1:
            ap.error("-K/--updates-per-launch is a fused-scan knob; the "
                     "host tier dispatches one update per trajectory (K=1)")
        names = list(OCEAN_HOST) if args.host_env == "all" \
            else [n.strip() for n in args.host_env.split(",")]
        for name in names:
            p = preset(name)
            tcfg = ocean_tcfg(name, checkpoint_dir=args.ckpt_dir,
                              engine_backend="host", updates_per_launch=1,
                              host_backend=args.host_backend or "thread")
            eng = make_host_engine(OCEAN_HOST[name], tcfg, hidden=p.hidden,
                                   recurrent=p.recurrent, seed=args.seed)
            steps = args.total_env_steps or p.total_steps
            print(f"=== host/{name} (M={eng.hvec.num_envs} "
                  f"N={eng.hvec.batch_envs} "
                  f"workers={eng.hvec.backend}) ===")
            try:
                hist, solved = eng.run(steps,
                                       target_score=p.target_score)
            finally:
                eng.close()
            m = solved if solved is not None else hist[-1]
            status = "SOLVED" if m["score"] >= p.target_score else "unsolved"
            print(f"  -> {status} score={m['score']:.3f} "
                  f"steps={m['env_steps']} sps={m['sps']:.0f}")
        return

    if args.conformance:
        # --selfplay routes to the competitive-env (league) profile
        from repro.envs.conformance import run_cli
        raise SystemExit(run_cli(args.ocean, seed=args.seed,
                                 selfplay=args.selfplay))

    if args.selfplay:
        # league self-play: frozen opponents from the --league-dir store
        from repro.configs.ocean import ocean_tcfg, preset
        from repro.envs.ocean import OCEAN
        from repro.league import run_selfplay
        if not args.ocean:
            ap.error("--selfplay requires --ocean <name(s)> (e.g. duel)")
        if not args.league_dir:
            ap.error("--selfplay requires --league-dir")
        names = [n.strip() for n in args.ocean.split(",")]
        for name in names:
            p = preset(name)
            tcfg = ocean_tcfg(name, checkpoint_dir=args.ckpt_dir,
                              engine_backend=args.engine_backend or "jit",
                              updates_per_launch=args.updates_per_launch)
            steps = args.total_env_steps or p.total_steps
            ldir = os.path.join(args.league_dir, name) if len(names) > 1 \
                else args.league_dir
            print(f"=== selfplay/{name} (league={ldir}) ===")
            res = run_selfplay(
                OCEAN[name](), tcfg, league_dir=ldir, total_steps=steps,
                snapshot_every=args.snapshot_every, hidden=p.hidden,
                recurrent=p.recurrent, conv=p.conv, strategy=args.strategy,
                seed=args.seed, backend=args.engine_backend or "jit",
                log_every=10)
            status = ("SOLVED" if res.winrate_random >= p.target_score
                      else "unsolved")
            print(f"  -> {status} winrate_vs_random="
                  f"{res.winrate_random:.3f} versions={len(res.store)}")
            print(res.ranker.leaderboard())
        return

    if args.ocean:
        from repro import telemetry
        from repro.envs.ocean import OCEAN
        if args.run_dir:
            telemetry.enable(args.run_dir)
        server = None
        if args.metrics_port is not None:
            from repro.telemetry.http import MetricsServer
            server = MetricsServer(port=args.metrics_port)
            print(f"monitoring: {server.url}/metrics  "
                  f"{server.url}/healthz  {server.url}/spans")
        on_launch = None
        if args.profile:
            prof = {"launches": 0, "active": False}

            def on_launch(u, _prof=prof):
                if _prof["launches"] == 0:
                    jax.profiler.start_trace(args.profile)
                    _prof["active"] = True
                _prof["launches"] += 1
                if _prof["active"] and \
                        _prof["launches"] >= args.profile_launches:
                    jax.profiler.stop_trace()
                    _prof["active"] = False
        names = list(OCEAN) if args.ocean == "all" \
            else [n.strip() for n in args.ocean.split(",")]
        try:
            for name in names:
                backend = args.engine_backend or "jit"
                tr, p = ocean_trainer(
                    name, seed=args.seed, log_dir=args.run_dir,
                    checkpoint_dir=args.ckpt_dir, engine_backend=backend,
                    updates_per_launch=args.updates_per_launch,
                    checkpoint_every=args.save_every,
                    metrics_port=(server.port if server else 0),
                    **async_overrides)
                tcfg = tr.tcfg
                if server is not None:
                    # fixed key: replaces the previous env's source, so a
                    # closed engine never lingers as a dead health source
                    server.add_source("engine", tr.engine.stats)
                steps = args.total_env_steps or p.total_steps
                extra = (f" actors={tcfg.num_actors} staleness="
                         f"{tcfg.staleness_mode}<={tcfg.max_staleness}"
                         if backend == "async" else "")
                print(f"=== {name} (recurrent={p.recurrent}{extra}) ===")
                try:
                    m = tr.train(steps, log_every=10,
                                 target_score=p.target_score,
                                 checkpoint_dir=os.path.join(args.ckpt_dir,
                                                             name),
                                 resume=args.resume, on_launch=on_launch)
                finally:
                    tr.engine.close()  # async tier: actor procs + slab
                    tr.logger.close()  # crash-safe final flush
                if not m:
                    print("  -> resumed past the step budget; nothing to do")
                    continue
                status = ("SOLVED" if m["score"] >= p.target_score
                          else "unsolved")
                print(f"  -> {status} score={m['score']:.3f} "
                      f"steps={m['env_steps']} sps={m['sps']:.0f}")
        finally:
            if server is not None:
                server.close()
            if args.profile and prof["active"]:
                jax.profiler.stop_trace()
            if args.run_dir:
                telemetry.flush()
                print(f"telemetry: python -m repro.telemetry summarize "
                      f"{args.run_dir}")
        return

    # ---- LM backbone PPO -----------------------------------------------------
    loop, _ = train_lm(args.arch, smoke=args.smoke, mesh=args.mesh,
                       batch=args.batch, seq=args.seq, steps=args.steps,
                       seed=args.seed, ckpt_dir=args.ckpt_dir,
                       save_every=args.save_every, resume=args.resume)
    print(f"done: {loop.steps_done} steps, {loop.recoveries} recoveries, "
          f"{loop.monitor.flagged} straggler flags")

if __name__ == "__main__":
    main()
