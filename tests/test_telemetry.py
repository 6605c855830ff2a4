"""Telemetry subsystem: spans (nesting, bounded ring, the disabled no-op
fast path, Chrome export, flush + CLI summarize), the metrics registry
(labels, histograms, Prometheus exposition, MetricsLogger feed), the
cross-process stat slabs (proc host pool + async actors, survivor
consistency after a kill), the shared per-tier TierTimer keys, and the
MetricsLogger hardening satellites (NaN scrubbing, idempotent close)."""
import json
import math
import os
import threading
import time
import tracemalloc

import numpy as np
import pytest

import jax

from repro import telemetry
from repro.telemetry import __main__ as tcli
from repro.telemetry import spans as tspans
from repro.telemetry.procstats import (ACTOR_FIELDS, STALENESS_EDGES,
                                       StatSlab)
from repro.telemetry.registry import registry
from repro.telemetry.timers import TierTimer
from repro.utils import metrics as ml

RECV_T = 30.0


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Span tracing and the default registry are process-global switches —
    every test starts and ends with both off/empty."""
    telemetry.disable()
    registry().reset()
    yield
    telemetry.disable()
    registry().reset()


# ---------------------------------------------------------------------------
# spans: disabled fast path

def test_disabled_span_is_shared_singleton():
    assert not telemetry.enabled()
    s1, s2 = telemetry.span("a"), telemetry.span("b")
    assert s1 is s2                       # one module-level no-op object
    with s1:
        pass                              # usable as a context manager
    assert telemetry.flush() == 0


def test_disabled_span_allocates_nothing():
    for _ in range(10):                   # warm up interpreter caches
        with telemetry.span("warm"):
            pass
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            with telemetry.span("hot"):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    filt = (tracemalloc.Filter(True, tspans.__file__),)
    stats = after.filter_traces(filt).compare_to(
        before.filter_traces(filt), "filename")
    assert sum(s.size_diff for s in stats) == 0


def test_disabled_span_tight_loop_bound():
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with telemetry.span("x"):
            pass
    dt = time.perf_counter() - t0
    # generous CI-safe bound: < 5 µs/span (measured ~0.1 µs); a regression
    # that makes the disabled path allocate or lock blows way past this
    assert dt < n * 5e-6, f"{dt / n * 1e9:.0f} ns per disabled span"


# ---------------------------------------------------------------------------
# spans: enabled path

def test_span_nesting_records_depth_and_parent():
    telemetry.enable()
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
    recs = {r.name: r for r in telemetry.get_tracer().records()}
    assert recs["inner"].depth == 1 and recs["inner"].parent == "outer"
    assert recs["outer"].depth == 0 and recs["outer"].parent == ""
    assert recs["outer"].dur_ns >= recs["inner"].dur_ns >= 0


def test_span_ring_is_bounded():
    telemetry.enable(capacity=16)
    for i in range(100):
        with telemetry.span(f"s{i}"):
            pass
    recs = telemetry.get_tracer().records()
    assert len(recs) == 16
    assert recs[-1].name == "s99"         # newest survive


def test_span_ring_is_thread_safe():
    telemetry.enable(capacity=100_000)

    def burn():
        for _ in range(500):
            with telemetry.span("t"):
                pass

    threads = [threading.Thread(target=burn) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(telemetry.get_tracer().drain()) == 8 * 500


def test_reenable_same_args_keeps_tracer():
    t1 = telemetry.enable()
    with telemetry.span("kept"):
        pass
    t2 = telemetry.enable()
    assert t1 is t2 and len(t2.records()) == 1


def test_chrome_trace_structure():
    telemetry.enable()
    with telemetry.span("a"):
        with telemetry.span("b"):
            pass
    trace = tspans.chrome_trace(telemetry.get_tracer().records())
    evs = trace["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X"
        assert ev["name"] in ("a", "b")
        assert ev["dur"] >= 0 and "ts" in ev and "pid" in ev and "tid" in ev


def test_summarize_records_percentiles():
    mk = lambda name, dur: {"name": name, "dur_ns": dur}  # noqa: E731
    recs = [mk("op", int(d * 1e6)) for d in range(1, 101)]  # 1..100 ms
    s = tspans.summarize_records(recs)["op"]
    assert s["count"] == 100
    assert 50.0 <= s["p50_ms"] <= 52.0
    assert 99.0 <= s["p99_ms"] <= 100.0
    assert s["max_ms"] == 100.0


# ---------------------------------------------------------------------------
# spans: flush + CLI

def test_flush_and_cli_summarize(tmp_path):
    run_dir = str(tmp_path)
    telemetry.enable(run_dir=run_dir)
    for _ in range(5):
        with telemetry.span("engine.launch"):
            pass
    with telemetry.span("engine.fetch"):
        pass
    assert telemetry.flush() == 6
    assert telemetry.flush() == 0         # drained; second flush is empty
    assert os.path.exists(os.path.join(run_dir, tspans.SPANS_FILE))

    data = tcli.summarize(run_dir, out=open(os.devnull, "w"))
    assert data["n_span_records"] == 6
    assert set(data["spans"]) == {"engine.launch", "engine.fetch"}
    assert data["spans"]["engine.launch"]["count"] == 5

    out = str(tmp_path / "trace.json")
    assert tcli.export_trace(run_dir, out) == 6
    with open(out) as f:
        events = json.load(f)["traceEvents"]
    # 6 duration events + one process_name lane-metadata event per process
    assert sum(1 for e in events if e.get("ph") == "X") == 6
    assert sum(1 for e in events if e.get("ph") == "M") == 1

    assert tcli.main(["summarize", run_dir]) == 0
    assert tcli.main(["summarize", str(tmp_path / "nope")]) == 2


def test_cli_summarize_reads_sps_curve(tmp_path):
    run_dir = str(tmp_path)
    telemetry.enable(run_dir=run_dir)
    with telemetry.span("s"):
        pass
    telemetry.flush()
    with ml.MetricsLogger(run_dir, "run") as logger:
        for i in range(4):
            logger.log((i + 1) * 64, {"env_steps": (i + 1) * 64,
                                      "sps": 1000.0 + i})
    data = tcli.summarize(run_dir, out=open(os.devnull, "w"))
    assert data["sps_curve"]["n"] == 4
    assert data["sps_curve"]["last"] == 1003.0


# ---------------------------------------------------------------------------
# registry

def test_registry_counters_gauges_labels():
    reg = registry()
    reg.counter("updates", tier="jit").inc()
    reg.counter("updates", tier="jit").inc(2)
    reg.counter("updates", tier="pool").inc()
    reg.gauge("workers").set(4)
    snap = reg.snapshot()
    assert snap["counters"]["updates{tier=jit}"] == 3.0
    assert snap["counters"]["updates{tier=pool}"] == 1.0
    assert snap["gauges"]["workers"] == 4.0


def test_registry_histogram_quantiles_and_flat():
    reg = registry()
    h = reg.histogram("lat_ms", edges=(1.0, 10.0, 100.0))
    for v in (0.5, 0.7, 5.0, 50.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(56.2)
    assert reg.histogram("lat_ms", edges=(1.0, 10.0, 100.0)) is h
    flat = reg.flat(prefix="telemetry.")
    assert flat["telemetry.lat_ms_count"] == 4
    assert flat["telemetry.lat_ms_p50"] == 1.0   # 2 of 4 in the <=1 bucket
    assert flat["telemetry.lat_ms_p99"] == 100.0


def test_registry_prometheus_cumulative_buckets():
    reg = registry()
    h = reg.histogram("wait", edges=(1.0, 2.0), tier="async")
    for v in (0.5, 1.5, 99.0):
        h.observe(v)
    reg.counter("errs").inc()
    text = reg.to_prometheus()
    assert "# TYPE errs counter" in text
    assert "# TYPE wait histogram" in text
    assert 'wait_bucket{tier="async",le="1.0"} 1' in text
    assert 'wait_bucket{tier="async",le="2.0"} 2' in text
    assert 'wait_bucket{tier="async",le="+Inf"} 3' in text


def test_registry_emit_feeds_metrics_logger(tmp_path):
    reg = registry()
    reg.counter("engine.updates", tier="jit").inc(7)
    with ml.MetricsLogger(str(tmp_path), "run") as logger:
        reg.emit(logger, step=640)
    rows = ml.read(logger.path)
    assert len(rows) == 1
    assert rows[0]["step"] == 640
    assert rows[0]["telemetry.engine.updates{tier=jit}"] == 7.0


# ---------------------------------------------------------------------------
# MetricsLogger hardening satellites

def test_metrics_logger_nan_inf_round_trip(tmp_path):
    with ml.MetricsLogger(str(tmp_path), "run") as logger:
        logger.log(1, {"loss": float("nan"), "kl": float("inf"),
                       "score": 0.5})
    raw = open(logger.path).read()
    assert "NaN" not in raw and "Infinity" not in raw  # strict-parser-safe
    rows = ml.read(logger.path)
    assert rows[0]["loss"] is None and rows[0]["kl"] is None
    assert rows[0]["score"] == 0.5


def test_metrics_logger_close_idempotent(tmp_path):
    logger = ml.MetricsLogger(str(tmp_path), "run")
    logger.log(1, {"a": 1.0}, flush=False)
    logger.close()
    logger.close()                        # second close is a no-op
    logger.log(2, {"a": 2.0})             # post-close log is a silent no-op
    logger.flush()
    rows = ml.read(logger.path)
    assert len(rows) == 1 and rows[0]["a"] == 1.0


def test_metrics_logger_context_manager_flushes_on_error(tmp_path):
    with pytest.raises(RuntimeError):
        with ml.MetricsLogger(str(tmp_path), "run") as logger:
            logger.log(1, {"a": 1.0}, flush=False)
            raise RuntimeError("interrupted")
    rows = ml.read(logger.path)
    assert rows and rows[-1]["a"] == 1.0  # final record survived the crash


# ---------------------------------------------------------------------------
# TierTimer: the one shared sps/launch_ms/fetch_ms implementation

def test_tier_timer_stamps_unified_keys():
    timer = TierTimer(64)
    with timer.launch():
        pass
    with timer.fetch():
        pass
    md = {}
    timer.stamp(md, 3 * 64)
    assert md["env_steps"] == 192
    assert md["sps"] > 0
    assert md["launch_ms"] >= 0 and md["fetch_ms"] >= 0


def test_tier_timer_resume_aware_sps():
    timer = TierTimer(64, done_before_steps=10 * 64)
    time.sleep(0.01)
    # resumed run: only the 2 new updates count toward this run's rate
    assert timer.sps(12 * 64) == pytest.approx(
        2 * 64 / timer.elapsed(), rel=0.5)


def test_tier_timer_opens_spans_when_enabled():
    telemetry.enable()
    timer = TierTimer(64)
    with timer.launch():
        pass
    with timer.fetch():
        pass
    names = [r.name for r in telemetry.get_tracer().records()]
    assert names == ["engine.launch", "engine.fetch"]


@pytest.mark.timeout(300)
def test_jit_and_pool_tiers_emit_same_telemetry_keys():
    """Satellite: all tiers report the same unified keys (here the two
    cheapest tiers; the async acceptance test covers the fifth)."""
    from repro.configs.base import TrainConfig
    from repro.core.emulation import Emulated
    from repro.envs.ocean import Bandit
    from repro.models.policy import OceanPolicy
    from repro.rl.distributions import Dist
    from repro.rl.engine import TrainEngine

    tcfg = TrainConfig(num_envs=16, unroll_length=16, update_epochs=1,
                       num_minibatches=2, learning_rate=1e-3, gamma=0.95)
    keys = ("env_steps", "sps", "launch_ms", "fetch_ms")
    for backend in ("jit", "pool"):
        em = Emulated(Bandit())
        dist = Dist("categorical", nvec=em.act_spec.nvec)
        pol = OceanPolicy(em.obs_spec.total, dist.nvec, hidden=32,
                          num_outputs=dist.num_outputs)
        eng = TrainEngine(em, pol, tcfg, dist, key=jax.random.PRNGKey(0),
                          backend=backend, kernel_mode="ref")
        hist, _ = eng.run(2 * eng.steps_per_update)
        assert len(hist) == 2
        for m in hist:
            for k in keys:
                assert k in m, (backend, k)
                assert math.isfinite(m[k]), (backend, k)


# ---------------------------------------------------------------------------
# stat slabs

def test_stat_slab_create_attach_aggregate():
    owner = StatSlab.create(2, ACTOR_FIELDS, STALENESS_EDGES)
    try:
        worker = StatSlab.attach(owner.spec)  # what a spawn worker does
        row = worker.row(1)
        row.add("steps", 64)
        row.add("fragments")
        row.set("errors", 0)
        for v in (0.0, 3.0, 99.0):
            row.observe(v)
        del row                            # drop views before close
        worker.close()

        agg = owner.aggregate()
        assert agg["rows"] == 2
        assert agg["total"]["steps"] == 64
        assert agg["per_worker"]["steps"] == [0, 64]
        assert agg["per_worker"]["fragments"] == [0, 1]
        assert agg["hist"]["edges"] == list(STALENESS_EDGES)
        # 0.0 <= edge 0; 3.0 <= 4; 99 overflows
        assert agg["hist"]["counts"] == [1, 0, 0, 1, 0, 1]
    finally:
        owner.close()


@pytest.mark.timeout(300)
def test_proc_host_pool_stats_aggregate():
    from repro.bridge import wrap
    from repro.envs.ocean_host import HostBandit
    v = wrap(HostBandit, num_envs=2, backend="proc")
    try:
        obs = v.reset(timeout=RECV_T)
        for _ in range(3):
            obs, _rew, _done, _info = v.step(
                np.zeros((len(obs), 1), np.int32), timeout=RECV_T)
        st = v.pool.stats()
        assert st["backend"] == "proc" and st["workers"] == 2
        assert st["steps"] >= 3           # parent-side recv accounting
        det = st["workers_detail"]        # worker-side slab accounting
        assert det["rows"] == 2
        assert det["total"]["steps"] + det["total"]["resets"] >= 5
        assert det["total"]["errors"] == 0
        assert det["total"]["wait_ns"] > 0
    finally:
        v.close()


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


@pytest.mark.timeout(300)
def test_actor_stat_slab_survives_killed_actor():
    """Satellite: after a mid-run actor kill + reshard the slab stays
    consistent — the survivor's row keeps counting, the dead actor's row
    stays readable (frozen), aggregation never blocks or pickles."""
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
        assert len(hist) == 6
        st = eng.rollouts.stats()
        assert st["dead"] == [1]
        agg = st["actors"]
        assert agg["rows"] == 2
        per = agg["per_worker"]
        assert per["fragments"][0] > 0            # survivor kept committing
        assert per["fragments"][1] >= 0           # dead row readable, frozen
        assert agg["total"]["steps"] >= 6 * spu   # every update's data came
        assert sum(agg["hist"]["counts"]) == agg["total"]["fragments"]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# acceptance: a real async run, summarized

@pytest.mark.timeout(600)
def test_async_run_summarize_reports_span_breadth(tmp_path):
    """Acceptance: `python -m repro.telemetry summarize <run_dir>` on a real
    --engine-backend async run reports p50/p99 for >= 8 distinct spans."""
    run_dir = str(tmp_path / "run")
    ckpt_dir = str(tmp_path / "ckpt")
    telemetry.enable(run_dir=run_dir)
    spu = 8 * 8
    eng = _async_engine(ckpt_dir, checkpoint_every=2)
    logger = ml.MetricsLogger(run_dir, "bandit")
    try:
        hist, _ = eng.run(total_steps=spu * 4, logger=logger)
        assert len(hist) == 4
    finally:
        eng.close()
        logger.close()
    time.sleep(0.5)                       # async ckpt write thread lands
    telemetry.flush()

    data = tcli.summarize(run_dir, out=open(os.devnull, "w"))
    names = set(data["spans"])
    assert len(names) >= 8, sorted(names)
    expect = {"engine.run", "engine.launch", "engine.fetch",
              "engine.collect", "engine.stack_fragments",
              "async.wait_fragments", "async.publish", "ckpt.snapshot"}
    assert expect <= names, sorted(expect - names)
    for s in data["spans"].values():
        assert s["p99_ms"] >= s["p50_ms"] >= 0
    assert data["sps_curve"]["n"] == 4    # logger stream fed the curve
    # registry summary record landed in the same stream (telemetry enabled)
    recs = tcli.load_metrics(run_dir)
    assert any(k.startswith("telemetry.engine.updates")
               for r in recs for k in r)
