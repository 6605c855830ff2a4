"""Observability PR: cross-process trace propagation (worker span files,
clock-offset rebasing, killed-worker merge tolerance, the export-trace
acceptance on a real async run), the live monitoring endpoints over a real
socket (/metrics, /healthz flipping on a killed actor, /spans), the
benchwatch perf-regression sentinel (baseline, gating, fingerprint
isolation), and the BLOCKING-NO-TIMEOUT lint extension to accept loops."""
import glob
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from repro import telemetry
from repro.telemetry import __main__ as tcli
from repro.telemetry import benchwatch, traceprop
from repro.telemetry import spans as tspans
from repro.telemetry.http import MetricsServer, collect_health
from repro.telemetry.registry import registry

RECV_T = 30.0
HTTP_T = 5.0


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    registry().reset()
    yield
    telemetry.disable()
    registry().reset()


def _get(url, timeout=HTTP_T):
    """(status, body bytes) — non-2xx statuses returned, not raised."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ---------------------------------------------------------------------------
# clock offset + worker file plumbing

def test_clock_offset_maps_monotonic_onto_wall_clock():
    off = tspans.clock_offset_ns()
    # monotonic + offset ≈ wall, within scheduling noise
    assert abs(time.monotonic_ns() + off - time.time_ns()) < 50_000_000
    # and the estimate is stable call-to-call (median of 5 samples)
    assert abs(tspans.clock_offset_ns() - off) < 50_000_000


def test_traceprop_current_none_without_run_dir():
    assert traceprop.current() is None       # tracing off
    telemetry.enable()                       # ring-only: nowhere to flush
    assert traceprop.current() is None
    telemetry.disable()


def test_traceprop_current_snapshots_tracer(tmp_path):
    telemetry.enable(run_dir=str(tmp_path))
    cfg = traceprop.current()
    assert cfg is not None and cfg.run_dir == str(tmp_path)
    assert cfg.trace_id == tspans.get_tracer().trace_id
    # the parent's own file carries an eagerly-written meta header
    files = traceprop.load_run_spans(str(tmp_path))
    assert len(files) == 1
    meta, recs = files[0]
    assert meta["pid"] == os.getpid() and meta["role"] == "main"
    assert meta["trace_id"] == cfg.trace_id and recs == []


def test_merge_tolerates_torn_tail_and_missing_meta(tmp_path):
    run_dir = str(tmp_path)
    # a healthy worker file
    with open(os.path.join(run_dir, "spans-111.jsonl"), "w") as f:
        f.write(json.dumps({"kind": "meta", "schema": 1, "trace_id": "t",
                            "pid": 111, "role": "host-worker-0",
                            "clock_offset_ns": 1000}) + "\n")
        f.write(json.dumps({"name": "worker.step", "ts_ns": 50, "dur_ns": 10,
                            "pid": 111, "tid": 1, "depth": 0,
                            "parent": ""}) + "\n")
        f.write('{"name": "worker.step", "ts_ns": 60, "dur')  # SIGKILL tear
    # a meta-less file from a pre-handshake writer
    with open(os.path.join(run_dir, "spans-222.jsonl"), "w") as f:
        f.write(json.dumps({"name": "worker.reset", "ts_ns": 30, "dur_ns": 5,
                            "pid": 222, "tid": 2, "depth": 0,
                            "parent": ""}) + "\n")
    recs = traceprop.merged_records(run_dir)
    assert [r["name"] for r in recs] == ["worker.reset", "worker.step"]
    by_pid = {r["pid"]: r for r in recs}
    assert by_pid[111]["ts_ns"] == 1050      # offset applied
    assert by_pid[222]["ts_ns"] == 30        # no meta -> offset 0
    assert by_pid[222]["role"] == "pid-222"  # role recovered from filename
    trace = traceprop.merge_chrome_trace(run_dir)
    lanes = {e["pid"]: e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M"}
    assert lanes == {111: "host-worker-0", 222: "pid-222"}


# ---------------------------------------------------------------------------
# proc host pool: real spawn workers on one merged timeline

@pytest.mark.timeout(300)
def test_proc_pool_workers_merge_onto_one_timeline(tmp_path):
    """The tentpole acceptance for the host tier: a traced proc-pool run
    leaves one spans file per worker pid; the merge puts parent spawn/recv
    and worker step/reset on one wall-aligned timeline — and a worker
    SIGKILLed after its last flush (plus a planted torn tail) degrades the
    merge to 'skip the damage', never an error."""
    from repro.bridge import wrap
    from repro.envs.ocean_host import HostBandit
    run_dir = str(tmp_path)
    telemetry.enable(run_dir=run_dir)
    v = wrap(HostBandit, num_envs=2, backend="proc")
    try:
        obs = v.reset(timeout=RECV_T)
        for _ in range(3):
            obs, _r, _d, _i = v.step(np.zeros((len(obs), 1), np.int32),
                                     timeout=RECV_T)
        time.sleep(0.3)                 # cross the workers' flush cadence
        for _ in range(2):              # post-gap ops trigger the flush
            obs, _r, _d, _i = v.step(np.zeros((len(obs), 1), np.int32),
                                     timeout=RECV_T)
        live = v.pool.liveness()
        assert live["dead"] == []
        assert all(b > 0 for b in live["last_beat_ns"])
        v.pool._procs[1].kill()         # SIGKILL: no finally-flush
        v.pool._procs[1].join(timeout=10)
    finally:
        v.close()
    telemetry.flush()

    files = sorted(os.path.basename(p) for p in
                   glob.glob(os.path.join(run_dir, "spans*.jsonl")))
    assert len(files) == 3 and "spans.jsonl" in files
    # plant a torn tail on the killed worker's file
    worker_files = [f for f in files if f != "spans.jsonl"]
    with open(os.path.join(run_dir, worker_files[-1]), "a") as f:
        f.write('{"name": "worker.step", "ts_ns": 1, "d')

    trace = traceprop.merge_chrome_trace(run_dir)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    lanes = {e["pid"]: e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M"}
    assert len({e["pid"] for e in xs}) >= 3          # learner + 2 workers
    assert len(lanes) >= 3
    roles = set(lanes.values())
    assert "main" in roles
    assert {"host-worker-0", "host-worker-1"} <= roles
    assert len(trace["otherData"]["trace_ids"]) == 1  # one shared trace id

    # clock-offset monotonicity: after rebasing, no worker span starts
    # before the parent began spawning it (1 ms slack for offset noise)
    recs = traceprop.merged_records(run_dir)
    spawn = [r for r in recs if r["name"] == "host.spawn"]
    worker = [r for r in recs if r["role"].startswith("host-worker")]
    assert spawn and worker
    assert min(r["ts_ns"] for r in worker) >= spawn[0]["ts_ns"] - 1_000_000


# ---------------------------------------------------------------------------
# async tier: the export-trace acceptance

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


@pytest.mark.timeout(600)
def test_async_export_trace_merges_learner_and_actor_lanes(tmp_path):
    """ISSUE acceptance: export-trace on an async run dir yields ONE Chrome
    trace where the learner and >= 2 actor pids appear in distinct lanes."""
    run_dir = str(tmp_path / "run")
    telemetry.enable(run_dir=run_dir)
    spu = 8 * 8
    eng = _async_engine()
    try:
        hist, _ = eng.run(total_steps=spu * 3)
        assert len(hist) == 3
    finally:
        eng.close()                      # actors flush spans in finally
    telemetry.flush()

    out = str(tmp_path / "merged_trace.json")
    assert tcli.main(["export-trace", run_dir, "--out", out]) == 0
    with open(out) as f:
        trace = json.load(f)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    lanes = {e["pid"]: e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M"}
    assert len({e["pid"] for e in xs}) >= 3
    roles = set(lanes.values())
    assert "main" in roles and {"actor-0", "actor-1"} <= roles
    names = {e["name"] for e in xs}
    # learner-side waits and actor-side rollouts on the same timeline
    assert "async.wait_fragments" in names
    assert "actor.rollout" in names


# ---------------------------------------------------------------------------
# live endpoints over a real socket

def test_metrics_endpoint_serves_registry_and_slab_counters():
    registry().counter("updates", tier="jit").inc(3)

    def stats():
        return {"pool": {"workers": {"per_worker": {"steps": [5, 7]},
                                     "total": {"steps": 12}}}}

    with MetricsServer(port=0) as srv:
        srv.add_source("engine", stats)
        code, body = _get(f"{srv.url}/metrics")
        text = body.decode()
    assert code == 200
    assert 'updates{tier="jit"} 3' in text       # registry exposition
    assert ('repro_worker_steps_total{source="engine.pool.workers",'
            'worker="1"} 7') in text


def test_healthz_statuses_and_503_on_dead_worker():
    now = time.time_ns()
    live = {"ok": {"liveness": {"workers": 3, "dead": [],
                                "last_beat_ns": [now, 0, now]}}}
    doc = collect_health([("s", lambda: live)], stale_after_s=10.0)
    assert doc["ok"]
    assert [w["status"] for w in doc["workers"]] == ["ok", "booting", "ok"]

    stale = dict(live)
    stale["ok"] = {"liveness": {"workers": 1, "dead": [],
                                "last_beat_ns": [now - 60_000_000_000]}}
    doc = collect_health([("s", lambda: stale)], stale_after_s=10.0)
    assert doc["ok"]                      # stale labels, never flips
    assert doc["workers"][0]["status"] == "stale"

    dead = {"liveness": {"workers": 2, "dead": [1],
                         "last_beat_ns": [now, now]}}
    with MetricsServer(port=0) as srv:
        srv.add_source("engine", lambda: {"pool": dead})
        code, body = _get(f"{srv.url}/healthz")
    assert code == 503
    doc = json.loads(body)
    assert not doc["ok"]
    assert [w["status"] for w in doc["workers"]] == ["ok", "dead"]


def test_http_404_spans_endpoint_and_idempotent_close(tmp_path):
    telemetry.enable(run_dir=str(tmp_path))
    with telemetry.span("op"):
        pass
    srv = MetricsServer(port=0)
    try:
        code, _ = _get(f"{srv.url}/nope")
        assert code == 404
        code, body = _get(f"{srv.url}/spans")
        assert code == 200
        assert json.loads(body)["op"]["count"] == 1
    finally:
        srv.close()
    srv.close()                           # second close is a no-op
    with pytest.raises(urllib.error.URLError):
        _get(f"{srv.url}/metrics", timeout=1.0)


@pytest.mark.timeout(600)
def test_healthz_flips_when_actor_killed():
    """ISSUE acceptance: /healthz goes 200 -> 503 when an async actor is
    killed mid-run, naming the dead worker."""
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

    srv = MetricsServer(port=0)
    srv.add_source("engine", eng.stats)
    try:
        code, body = _get(f"{srv.url}/healthz")
        assert code == 200 and json.loads(body)["ok"]
        hist, _ = eng.run(total_steps=spu * 6, on_update=on_update)
        assert len(hist) == 6
        code, body = _get(f"{srv.url}/healthz")
        assert code == 503
        doc = json.loads(body)
        dead = [w for w in doc["workers"] if w["status"] == "dead"]
        assert [w["worker"] for w in dead] == [1]
        # /metrics keeps serving through the fault, with live slab counters
        code, body = _get(f"{srv.url}/metrics")
        assert code == 200
        assert "repro_worker_steps_total" in body.decode()
    finally:
        srv.close()
        eng.close()


def test_thread_pool_liveness_beats():
    from repro.bridge import wrap
    from repro.envs.ocean_host import HostBandit
    v = wrap(HostBandit, num_envs=2)             # thread backend
    try:
        obs = v.reset(timeout=RECV_T)
        v.step(np.zeros((len(obs), 1), np.int32), timeout=RECV_T)
        live = v.pool.stats()["liveness"]
        assert live["dead"] == []
        assert all(b > 0 for b in live["last_beat_ns"])
        assert len(live["last_beat_ns"]) == 2
    finally:
        v.close()


def test_straggler_monitor_exposes_staleness_age():
    from repro.distributed.fault import StragglerMonitor
    m = StragglerMonitor()
    assert m.age() is None                       # booting, not stale
    m.record(0.01)
    age = m.age()
    assert age is not None and 0 <= age < 5.0
    st = m.stats()
    assert st["samples"] == 1 and st["age_s"] >= age


# ---------------------------------------------------------------------------
# benchwatch: the perf-regression sentinel

def _hist(tmp_path):
    return str(tmp_path / "BENCH_history.jsonl")


def test_benchwatch_appends_schema_versioned_records(tmp_path):
    h = _hist(tmp_path)
    benchwatch.record("demo", {"sps": 100.0}, history=h)
    benchwatch.record("demo", {"sps": 101.0},
                      acceptance={"fast_enough": True}, history=h)
    recs = benchwatch.load_history(h)
    assert len(recs) == 2
    assert all(r["schema"] == benchwatch.SCHEMA for r in recs)
    assert recs[0]["fingerprint"] == benchwatch.fingerprint()
    assert recs[1]["acceptance"] == {"fast_enough": True}
    # a torn tail is skipped, not fatal
    with open(h, "a") as f:
        f.write('{"schema": 1, "bench": "demo"')
    assert len(benchwatch.load_history(h)) == 2


def test_benchwatch_gate_exits_nonzero_on_planted_regression(tmp_path):
    h = _hist(tmp_path)
    benchwatch.record("demo", {"sps": 1000.0}, history=h)
    assert tcli.main(["compare", "--history", h, "--gate"]) == 0  # no base
    benchwatch.record("demo", {"sps": 1020.0}, history=h)
    assert tcli.main(["compare", "--history", h, "--gate"]) == 0  # wiggle
    benchwatch.record("demo", {"sps": 800.0}, history=h)   # -20% planted
    assert tcli.main(["compare", "--history", h, "--gate"]) == 1
    # report-only default never gates
    assert tcli.main(["compare", "--history", h]) == 0
    result = benchwatch.compare(h)
    assert result["benches"]["demo"]["status"] == "regression"
    (reg,) = result["regressions"]
    assert reg["cell"] == "sps" and reg["delta_pct"] < -10


def test_benchwatch_fingerprint_mismatch_never_gates(tmp_path):
    h = _hist(tmp_path)
    rec = benchwatch.record("demo", {"sps": 1000.0}, history=h)
    other = dict(rec, fingerprint={"cores": 9999, "python": "9.9",
                                   "platform": "Other-arch"},
                 cells={"sps": 1.0})             # catastrophic "drop"
    with open(h, "a") as f:
        f.write(json.dumps(other) + "\n")
    result = benchwatch.compare(h)
    assert result["benches"]["demo"]["status"] == "no_baseline"
    assert result["regressions"] == []
    assert tcli.main(["compare", "--history", h, "--gate"]) == 0


def test_benchwatch_baseline_is_rolling_same_fingerprint_median(tmp_path):
    h = _hist(tmp_path)
    for sps in (900.0, 1000.0, 1100.0, 1005.0):
        benchwatch.record("demo", {"sps": sps}, history=h)
    cell = benchwatch.compare(h)["benches"]["demo"]["cells"]["sps"]
    assert cell["baseline"] == 1000.0            # median of first three
    assert cell["status"] == "ok"


# ---------------------------------------------------------------------------
# lint: BLOCKING-NO-TIMEOUT covers accept loops

def test_lint_flags_bare_accept_and_serve_forever():
    from repro.analysis import check_source
    src = ("import socket\n"
           "def serve(sock, httpd):\n"
           "    conn, addr = sock.accept()\n"
           "    httpd.serve_forever()\n")
    rules = {f.rule for f in check_source(src, "m.py")}
    assert "BLOCKING-NO-TIMEOUT" in rules
    fs = [f for f in check_source(src, "m.py")
          if f.rule == "BLOCKING-NO-TIMEOUT"]
    assert len(fs) == 2                          # accept AND serve_forever


def test_lint_http_module_is_clean():
    from repro.analysis import check_file
    path = os.path.join(os.path.dirname(__file__), os.pardir, "src",
                        "repro", "telemetry", "http.py")
    assert [f.rule for f in check_file(path)] == []
