"""repro.analysis: every lint rule fires on a planted violation (and not on
noqa'd / static-attribute lookalikes), every jaxpr/HLO audit check fires on a
planted program (and not on clean ones), baselines round-trip count-aware,
the CLI gates correctly, and the host-pool timeout satellites hold."""
import json
import os
import queue
import subprocess
import sys
import textwrap
import threading
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import (RULES, apply_baseline, audit_fn, check_source,
                            load_baseline, save_baseline)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _src(text: str) -> str:
    return textwrap.dedent(text)


def _rules(findings) -> set:
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# layer 1: one planted violation per rule

def test_tracer_branch_on_jitted_if():
    fs = check_source(_src("""
        import jax

        @jax.jit
        def branchy(x):
            if x > 0:
                return x
            return -x
    """))
    assert "TRACER-BRANCH" in _rules(fs)


def test_tracer_branch_via_scan_body_assert():
    fs = check_source(_src("""
        import jax
        import jax.numpy as jnp

        def inner_traced():
            def body(c, x):
                assert x > 0
                return c + x, x
            return jax.lax.scan(body, 0.0, jnp.ones(3))
    """))
    assert "TRACER-BRANCH" in _rules(fs)


def test_host_sync_in_traced_and_loop():
    fs = check_source(_src("""
        import jax

        @jax.jit
        def syncy(x):
            y = x * 2
            return float(y)

        def hot_loop(vals):
            out = []
            for v in vals:
                out.append(float(jax.device_get(v)))
            return out
    """))
    assert "HOST-SYNC" in _rules(fs)
    assert sum(f.rule == "HOST-SYNC" for f in fs) >= 2


def test_blocking_no_timeout_on_bare_get():
    fs = check_source(_src("""
        import queue

        def worker(q: "queue.Queue"):
            item = q.get()
            return item
    """))
    assert "BLOCKING-NO-TIMEOUT" in _rules(fs)


def test_blocking_with_timeout_not_flagged():
    fs = check_source(_src("""
        import queue

        def worker(q: "queue.Queue"):
            return q.get(timeout=1.0)
    """))
    assert "BLOCKING-NO-TIMEOUT" not in _rules(fs)


def test_blocking_gate_sees_submodule_imports():
    """``import multiprocessing.shared_memory`` must arm the threaded-code
    gate (root-normalized), so multiprocessing Queue.get()/Process.join()
    sites are covered like their ``queue``/``threading`` twins."""
    fs = check_source(_src("""
        import multiprocessing.shared_memory

        def pump(q, p):
            item = q.get()
            p.join()
            return item
    """))
    assert sum(f.rule == "BLOCKING-NO-TIMEOUT" for f in fs) == 2


def test_blocking_connection_wait_flagged():
    """``connection.wait(objects)`` blocks forever by default — its
    positional arg is the object list, not a timeout."""
    fs = check_source(_src("""
        from multiprocessing import connection

        def pump(sentinels):
            return connection.wait(sentinels)
    """))
    assert "BLOCKING-NO-TIMEOUT" in _rules(fs)

    fs = check_source(_src("""
        from multiprocessing import connection

        def pump(sentinels):
            return connection.wait(sentinels, timeout=1.0)
    """))
    assert "BLOCKING-NO-TIMEOUT" not in _rules(fs)


def test_blocking_bare_wait_from_import_flagged():
    fs = check_source(_src("""
        from multiprocessing.connection import wait

        def pump(sentinels):
            return wait(sentinels)
    """))
    assert "BLOCKING-NO-TIMEOUT" in _rules(fs)


def test_blocking_repro_waits_need_timeout_kwarg():
    """The repo's own cross-process waits (shm.spin_until, the async
    tier's wait_fragments) are covered — with or without the stdlib
    import gate, as a method or a bare call."""
    fs = check_source(_src("""
        from repro.core import shm

        def drain(ro, pred):
            shm.spin_until(pred)                 # no timeout
            frags = ro.wait_fragments(4)         # no timeout
            return frags
    """))
    assert sum(f.rule == "BLOCKING-NO-TIMEOUT" for f in fs) == 2

    fs = check_source(_src("""
        from repro.core.shm import spin_until

        def drain(ro, pred):
            spin_until(pred, timeout=5.0)
            return ro.wait_fragments(4, timeout=60.0)
    """))
    assert "BLOCKING-NO-TIMEOUT" not in _rules(fs)


def test_nondet_in_pure_on_time_call():
    fs = check_source(_src("""
        import time
        import jax

        @jax.jit
        def stampy(x):
            return x + time.time()
    """))
    assert "NONDET-IN-PURE" in _rules(fs)


def test_donation_reuse_after_donating_call():
    fs = check_source(_src("""
        import jax

        def trainer(ts, batch):
            step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
            out = step(ts, batch)
            print(ts.mean())
            return out
    """))
    assert "DONATION-REUSE" in _rules(fs)


def test_impure_import_numpy_in_jitted():
    fs = check_source(_src("""
        import numpy as np
        import jax

        @jax.jit
        def mixed(x):
            return np.tanh(x)
    """))
    assert "IMPURE-IMPORT" in _rules(fs)


def test_telemetry_in_jit_flags_span_under_trace():
    fs = check_source(_src("""
        import jax
        from repro.telemetry import span

        @jax.jit
        def instrumented(x):
            with span("learn"):
                return x * 2
    """))
    assert "TELEMETRY-IN-JIT" in _rules(fs)


def test_telemetry_in_jit_flags_aliased_and_scan_body():
    fs = check_source(_src("""
        import jax
        import jax.numpy as jnp
        from repro import telemetry
        from repro.telemetry import span as _span

        def launch():
            def body(c, x):
                telemetry.registry().counter("steps").inc()
                with _span("step"):
                    c = c + x
                return c, x
            return jax.lax.scan(body, 0.0, jnp.ones(3))
    """))
    assert sum(f.rule == "TELEMETRY-IN-JIT" for f in fs) >= 2


def test_telemetry_in_host_loop_is_clean():
    fs = check_source(_src("""
        import jax
        from repro.telemetry import span

        def host_loop(launch, n):
            out = []
            for i in range(n):
                with span("engine.launch"):
                    out.append(launch(i))
            return out
    """))
    assert "TELEMETRY-IN-JIT" not in _rules(fs)


def test_telemetry_in_jit_noqa_suppresses():
    fs = check_source(_src("""
        import jax
        from repro.telemetry import span

        @jax.jit
        def waived(x):
            with span("trace-time-only"):  # repro: noqa[TELEMETRY-IN-JIT]
                return x * 2
    """))
    assert "TELEMETRY-IN-JIT" not in _rules(fs)


# ---------------------------------------------------------------------------
# layer 1: suppression and static lookalikes

def test_noqa_suppresses_named_rule():
    fs = check_source(_src("""
        import jax

        @jax.jit
        def quiet(x):
            if x > 0:                      # repro: noqa[TRACER-BRANCH]
                return x
            return -x
    """))
    assert "TRACER-BRANCH" not in _rules(fs)


def test_bare_noqa_suppresses_everything():
    fs = check_source(_src("""
        import jax

        @jax.jit
        def quiet(x):
            if x > 0:                      # repro: noqa
                return x
            return -x
    """))
    assert not fs


def test_noqa_for_other_rule_does_not_suppress():
    fs = check_source(_src("""
        import jax

        @jax.jit
        def loud(x):
            if x > 0:                      # repro: noqa[HOST-SYNC]
                return x
            return -x
    """))
    assert "TRACER-BRANCH" in _rules(fs)


def test_shape_branch_is_static_and_clean():
    fs = check_source(_src("""
        import jax

        @jax.jit
        def shape_branch(x):
            if x.shape[0] > 2:
                return x
            return x * 2
    """))
    assert not fs


def test_syntax_error_is_a_finding():
    fs = check_source("def broken(:\n")
    assert [f.rule for f in fs] == ["SYNTAX"]


# ---------------------------------------------------------------------------
# baseline round-trip (count-aware multiset)

_TWO_GETS = _src("""
    import queue

    def worker_a(q: "queue.Queue"):
        return q.get()

    def worker_b(q: "queue.Queue"):
        return q.get()
""")

_ONE_GET = _src("""
    import queue

    def worker_a(q: "queue.Queue"):
        return q.get()
""")


def test_baseline_roundtrip(tmp_path):
    fs = check_source(_TWO_GETS, path="w.py")
    assert len(fs) == 2
    bl = tmp_path / "baseline.json"
    save_baseline(fs, bl)
    loaded = load_baseline(bl)
    assert sum(loaded.values()) == 2
    assert apply_baseline(fs, loaded) == []


def test_baseline_is_count_aware(tmp_path):
    bl = tmp_path / "baseline.json"
    save_baseline(check_source(_ONE_GET, path="w.py"), bl)
    fresh = apply_baseline(check_source(_TWO_GETS, path="w.py"),
                           load_baseline(bl))
    assert len(fresh) == 1            # one grandfathered, one fresh


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == {}
    assert load_baseline(None) == {}


# ---------------------------------------------------------------------------
# layer 2: planted audit violations, one per check

def test_audit_clean_function_passes():
    res = audit_fn(lambda x: jnp.tanh(x) * 2.0,
                   (jnp.ones((4,), jnp.float32),),
                   variants=[(jnp.ones((8,), jnp.float32),)],
                   name="clean")
    assert res.ok, [v.render() for v in res.violations]
    assert set(res.checks) == {"host-callback", "f64-promotion", "retrace"}


def test_audit_detects_host_callback():
    def cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    res = audit_fn(cb, (jnp.ones((4,), jnp.float32),),
                   check_retrace=False, check_f64=False)
    assert any(v.check == "host-callback" for v in res.violations)


def test_audit_allow_callbacks_whitelist():
    def cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    res = audit_fn(cb, (jnp.ones((4,), jnp.float32),),
                   check_retrace=False, check_f64=False,
                   allow_callbacks=("pure_callback",))
    assert res.ok


def test_audit_detects_retrace_on_static_flip():
    def rt(x, flag):
        return x * (2.0 if flag else 3.0)

    x = jnp.ones((4,), jnp.float32)
    res = audit_fn(rt, (x, False), variants=[(x, True)],
                   check_callbacks=False, check_f64=False)
    assert any(v.check == "retrace" for v in res.violations)


def test_audit_no_retrace_across_shape_sweep():
    res = audit_fn(lambda x: x * 2.0, (jnp.ones((4,), jnp.float32),),
                   variants=[(jnp.ones((8,), jnp.float32),)],
                   check_callbacks=False, check_f64=False)
    assert res.ok


def test_audit_detects_unconsumed_donation():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # XLA warns about the same thing
        res = audit_fn(lambda x: jnp.sum(x),
                       (jnp.ones((64,), jnp.float32),),
                       donate_argnums=(0,), check_retrace=False)
    assert any(v.check == "donation" for v in res.violations)


def test_audit_donation_consumed_passes():
    res = audit_fn(lambda x: x + 1.0, (jnp.ones((64,), jnp.float32),),
                   donate_argnums=(0,), check_retrace=False)
    assert res.ok, [v.render() for v in res.violations]
    assert "donation" in res.checks


def test_audit_detects_f64_promotion():
    def widen(x):
        return x.astype(jnp.float64) * 2.0

    with jax.enable_x64(True):
        res = audit_fn(widen, (jnp.ones((4,), jnp.float32),),
                       check_retrace=False)
    assert any(v.check == "f64-promotion" for v in res.violations)


def test_audit_f64_input_is_allowed():
    with jax.enable_x64(True):
        res = audit_fn(lambda x: x * 2.0,
                       (jnp.ones((4,), jnp.float64),),
                       check_retrace=False)
    assert res.ok


def test_audit_trace_failure_is_reported():
    res = audit_fn(lambda x: x @ jnp.ones((99, 2)),
                   (jnp.ones((4, 4), jnp.float32),))
    assert any(v.check == "trace" for v in res.violations)


# ---------------------------------------------------------------------------
# target enumeration: coverage must not silently shrink

def test_kernel_coverage_gate(monkeypatch):
    from repro.analysis import targets
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "ops", lambda: ["mystery_op"])
    out = targets.audit_kernel_ops()
    assert len(out) == 1
    assert any(v.check == "coverage" for v in out[0].violations)


def test_audit_bandit_env_clean():
    from repro.analysis import audit_ocean_envs
    (res,) = audit_ocean_envs(["bandit"])
    assert res.ok, [v.render() for v in res.violations]


# ---------------------------------------------------------------------------
# CLI

_BAD = ("import jax\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n")


def _cli(*argv):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run([sys.executable, "-m", "repro.analysis", *argv],
                          capture_output=True, text=True, env=env, cwd=ROOT)


def test_cli_exits_nonzero_on_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(_BAD)
    out = _cli(str(bad))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "TRACER-BRANCH" in out.stdout


def test_cli_report_only_and_json(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(_BAD)
    out = _cli(str(bad), "--report-only", "--format", "json")
    assert out.returncode == 0, out.stdout + out.stderr
    report = json.loads(out.stdout)
    assert any(f["rule"] == "TRACER-BRANCH" for f in report["findings"])
    assert set(RULES) <= set(report["rules"])


def test_cli_baseline_gates(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(_BAD)
    bl = tmp_path / "baseline.json"
    up = _cli(str(bad), "--baseline", str(bl), "--update-baseline")
    assert up.returncode == 0, up.stdout + up.stderr
    out = _cli(str(bad), "--baseline", str(bl))
    assert out.returncode == 0, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# satellites: HostPool stop-polling and recv timeouts

class _TinyEnv:
    def reset(self, seed):
        return np.zeros((1,), np.float32)

    def step(self, a):
        return np.zeros((1,), np.float32), 1.0, False, {}


class _HangEnv:
    """reset() blocks until released — a deadlocked host env."""

    def __init__(self, release):
        self._release = release

    def reset(self, seed):
        self._release.wait(20)
        return np.zeros((1,), np.float32)

    def step(self, a):
        return np.zeros((1,), np.float32), 0.0, False, {}


class _DeadInbox:
    """Inbox whose sentinel can never be delivered nor drained."""

    def get(self, timeout=None):
        raise queue.Empty

    def get_nowait(self):
        raise queue.Empty

    def put_nowait(self, item):
        raise queue.Full


def test_close_joins_workers_with_empty_inbox():
    from repro.core.host import HostPool
    pool = HostPool([_TinyEnv, _TinyEnv], batch_size=2, recv_timeout=5.0)
    pool.recv()                       # drain the initial resets
    pool.close(timeout=3.0)           # workers are parked on empty inboxes
    assert all(not t.is_alive() for t in pool._threads)


def test_stop_flag_wins_when_sentinel_undeliverable():
    """Regression: the worker must poll, not park — with the close sentinel
    undeliverable, only the _stop check can end the loop."""
    from repro.core.host import HostPool
    pool = HostPool([_TinyEnv], batch_size=1, recv_timeout=5.0)
    pool.recv()
    pool._inboxes[0] = _DeadInbox()
    pool.close(timeout=3.0)
    assert not pool._threads[0].is_alive()


def test_recv_uses_pool_default_timeout():
    from repro.core.host import HostPool
    release = threading.Event()
    pool = HostPool([lambda: _HangEnv(release)], batch_size=1,
                    recv_timeout=0.2)
    with pytest.raises(TimeoutError):
        pool.recv()                   # no argument: pool default applies
    release.set()
    pool.close(timeout=3.0)


def test_recv_explicit_timeout_overrides_default():
    from repro.core.host import HostPool
    release = threading.Event()
    pool = HostPool([lambda: _HangEnv(release)], batch_size=1,
                    recv_timeout=None)
    with pytest.raises(TimeoutError):
        pool.recv(timeout=0.2)
    release.set()
    pool.close(timeout=3.0)


def test_wrap_default_timeout_is_trainconfig():
    import inspect
    from repro.bridge.vecenv import wrap
    from repro.configs.base import TrainConfig
    default = inspect.signature(wrap).parameters["recv_timeout"].default
    assert default == TrainConfig.host_recv_timeout
    assert default is not None        # hung host envs raise, not deadlock


def test_hostvecenv_reset_times_out_on_hung_env():
    from repro.bridge.vecenv import wrap
    from repro.core import spaces as sp

    class _HangDuck(_HangEnv):
        def __init__(self, release):
            super().__init__(release)
            self.observation_space = sp.Box((1,))
            self.action_space = sp.Discrete(2)

    release = threading.Event()
    hv = wrap(lambda: _HangDuck(release), num_envs=1, api="duck",
              recv_timeout=0.25)
    with pytest.raises(TimeoutError):
        hv.reset()
    release.set()
    hv.close(timeout=3.0)
