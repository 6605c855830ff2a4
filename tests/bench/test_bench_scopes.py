"""Device time per phase of the PPO update (``bench/phases.py`` and its
readers) and idle gaps named by program spans, on a small hand-made trace:
a launch module run twice in the window and once before it, a ``while`` and
its body ops, and a key-split module with an op whose text equals one of
the launch's. Also the instruction map the readers take from the program,
on a small HLO text and on a live launch on the CPU."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, phases, trace  # noqa: E402
from repro.rl.engine import hlo_op_scopes  # noqa: E402
from repro.telemetry import SpanRecord, registry  # noqa: E402

US_MS = 1e-3
FIXTURE = str(ROOT / "tests/bench/data/scoped_trace.pbtxt")

# the optimized-HLO text of the fixture's launch, as ``as_text()`` prints it
HLO = """HloModule jit_launch, is_scheduled=true

%fused_computation.4 (param_0: f32[16], param_1: s32[4]) -> f32[4] {
  %param_0 = f32[16]{0:T(128)} parameter(0)
  %param_1 = s32[4]{0:T(128)} parameter(1)
  ROOT %gather.9 = f32[4]{0:T(128)} gather(%param_0, %param_1), metadata={op_name="jit(launch)/ppo_learn/while/body/jvp(ppo_minibatch_rows)/gather"}
}

%region_0.1 (arg_tuple.1: (f32[8], s32[])) -> (f32[8], s32[]) {
  %arg_tuple.1 = (f32[8]{0:T(128)}, s32[]{:T(128)}) parameter(0)
  %fusion.1 = u32[2]{0:T(128)} fusion(%key.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(launch)/ppo_rollout/while/body/threefry2x32"}
  %fusion.2 = f32[8]{0:T(128)} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(launch)/ppo_rollout/while/body/ppo_env_step/add"}
  %fusion.3 = (f32[8]{0:T(128)}, s32[]{:T(128)}) fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
  %gather_fusion.4 = f32[4]{0:T(128)} fusion(%param.1, %iota.1), kind=kCustom, calls=%fused_computation.4, metadata={op_name="jit(launch)/ppo_learn/while/body/jvp(ppo_minibatch_rows)/gather"}
  ROOT %tuple.2 = (f32[8]{0:T(128)}, s32[]{:T(128)}) tuple(%fusion.2, %constant.1)
}

ENTRY %main.3 (p: f32[8]) -> f32[8] {
  %while.5 = (f32[8]{0:T(128)}, s32[]{:T(128)}) while(%tuple.1), condition=%region_1.2, body=%region_0.1, metadata={op_name="jit(launch)/ppo_learn/while"}
  ROOT %copy.7 = f32[8]{0:T(128)} copy(%fusion.2), metadata={op_name="jit(launch)/copy"}
}
"""

KEYS = {
    "%fusion.1 = u32[2]{0:T(128)}": "ppo_rollout",
    "%fusion.2 = f32[8]{0:T(128)}": "ppo_env_step",
    "%fusion.3 = (f32[8]{0:T(128)}, s32[]{:T(128)})": "ppo_learn",
    "%gather_fusion.4 = f32[4]{0:T(128)}": "ppo_minibatch_rows",
}


def test_instruction_map_from_hlo_text():
    # fusion.3 has no op_name and takes its while's scope (as do the body's
    # parameter and tuple); the while, the fused computation's gather and
    # the unscoped copy are no keys
    got = hlo_op_scopes(HLO)
    assert got.items() >= KEYS.items()
    assert not [k for k in got if k.startswith(("%while", "%gather.9",
                                                "%copy.7"))]


@pytest.fixture
def scoped(monkeypatch):
    monkeypatch.setattr(phases, "latest_trace", lambda: FIXTURE)
    monkeypatch.setattr(phases, "launch_scopes",
                        lambda: (("jit_launch",), KEYS))
    monkeypatch.setattr(phases, "_cache", {})


def test_scope_time_counts_only_ops_of_launches_in_the_window():
    r = phases.scope_time(trace.load(FIXTURE), ["jit_launch"], KEYS)
    # the key split's fusion.1 [12, 14] matches the launch's key by text
    # alone; it runs outside the launch module and is not counted. The
    # launch before the window and its fusion.3 are not counted either.
    assert r["launches"] == 2
    assert r["seconds"] == pytest.approx({
        "ppo_rollout": 4e-6, "ppo_env_step": 6e-6, "ppo_learn": 30e-6,
        "ppo_minibatch_rows": 20e-6})


def test_scope_time_keys_tell_same_named_ops_apart():
    keys = dict(KEYS)
    del keys["%fusion.1 = u32[2]{0:T(128)}"]
    keys["%fusion.1 = u32[4]{0:T(128)}"] = "ppo_rollout"
    r = phases.scope_time(trace.load(FIXTURE), ["jit_launch"], keys)
    assert "ppo_rollout" not in r["seconds"]


@pytest.mark.parametrize("metric,want_us", [
    ("rollout_ms.ocean", 5.0),
    ("env_step_ms.ocean", 3.0),
    ("learn_ms.ocean", 25.0),
    ("gather_ms.ocean", 10.0),
])
def test_phase_readers_give_device_ms_per_launch(scoped, metric, want_us):
    assert harness.reader(metric).read({}) == pytest.approx(want_us * US_MS)


@pytest.mark.parametrize("metric", ["rollout_ms.ocean", "env_step_ms.ocean",
                                    "learn_ms.ocean", "gather_ms.ocean"])
def test_phase_readers_are_silent_without_scopes(monkeypatch, metric):
    monkeypatch.setattr(phases, "latest_trace", lambda: FIXTURE)
    monkeypatch.setattr(phases, "launch_scopes", lambda: ((), {}))
    monkeypatch.setattr(phases, "_cache", {})
    assert harness.reader(metric).read({}) is None


def test_drain_reader_averages_the_span():
    rec = lambda name, ms: SpanRecord(name, 0, int(ms * 1e6), 1, 1, 0, "")
    drain = harness.reader("drain_ms.fused")
    assert drain.read({"spans": [rec("engine.drain", 0.1),
                                 rec("engine.launch", 5.0),
                                 rec("engine.drain", 0.3)]}) \
        == pytest.approx(0.2)
    assert drain.read({"spans": [rec("engine.launch", 5.0)]}) is None


def test_compile_reader_reads_the_counter():
    compile_s = harness.reader("compile_s.ocean")
    was = registry().snapshot()["counters"].get("jax.compile_s")
    registry().reset()
    try:
        assert compile_s.read({}) is None
        registry().counter("jax.compile_s").inc(2.5)
        assert compile_s.read({}) == pytest.approx(2.5)
    finally:
        registry().reset()
        if was is not None:
            registry().counter("jax.compile_s").inc(was)


def test_idle_gaps_named_by_the_innermost_program_span():
    # TPU:0 idles [10, 12] and [14, 20] under engine.launch, the second
    # inside python.gc; [56, 70] under engine.drain (inside engine.run, over
    # a runtime event); [100, 110] under no program span
    names = ["engine.run", "engine.launch", "engine.drain", "python.gc"]
    assert phases.idle_by_span(trace.load(FIXTURE), names) == [
        ["engine.drain", pytest.approx(14e-6)],
        [phases.NO_SPAN, pytest.approx(10e-6)],
        ["python.gc", pytest.approx(6e-6)],
        ["engine.launch", pytest.approx(2e-6)]]


def test_launch_scopes_reads_the_live_launch():
    """The readers' map comes from the process's live executables: after one
    launch on the CPU it holds all four scopes of that launch."""
    import jax

    from repro.configs.ocean import ocean_tcfg
    from repro.envs.ocean import OCEAN
    from repro.rl.engine import TrainEngine
    from repro.rl.trainer import ocean_policy_stack
    em, dist, pol = ocean_policy_stack(OCEAN["squared"](), hidden=8)
    eng = TrainEngine(em, pol, ocean_tcfg("squared", num_envs=8,
                                          unroll_length=4), dist,
                      key=jax.random.PRNGKey(0))
    eng.run(eng.steps_per_update)
    modules, keys = phases.launch_scopes()
    assert "jit_launch" in modules
    assert set(keys.values()) == {"ppo_rollout", "ppo_env_step",
                                  "ppo_learn", "ppo_minibatch_rows"}
