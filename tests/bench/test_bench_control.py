"""``correct`` comes out true for a sound run and false for a broken one.

Each case drives a configuration's ``Cell`` on the CPU at a test size, as
a run does after the chip check: set-up (the first steps, through the
timed path), then the comparison with the reference against the limits.
It runs once as it is and once with each fault its driver plants under the
timed path: a step that returns its state unchanged, half of the batch
left out with the mean over the rest, an action or token altered where it
is produced, and on several chips the exchange between chips left out.
The control, the reference in the next lower precision put in the
program's place, must fail at least one compared number.

Cases: every cell of ``BENCHMARK.json`` under its own limits (set on the
chip), and the shard_map tier of the Ocean cell on four virtual CPU
devices in a child process, under the jit cell's limits. Each
configuration runs at the test size its driver declares in ``TEST_SIZE``,
so a new configuration needs no edit here."""
import contextlib
import json
import os
import subprocess
import sys
from pathlib import Path
from unittest import mock

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, harness  # noqa: E402

SEED = 2**31 + 99
BM = harness.benchmark()
# case -> (configuration, traffic, chips)
CASES = {w["name"]: (w["config"], harness.traffic(w["traffic"]), w["chips"])
         for w in BM["workloads"]}
_JIT = harness.workload(BM, "squared.jit.e4096")
CASES["squared.shard_map.test"] = (
    _JIT["config"], dict(harness.traffic(_JIT["traffic"]), tier="shard_map"),
    4)


def _faults(config, chips, bench=harness.BENCH):
    return [f for f in harness.driver(config, bench).FAULTS
            if f != "no_exchange" or chips > 1]


@contextlib.contextmanager
def at_test_size(config, bench=harness.BENCH):
    """For ``harness.run`` of a cell of ``config`` at the driver's test
    size: the configuration's overrides lie over ``harness.config``
    meanwhile, and the traffic's are yielded for ``traffic_override``."""
    size = harness.driver(config, bench).TEST_SIZE
    real = harness.config

    def config_at_size(name, bench=harness.BENCH):
        cfg = real(name, bench)
        return dict(cfg, **size["config"]) if name == config else cfg
    with mock.patch.object(harness, "config", config_at_size):
        yield size["traffic"]


def outcomes_of(config, traffic, chips, bench=harness.BENCH, faults=None,
                control=True) -> dict:
    """``correct`` of the sound run and of each fault (those given, else
    every fault of the driver that ``chips`` can have), and whether the
    control fails a compared number: ``{case: bool}``. ``config`` names the
    configuration under ``bench``; it and ``traffic`` run at the driver's
    test size."""
    drv = harness.driver(config, bench)
    cfg = dict(harness.config(config, bench), **drv.TEST_SIZE["config"])
    traffic = dict(traffic, **drv.TEST_SIZE["traffic"])
    limits = traffic["limits"]

    def fails(nums):
        return any(nums[k] > v for k, v in limits.items())

    def cell():
        return drv.Cell(cfg, traffic, SEED, chips)

    ref = cell().reference_side()

    def correct(fault=None):
        c = cell()
        if fault is None:
            c.setup()
        else:
            with drv.FAULTS[fault]():
                c.setup()
        c.release()
        return not fails(c.numbers(ref))

    out = {"sound": correct()}
    for f in _faults(config, chips, bench) if faults is None else faults:
        out[f] = correct(f)
    if control:
        out["control_fails"] = fails(compare.training_numbers(
            cell().reference_side(control=True), ref))
    return out


def outcomes(case: str) -> dict:
    """``outcomes_of`` the case's configuration, traffic and chips."""
    return outcomes_of(*CASES[case])


@pytest.fixture(scope="module")
def results():
    cache = {}

    def get(case):
        if case not in cache:
            chips = CASES[case][2]
            if chips == 1:
                cache[case] = outcomes(case)
            else:
                env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
                    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                    f"platform_device_count={chips}"))
                code = (f"import sys, json; sys.path[:0] = [{str(ROOT)!r}, "
                        f"{str(ROOT / 'src')!r}]; "
                        "from tests.bench.test_bench_control import outcomes; "
                        f"print(json.dumps(outcomes({case!r})))")
                p = subprocess.run([sys.executable, "-c", code], env=env,
                                   capture_output=True, text=True,
                                   timeout=600, cwd=str(ROOT))
                assert p.returncode == 0, p.stderr[-3000:]
                cache[case] = json.loads(p.stdout.strip().splitlines()[-1])
        return cache[case]
    return get


@pytest.mark.parametrize("case", list(CASES))
def test_sound_run_is_correct(results, case):
    assert results(case)["sound"]


@pytest.mark.parametrize("case,fault", [(c, f) for c, (cfg, _, chips)
                                        in CASES.items()
                                        for f in _faults(cfg, chips)])
def test_fault_under_the_timed_path_is_not_correct(results, case, fault):
    assert results(case)[fault] is False


@pytest.mark.parametrize("case", list(CASES))
def test_control_fails_a_compared_number(results, case):
    assert results(case)["control_fails"]


@pytest.mark.parametrize("fault", [None, "state_unchanged"])
def test_harness_run_reports_correct_and_ends_with_the_checks(fault):
    w = harness.workload(BM, "squared.jit.e4096")
    drv = harness.driver(w["config"])

    def run():
        with at_test_size(w["config"]) as tfc:
            return harness.run(w["name"], SEED, 0.2, False,
                               require_tpu=False, traffic_override=tfc)
    if fault is None:
        out = run()
    else:
        with drv.FAULTS[fault]():
            out = run()
    assert out["correct"] is (fault is None)
    assert list(out)[-1] == "checks"
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert all(set(c) == {"value", "limit"} for c in out["checks"].values())
