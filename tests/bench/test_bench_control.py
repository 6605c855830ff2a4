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
devices in a child process, under the jit cell's limits."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, harness  # noqa: E402

SEED = 2**31 + 99
BM = harness.benchmark()
# per configuration: traffic and configuration overrides of the test size
SIZES = {"ocean-squared": ({"num_envs": 16}, {})}
# case -> (configuration, traffic, chips)
CASES = {w["name"]: (w["config"], harness.traffic(w["traffic"]), w["chips"])
         for w in BM["workloads"]}
CASES["squared.shard_map.test"] = (
    "ocean-squared", dict(harness.traffic("squared.jit.e4096"),
                          tier="shard_map"), 4)


def _faults(case):
    name, _, chips = CASES[case]
    return [f for f in harness.driver(name).FAULTS
            if f != "no_exchange" or chips > 1]


def outcomes(case: str) -> dict:
    """``correct`` of the sound run and of each fault, and whether the
    control fails a compared number: ``{case: bool}``."""
    name, traffic, chips = CASES[case]
    tfc, cfg = SIZES[name]
    traffic = dict(traffic, **tfc)
    config = dict(harness.config(name), **cfg)
    drv = harness.driver(name)
    limits = traffic["limits"]

    def fails(nums):
        return any(nums[k] > v for k, v in limits.items())

    def cell():
        return drv.Cell(config, traffic, SEED, chips)

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
    for f in _faults(case):
        out[f] = correct(f)
    out["control_fails"] = fails(compare.training_numbers(
        cell().reference_side(control=True), ref))
    return out


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


@pytest.mark.parametrize("case,fault", [(c, f) for c in CASES
                                        for f in _faults(c)])
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
        return harness.run(w["name"], SEED, 0.2, False, require_tpu=False,
                           traffic_override=SIZES[w["config"]][0])
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
