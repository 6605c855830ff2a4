"""BENCHMARK.json is consistent as data, and the harness finds a cell, a
traffic mix and a per-layer metric by name alone: adding one needs new
files and entries, no edit of code."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def test_top_level_keys_and_paths():
    assert set(BM) == TOP_KEYS
    assert 1 <= len(BM["paths"]) <= 16
    for p in BM["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert BM["command"] == ["python3", "bench/run.py"]


def test_names_and_units_use_the_allowed_characters():
    names = ([c["name"] for c in BM["configs"]]
             + [w["name"] for w in BM["workloads"]]
             + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]])
    names += [w["traffic"] for w in BM["workloads"]]
    names += [k for c in BM["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in BM["end_to_end"] + BM["per_layer"])) \
        == len(BM["end_to_end"]) + len(BM["per_layer"])
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_workload_names_an_existing_configuration_and_traffic():
    configs = {c["name"]: c for c in BM["configs"]}
    for w in BM["workloads"]:
        assert w["config"] in configs
        d = ROOT / "bench/configs" / w["config"]
        for f in ("config.json", "driver.py", "reference.py"):
            assert (d / f).is_file(), d / f
        t = harness.traffic(w["traffic"])
        assert t["limits"], w["name"]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in BM["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        assert any(w["config"] == c["name"] for w in BM["workloads"])
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)


def _reports(cell):
    return {m["name"] for m in harness.cell_metrics(BM, "end_to_end", cell)}


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for w in BM["workloads"]:
        e2e = _reports(w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(BM, "per_layer", w["name"])


def test_every_layer_metric_moves_an_end_to_end_metric_its_cells_report():
    e2e = {m["name"] for m in BM["end_to_end"]}
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert m["moves"] in _reports(cell), (m["name"], cell)
        assert callable(harness.reader(m["name"]).read)
        assert "\n" not in m["layer"] and m["layer"]


def test_bounds_and_run_length_fit_the_check():
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    rs = BM["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_a_new_cell_traffic_and_metric_are_found_by_name(tmp_path):
    """A copy of the benchmark gains a cell, its traffic file and a
    per-layer metric as data alone; the unchanged harness finds and runs
    them (on the CPU, at a test size)."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    tfc = harness.traffic("squared.jit.e4096")
    tfc["num_envs"] = 8
    (bench / "traffic" / "squared.jit.e8.json").write_text(json.dumps(tfc))
    (bench / "metrics" / "work.ocean.py").write_text(
        "def read(ctx):\n    return ctx['work']\n")
    bm = json.loads(json.dumps(BM))
    bm["workloads"].append({"name": "squared.jit.e8",
                            "config": "ocean-squared",
                            "traffic": "squared.jit.e8", "chips": 1,
                            "why": "a test cell"})
    for m in bm["end_to_end"]:
        if m["name"] == "agent_steps_per_s":
            m["workloads"].append("squared.jit.e8")
    bm["per_layer"].append({"name": "work.ocean", "unit": "agent_steps",
                            "better": "higher", "source": "host_clock",
                            "layer": "engine", "moves": "agent_steps_per_s",
                            "workloads": ["squared.jit.e8"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    assert harness.traffic("squared.jit.e8", bench)["num_envs"] == 8
    assert harness.reader("work.ocean", bench).read({"work": 3}) == 3
    assert [m["name"] for m in harness.cell_metrics(
        bm, "per_layer", "squared.jit.e8")] == ["work.ocean"]
    out = harness.run("squared.jit.e8", 5, 0.5, False, require_tpu=False,
                      bench=bench, root=tmp_path)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"agent_steps_per_s", "setup_s"}
    assert out["metrics"]["agent_steps_per_s"]["value"] > 0


def test_every_configuration_declares_a_test_size():
    """Each configuration's driver declares the overrides that run it on
    the CPU, and every key it overrides exists in the configuration or in
    each of its cells' traffic: a misspelt key fails here instead of
    running a model at full width."""
    for c in BM["configs"]:
        drv = harness.driver(c["name"])
        assert hasattr(drv, "TEST_SIZE"), c["name"]
        size = drv.TEST_SIZE
        assert set(size) == {"traffic", "config"}, c["name"]
        assert set(size["config"]) <= set(harness.config(c["name"])), \
            c["name"]
        for w in BM["workloads"]:
            if w["config"] == c["name"]:
                assert set(size["traffic"]) <= set(
                    harness.traffic(w["traffic"])), w["name"]


def _files(root: Path) -> dict:
    return {p.relative_to(root).as_posix(): p.read_bytes()
            for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_configuration_is_found_by_name(tmp_path):
    """A copy of the benchmark gains a configuration (Ocean Squared under
    another name), its traffic file and a cell as new files and entries
    alone; the unchanged harness runs the cell at the new driver's test
    size, the control tests take it, and no file that was there changes
    but ``BENCHMARK.json``."""
    from tests.bench.test_bench_control import at_test_size, outcomes_of
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BM))
    before = _files(tmp_path)

    name, cell = "ocean-squared-copy", "copy.jit.e4096"
    shutil.copytree(bench / "configs" / "ocean-squared",
                    bench / "configs" / name)
    cfg = dict(harness.config("ocean-squared", bench), name=name)
    (bench / "configs" / name / "config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / f"{cell}.json").write_text(
        json.dumps(harness.traffic("squared.jit.e4096", bench)))
    bm = json.loads(json.dumps(BM))
    entry = next(c for c in bm["configs"] if c["name"] == "ocean-squared")
    bm["configs"].append(dict(entry, name=name,
                              file=f"bench/configs/{name}/config.json"))
    bm["workloads"].append({"name": cell, "config": name, "traffic": cell,
                            "chips": 1, "why": "a test cell"})
    for m in bm["end_to_end"]:
        if m["name"] == "agent_steps_per_s":
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    with at_test_size(name, bench) as tfc:
        out = harness.run(cell, 5, 0.5, False, require_tpu=False,
                          traffic_override=tfc, bench=bench, root=tmp_path)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"agent_steps_per_s", "setup_s"}
    res = outcomes_of(name, harness.traffic(cell, bench), 1, bench,
                      faults=["state_unchanged"], control=False)
    assert res == {"sound": True, "state_unchanged": False}
    after = _files(tmp_path)
    assert [f for f in before if f != "BENCHMARK.json"
            and after.get(f) != before[f]] == []


def test_run_exits_nonzero_without_a_tpu():
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench/run.py"),
                        "--workload", "squared.jit.e4096", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_device_check_refuses_the_cpu_and_too_few_chips():
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.device(1)
    with pytest.raises(harness.NoChip, match="needs 64 chips"):
        harness.device(64, require_tpu=False)
