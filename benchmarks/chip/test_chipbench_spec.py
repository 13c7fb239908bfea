"""Every cell of BENCHMARK.json resolves to its files; a new cell, mix,
deployment or metric is found by its name, with no file edited."""
import json
import os
import re

import pytest

from chipbench import harness, spec
from chipbench.traffic import Mix
from chipbench_testing import REPO, run_cell, tiny_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def quiet_jax(monkeypatch, tmp_path):
    """No persistent compile cache, and JAX's settings as they were."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        assert cell.chips == w["chips"]
        names = {m.name for m in cell.end_to_end}
        assert {"setup_s", "cloudlets_per_s"} <= names
        assert cell.per_layer, w["name"]
        assert all(callable(m.read) for m in cell.end_to_end + cell.per_layer)
        mixes = [Mix(cell.config, cell.traffic, seed)
                 for seed in (2**31 + 5, 2**33 + 7)]
        # the seed orders the pairs and changes no work
        work = [sorted(p for i in range(8) for p in m.study(i))
                for m in mixes]
        assert work[0] == work[1] and work[0]
        assert mixes[0].replicates >= 1
        assert set(cell.config["checks"]) == {
            "placements_wrong", "states_wrong", "time_rel_err",
            "energy_rel_err", "lanes_short"}


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))
    every = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
             + bench["per_layer"])
    assert all(NAME.match(x["name"]) for x in every)
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(REPO, c["file"])) as f:
            assert set(c["reduced"]) <= set(json.load(f))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    moved = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in moved
        assert set(m.get("workloads", cells)) <= cells


def test_readers_of_the_whole_window():
    """The rate is all the window's cloudlets over all its time."""
    read = {n: spec._reader(spec.BENCH_DIR, n)
            for n in ("cloudlets_per_s", "setup_s", "build_s", "compile_s")}
    studies = [harness.StudyRun(0, 10.0, 11.0, 100, 0),
               harness.StudyRun(1, 11.0, 12.5, 300, 0),
               harness.StudyRun(2, 12.5, 13.0, 50, 0)]
    rec = harness.Record(chips=1, setup_s=7.5, build_s=1.5, compile_s=2.5,
                         cache_hits=3, studies=studies, memory_peak_bytes=0)
    assert rec.window_s == 3.0
    assert read["cloudlets_per_s"](rec) == pytest.approx(450 / 3.0)
    assert read["setup_s"](rec) == 7.5
    assert read["build_s"](rec) == 1.5
    assert read["compile_s"](rec) == 2.5


def test_a_new_cell_is_found_by_name(tmp_path, quiet_jax):
    root, bench_dir = tiny_root(tmp_path)
    with open(os.path.join(bench_dir, "configs", "paper_fig89.json")) as f:
        dep = json.load(f)
    dep["hosts"]["count"] = 8
    with open(os.path.join(bench_dir, "configs", "small_dc.json"), "w") as f:
        json.dump(dep, f)
    with open(os.path.join(bench_dir, "traffic", "time_shared.json"),
              "w") as f:
        json.dump({"runner": "engine.run", "policy_pairs": [[1, 1]],
                   "max_steps": 64}, f)
    with open(os.path.join(bench_dir, "metrics", "studies_run.py"),
              "w") as f:
        f.write("def read(record):\n    return len(record.studies)\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "small_dc", "source": "test", "reduced": [], "why": "test",
         "file": "benchmarks/chip/configs/small_dc.json"})
    bench["workloads"].append(
        {"name": "small.time", "config": "small_dc",
         "traffic": "time_shared", "chips": 1, "why": "test"})
    bench["end_to_end"].append(
        {"name": "studies_run", "unit": "studies", "better": "higher",
         "bound": 0.25, "source": "host_clock", "workloads": ["small.time"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell(root, "small.time", bench_dir)
    assert cell.config["hosts"]["count"] == 8
    assert [m.name for m in cell.end_to_end][-1] == "studies_run"
    result = run_cell(root, bench_dir, "small.time")
    assert result["correct"] is True
    assert result["metrics"]["studies_run"]["value"] == result["attempted"]
    # the cells already there do not report the new metric
    other = spec.load_cell(root, "fig89.single", bench_dir)
    assert "studies_run" not in {m.name for m in other.end_to_end}
