"""Every cell of BENCHMARK.json resolves to its files; a new cell, mix,
deployment or metric is found by its name, with no file edited."""
import hashlib
import json
import os
import re
import shutil

import pytest

from chipbench import check, harness, spec
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
        mixes = [cell.kind.make_mix(cell.config, cell.traffic, seed)
                 for seed in (2**31 + 5, 2**33 + 7)]
        # the seed orders the work and changes none of it
        work = [sorted(p for i in range(8) for p in m.study(i))
                for m in mixes]
        assert work[0] == work[1] and work[0]
        assert mixes[0].lanes(mixes[0].study(0)) >= 1
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


# A deployment kind's own span and counter, appended to a copy of
# ``deployments/waves.py``: its program runs under the host span ``admit``
# and counts ``admissions``, names no kind has today.
OWN_SPAN_AND_COUNTER = """

import jax as _jax

_Waves = System
_waves_counters = counters


class System(_Waves):
    def dispatch(self, inputs):
        with _jax.profiler.TraceAnnotation("admit"):
            return super().dispatch(inputs)


def counters(mix, studies, outputs):
    return [dict(c, admissions=mix.lanes(s)) for c, s
            in zip(_waves_counters(mix, studies, outputs), studies)]
"""

# A per-layer metric of that span and counter, as a file of its own.
ADMIT_READER = """
import statistics


def read(record):
    tr = record.trace
    if tr is None or "admit" not in tr.host:
        return None
    per_study = []
    for (s, e), run in zip(tr.studies(), record.studies):
        ns = sum(min(z, e) - max(a, s) for a, z in tr.host["admit"]
                 if a < e and z > s)
        per_study.append(1e-3 * ns / run.counters["admissions"])
    return statistics.median(per_study)
"""


@pytest.mark.parametrize("kind", ["waves", "copied_kind", "counted_kind"])
def test_a_new_cell_is_found_by_name(tmp_path, quiet_jax, kind):
    """A configuration, a mix, a metric and a cell added as files and
    entries; under ``copied_kind`` the configuration names a deployment
    kind of its own, ``deployments/waves.py`` copied under a new name;
    under ``counted_kind`` that kind also runs under a span and counts with
    a counter of its own, and a new per-layer metric file reads both in a
    traced run."""
    root, bench_dir = tiny_root(tmp_path)
    with open(os.path.join(bench_dir, "configs", "paper_fig89.json")) as f:
        dep = json.load(f)
    dep["hosts"]["count"] = 8
    if kind != "waves":
        dep["generator"] = kind
        shutil.copy(os.path.join(bench_dir, "deployments", "waves.py"),
                    os.path.join(bench_dir, "deployments", f"{kind}.py"))
    if kind == "counted_kind":
        with open(os.path.join(bench_dir, "deployments", f"{kind}.py"),
                  "a") as f:
            f.write(OWN_SPAN_AND_COUNTER)
        with open(os.path.join(bench_dir, "metrics", "admit_host_us.py"),
                  "w") as f:
            f.write(ADMIT_READER)
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
    if kind == "counted_kind":
        bench["per_layer"].append(
            {"name": "admit_host_us", "unit": "us", "better": "lower",
             "source": "program_span", "layer": "admission",
             "moves": "studies_run", "workloads": ["small.time"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    before = chipbench_digest()
    cell = spec.load_cell(root, "small.time", bench_dir)
    assert cell.config["hosts"]["count"] == 8
    assert cell.kind.__file__ == os.path.join(bench_dir, "deployments",
                                              f"{kind}.py")
    assert [m.name for m in cell.end_to_end][-1] == "studies_run"
    result = run_cell(root, bench_dir, "small.time")
    assert result["correct"] is True
    assert result["metrics"]["studies_run"]["value"] == result["attempted"]
    # the cells already there do not report the new metric
    other = spec.load_cell(root, "fig89.single", bench_dir)
    assert "studies_run" not in {m.name for m in other.end_to_end}
    if kind == "counted_kind":
        traced = run_cell(root, bench_dir, "small.time", trace=1)
        assert traced["correct"] is True
        assert traced["metrics"]["admit_host_us"]["value"] > 0.0
        assert "admit_host_us" not in {m.name for m in other.per_layer}
    assert chipbench_digest() == before


def chipbench_digest():
    """A digest of the harness's own files."""
    h = hashlib.sha256()
    pkg = os.path.join(spec.BENCH_DIR, "chipbench")
    for name in sorted(os.listdir(pkg)):
        if name.endswith(".py"):
            with open(os.path.join(pkg, name), "rb") as f:
                h.update(name.encode() + f.read())
    return h.hexdigest()


def test_a_missing_kind_fails_loudly(tmp_path):
    root, bench_dir = tiny_root(tmp_path)
    path = os.path.join(bench_dir, "configs", "paper_fig89.json")
    with open(path) as f:
        dep = json.load(f)
    dep["generator"] = "no_such_kind"
    with open(path, "w") as f:
        json.dump(dep, f)
    with pytest.raises(FileNotFoundError, match="no_such_kind"):
        spec.load_cell(root, "sweep.grid", bench_dir)


@pytest.mark.parametrize("readings", [
    {"a": 0, "b": 0.0},                 # a check with no reading
    {"a": 0, "b": 0.0, "c": 0, "d": 1},  # a reading with no check
    {"a": 0, "c": 0},                   # both
])
def test_verdict_refuses_readings_and_checks_that_differ(readings):
    limits = {"a": 0, "b": 1e-3, "c": 0}
    with pytest.raises(check.Mismatch):
        check.verdict(readings, limits)
    correct, table = check.verdict({"a": 0, "b": 2e-3, "c": 0}, limits)
    assert correct is False and list(table) == ["a", "b", "c"]
    assert table["b"] == {"value": 2e-3, "limit": 1e-3}


# Each existing mix's first 8 studies for three seeds, as the harness
# before deployment kinds made them: a study's pairs (vm, task) as the
# digits 2 * vm + task, in lane order.
PINNED_STUDIES = {
    ("sweep.grid", 1): ["3102", "2310", "3201", "2130", "2031", "2103",
                        "1203", "1230"],
    ("sweep.grid", 2**31 + 11): ["0123", "0213", "1032", "0132", "1302",
                                 "3120", "3012", "3201"],
    ("sweep.grid", 2**33 + 7): ["3021", "0321", "2013", "1023", "3021",
                                "3102", "3012", "3102"],
    ("fig89.single", 1): ["3", "1", "0", "2", "2", "3", "1", "0"],
    ("fig89.single", 2**31 + 11): ["0", "1", "2", "3", "0", "2", "1", "3"],
    ("fig89.single", 2**33 + 7): ["3", "0", "2", "1", "0", "3", "2", "1"],
}


@pytest.mark.parametrize("workload,seed", sorted(PINNED_STUDIES))
def test_studies_pinned(workload, seed):
    cell = spec.load_cell(REPO, workload)
    mix = cell.kind.make_mix(cell.config, cell.traffic, seed)
    got = ["".join(str(2 * v + t) for v, t in mix.study(i))
           for i in range(8)]
    assert got == PINNED_STUDIES[workload, seed]


def state_digest(tree):
    import jax
    import numpy as np
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode() + a.tobytes())
    return h.hexdigest()[:16]


# The tiny deployment's state as the harness before deployment kinds
# built it (the template), and the inputs of studies 0 and 1 (seed 5).
PINNED_STATES = {
    "sweep.grid": ("b1559e825f12b613", "e50a051f2526cc1b",
                   "a9c590431635653d"),
    "fig89.single": ("cddfd0a3ce4fe0ff", "d0028b5c4309bc76",
                     "cc2121ba3d6af9a2"),
}
PINNED_CALLS = {"sweep.grid": ("run_grid", {"max_steps": 8192,
                                            "stats": True,
                                            "sharded": False}),
                "fig89.single": ("run", {"max_steps": 8192, "stats": True})}


@pytest.mark.parametrize("workload", sorted(PINNED_STATES))
def test_state_and_program_calls_pinned(tmp_path, monkeypatch, workload):
    """The same ``DatacenterState`` built, and the same program call with
    the same arguments, as before deployment kinds, with the program's
    loop counters asked for (``stats=True``: the same compiled program)."""
    import jax
    from repro.core import engine, sweep
    root, bench_dir = tiny_root(tmp_path)
    cell = spec.load_cell(root, workload, bench_dir)
    # the traffic's own budget, not the tiny cut's
    assert cell.traffic["max_steps"] == 8192
    mix = cell.kind.make_mix(cell.config, cell.traffic, 5)
    system = cell.kind.System(mix, jax.devices()[:1])
    got = (state_digest(system.template),
           state_digest(system.prepare(mix.study(0))),
           state_digest(system.prepare(mix.study(1))))
    assert got == PINNED_STATES[workload]
    calls = []
    for module, name in ((engine, "run"), (sweep, "run_grid")):
        monkeypatch.setattr(module, name, lambda *a, _n=name, **k:
                            calls.append((_n, k)))
    system.dispatch(system.prepare(mix.study(0)))
    assert calls == [PINNED_CALLS[workload]]


@pytest.mark.parametrize("workload", ["fig89.single", "sweep.grid"])
def test_waves_counters_are_the_run_stats(tmp_path, quiet_jax, workload):
    """The ``waves`` kind's ``counters`` of a study: ``iterations``, the
    loop trips of its longest lane, and ``events``, summed over its lanes,
    as ``engine.run(..., stats=True)`` and ``sweep.run_grid(...,
    stats=True)`` count them when called directly; a grid lane counts
    what a single run of its pair does."""
    import dataclasses
    import jax
    import numpy as np
    from repro.core import engine, sweep
    root, bench_dir = tiny_root(tmp_path)
    cell = spec.load_cell(root, workload, bench_dir)
    kind = cell.kind
    mix = kind.make_mix(cell.config, cell.traffic, 2**31 + 21)
    system = kind.System(mix, jax.devices()[:1])
    studies = [mix.study(i) for i in range(2)]
    kept, direct = [], []
    one = (system.template if mix.runner == "engine.run" else
           jax.tree.map(lambda x: x[0], system.template))
    for study in studies:
        dc, vm_p, task_p = system.prepare(study)
        kept.append(system.keep(system.dispatch((dc, vm_p, task_p))))
        if mix.runner == "engine.run":
            _, stats = engine.run(dc, max_steps=mix.max_steps, stats=True)
        else:
            _, stats = sweep.run_grid(dc, vm_p, task_p, sharded=False,
                                      max_steps=mix.max_steps, stats=True)
        stats = jax.device_get(stats)
        direct.append({"iterations": int(np.max(stats.iterations)),
                       "events": int(np.sum(stats.events))})
        single = [jax.device_get(engine.run(
            dataclasses.replace(one, vm_policy=np.int32(v),
                                task_policy=np.int32(t)),
            max_steps=mix.max_steps, stats=True)[1]) for v, t in study]
        assert direct[-1] == {
            "iterations": max(int(s.iterations) for s in single),
            "events": mix.replicates * sum(int(s.events) for s in single)}
    got = kind.counters(mix, studies, jax.device_get(kept))
    assert got == direct
    assert all(c["iterations"] >= 1 and c["events"] >= 1 for c in got)
