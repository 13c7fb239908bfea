"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration, whose ``file``
``BENCHMARK.json`` gives, and a traffic mix, read from
``traffic/<traffic>.json`` beside this package.  The configuration names
its deployment kind (``"generator"``, ``waves`` where it names none),
whose module ``deployments/<kind>.py`` holds everything that belongs to
that kind of deployment.  Every metric, end to end or per layer, is read
by ``metrics/<name>.py``, a module with ``read(record) -> float | None``
(``harness.Record``: the host clock's readings, each study's counters
and, in a traced run, the ``tracing.Trace``); a reader names the spans,
scopes and counters it reads.
Adding a cell, a mix, a deployment kind or a metric adds files and
entries; no file here changes.

A deployment kind's module provides what the harness calls:

``make_mix(config, traffic, seed)``
    the cell's studies: ``warmup()`` (a study of the timed shapes, not
    timed), ``study(i)``, ``lanes(study)`` (simulations a study runs) and
    ``lane(key)`` (the plain inputs of one simulation, for the reference).
``System(mix, devices)``
    the system under test on ``devices``, entered through the program's
    public API: ``prepare(study)``, ``dispatch(inputs)`` (which entry of
    the program it calls is the kind's choice), ``summary(out)`` (fetched
    to the host: cloudlets completed and lanes that fell short),
    ``keep(out)`` (the leaves the comparison and ``counters`` read) and
    ``peak_bytes()``.
``reference(lane, precision)``
    the plain reference of one lane; it imports nothing of the program,
    and ``precision="bfloat16"`` makes the control.
``readings(mix, studies, outputs)``
    the compared numbers, a dict with the same names as the
    configuration's ``checks`` but ``lanes_short``, which the harness adds.
``counters(mix, studies, outputs)``
    one dict of named integers per study, the program's own counts of its
    work (loop trips, events, admissions, ...), read on the host from the
    kept outputs after the window, as ``readings`` is; the harness hangs
    each on its study (``StudyRun.counters``) for the metric readers.
``reference_outputs(mix, studies, precision)``
    the studies' kept outputs as the reference in ``precision`` makes
    them, for the control.
``tiny(config, traffic)``
    the configuration and traffic cut to a size the CPU runs in a second,
    for the benchmark's own tests.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Callable, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_KIND = "waves"


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[object], Optional[float]]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    kind: ModuleType            # deployments/<kind>.py
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(bench_dir: str, sub: str, name: str, what: str) -> ModuleType:
    """The module ``<bench_dir>/<sub>/<name>.py``."""
    path = os.path.join(bench_dir, sub, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {what} {name!r}: {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module     # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def _reader(bench_dir: str, name: str) -> Callable:
    return _module(bench_dir, "metrics", name, "reader for metric").read


def deployment_kind(config: dict, bench_dir: str = BENCH_DIR) -> ModuleType:
    """The module of the configuration's deployment kind."""
    return _module(bench_dir, "deployments",
                   config.get("generator", DEFAULT_KIND), "deployment kind")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    as_metric = lambda m: Metric(m["name"], m["unit"],
                                 _reader(bench_dir, m["name"]))
    return Cell(name, int(w["chips"]), config, traffic,
                deployment_kind(config, bench_dir),
                [as_metric(m) for m in e2e], [as_metric(m) for m in layer])

