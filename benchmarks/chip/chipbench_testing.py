"""Helpers of the benchmark's own tests: a copy of the benchmark's files
with its deployments cut to a size the CPU runs in a second."""
import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(REPO, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def tiny_root(tmp_path):
    """(root, bench_dir): BENCHMARK.json and the benchmark's data files
    under ``tmp_path``, with every cell's configuration and traffic cut
    down by its deployment kind's ``tiny``."""
    from chipbench import spec
    root = str(tmp_path / "checkout")
    bench_dir = os.path.join(root, "benchmarks", "chip")
    for sub in ("configs", "traffic", "metrics", "deployments"):
        shutil.copytree(os.path.join(HERE, sub), os.path.join(bench_dir, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    bench = load(os.path.join(REPO, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        config = load(os.path.join(REPO, files[w["config"]]))
        traffic_file = os.path.join("traffic", f"{w['traffic']}.json")
        traffic = load(os.path.join(HERE, traffic_file))
        config, traffic = spec.deployment_kind(config).tiny(config, traffic)
        dump(os.path.join(root, files[w["config"]]), config)
        dump(os.path.join(bench_dir, traffic_file), traffic)
    return root, bench_dir


def load(path):
    with open(path) as f:
        return json.load(f)


def dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def run_cell(root, bench_dir, workload, *, seed=2**31 + 11, seconds=0.3,
             trace=0, require_tpu=False):
    """One run of ``workload`` on whatever JAX finds."""
    from chipbench import harness
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    return harness.run(args, time.perf_counter(), root=root,
                       bench_dir=bench_dir, require_tpu=require_tpu)
