"""Helpers of the benchmark's own tests: a copy of the benchmark's files
with its deployments cut to a size the CPU runs in a second."""
import argparse
import glob
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

# (hosts, vms, waves) of each deployment, and the replicates of each grid
# mix, cut down
TINY = {"paper_fig89": (32, 4, 3)}
TINY_REPLICATES = 2


def tiny_root(tmp_path):
    """(root, bench_dir): BENCHMARK.json and the benchmark's data files
    under ``tmp_path``, with every deployment and grid cut down."""
    root = str(tmp_path / "checkout")
    bench_dir = os.path.join(root, "benchmarks", "chip")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(HERE, sub), os.path.join(bench_dir, sub))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for name, (hosts, vms, waves) in TINY.items():
        path = os.path.join(bench_dir, "configs", f"{name}.json")
        config = load(path)
        config["hosts"]["count"], config["vms"]["count"] = hosts, vms
        config["cloudlets"]["waves"] = waves
        dump(path, config)
    for path in glob.glob(os.path.join(bench_dir, "traffic", "*.json")):
        traffic = load(path)
        if "replicates" in traffic:
            traffic["replicates"] = TINY_REPLICATES
            dump(path, traffic)
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
