"""Record a profile of a cell's studies and read the program's own spans,
pass scopes and loop counters in it.

    python3 benchmarks/chip/record_trace.py --workload <cell> \
        [--hosts <n>] [--studies <k>] [--out <dir>]

Builds the cell's deployment (cut to ``--hosts`` hosts where given),
warms up, then runs ``--studies`` studies under the profiler, each in the
harness's ``study`` / ``prepare`` / ``dispatch`` / ``wait`` / ``fetch``
spans, and as many again with the profiler off.  It calls the deployment
kind as the harness does (``make_mix``, ``System``, ``keep`` and, once
the studies are done, ``counters``), and reads the profile with the
harness's own ``tracing.load`` and metric readers.  Prints one JSON line:
per study its counters, the reading of every per-layer metric of the
cell, the device ms of each pass scope the kind names (``SCOPES``) per
study, innermost scope first and ``other`` for the rest, the idle gaps by
host piece, and the mean study time traced and untraced.  With ``--out``
the profile lands in ``<out>/trace.xplane.pb``, beside ``studies.json``
(the traced studies and the chips' device ids); source files in its op
metadata are named relative to the checkout.  Needs a TPU, as ``run.py``
does (exit 3 without one).
"""
import argparse
import dataclasses
import json
import os
import re
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(REPO, "src")]

from chipbench import harness, spec, tracing  # noqa: E402

SEED = 2**31 + 101
OTHER = "other"


def run_studies(jax, kind, system, mix, first: int, count: int):
    """``count`` studies from index ``first``, as the harness runs them,
    each with its counters."""
    span = jax.profiler.TraceAnnotation
    runs, studies, kept = [], [], []
    for i in range(first, first + count):
        with span(tracing.SPAN_STUDY, index=i):
            s0 = time.perf_counter()
            with span("prepare"):
                study = mix.study(i)
                dc = system.prepare(study)
            with span("dispatch"):
                out = system.dispatch(dc)
            with span("wait"):
                jax.block_until_ready(out)
            with span("fetch"):
                cloudlets, short = system.summary(out)
            s1 = time.perf_counter()
        kept.append(system.keep(out))
        studies.append(study)
        runs.append(harness.StudyRun(i, s0, s1, cloudlets, short))
    counts = kind.counters(mix, studies, jax.device_get(kept))
    for r, c in zip(runs, counts, strict=True):
        r.counters = c
    return runs


def as_dict(run: harness.StudyRun) -> dict:
    """A study as ``studies.json`` holds it, its counters among its
    fields."""
    d = dataclasses.asdict(run)
    return {**d, **d.pop("counters")}


def passes_ms(trace: tracing.Trace, names) -> dict:
    """Device ms per traced study of each scope in ``names``, by the
    innermost of them in each leaf operation's name path (``other`` where
    none is), the busiest chip for each."""
    n = max(len(trace.studies()), 1)
    out = {}
    for chip in trace.leaf_ns:
        total = {}
        for study in chip:
            for path, ns in study.items():
                k = next((s for s in reversed(tracing.scopes(path))
                          if s in names), OTHER)
                total[k] = total.get(k, 0.0) + ns
        for k, ns in total.items():
            out[k] = max(out.get(k, 0.0), 1e-6 * ns / n)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def record(a, require_tpu: bool = True):
    """``(exit code, result)`` of the recording the arguments ``a``
    describe; ``(3, None)`` where JAX finds no TPU."""
    cell = spec.load_cell(REPO, a.workload)
    if a.hosts:
        cell.config["hosts"]["count"] = a.hosts
    import jax
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("[record] refusing to run: no TPU", file=sys.stderr)
        return 3, None
    kind = cell.kind
    mix = kind.make_mix(cell.config, cell.traffic, SEED)
    system = kind.System(mix, jax.devices()[:cell.chips])
    system.summary(system.dispatch(system.prepare(mix.warmup())))

    trace_dir = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        jax.profiler.start_trace(trace_dir)
        traced = run_studies(jax, kind, system, mix, 0, a.studies)
        jax.profiler.stop_trace()
        untraced = run_studies(jax, kind, system, mix, a.studies, a.studies)
        path = tracing.trace_file(trace_dir)
        device_ids = [d.id for d in system.devices]
        trace = tracing.load_file(path, device_ids)
        as_dicts = [as_dict(r) for r in traced]
        if a.out:
            os.makedirs(a.out, exist_ok=True)
            shutil.copy(path, os.path.join(a.out, "trace.xplane.pb"))
            with open(os.path.join(a.out, "studies.json"), "w") as f:
                json.dump({"device_ids": device_ids, "studies": as_dicts},
                          f, indent=1)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    rec = harness.Record(cell.chips, setup_s=None, build_s=None,
                         compile_s=None, cache_hits=0, studies=traced,
                         memory_peak_bytes=system.peak_bytes(), trace=trace)
    studies = trace.studies()
    mean_ms = lambda ss: 1e3 * sum(s.end - s.start for s in ss) / len(ss)
    result = {
        "workload": a.workload, "hosts": int(cell.config["hosts"]["count"]),
        "studies": as_dicts,
        "readings": {m.name: m.read(rec) for m in cell.per_layer},
        "passes_ms": passes_ms(trace, getattr(kind, "SCOPES", ())),
        "idle_ms_by_host": {k: 1e3 * v / len(studies)
                            for k, v in trace.idle_by_host()},
        "study_ms_traced": mean_ms(traced),
        "study_ms_untraced": mean_ms(untraced),
    }
    return int(any(s.lanes_short for s in traced + untraced)), result


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--hosts", type=int)
    ap.add_argument("--studies", type=int, default=1)
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    # source files in the trace's op metadata relative to the checkout; no
    # persistent compile cache, whose programs keep the paths they were
    # compiled with (both read when JAX is first imported, in ``record``)
    os.environ.setdefault("JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX",
                          "^" + re.escape(REPO + os.sep))
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    rc, result = record(a)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
