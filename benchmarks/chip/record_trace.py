"""Record a profile of a cell's studies and read the program's own spans,
pass scopes and loop counters in it.

    python3 benchmarks/chip/record_trace.py --workload <cell> \
        [--hosts <n>] [--studies <k>] [--out <dir>]

Builds the cell's deployment (cut to ``--hosts`` hosts where given),
warms up, then runs ``--studies`` studies under the profiler, each in the
harness's ``study`` / ``prepare`` / ``dispatch`` / ``wait`` / ``fetch``
spans and with the program's loop counters on (``stats=True``), and as
many again with the profiler off.  Prints one JSON line: per study the
loop trips and events, the readings of ``chipbench.program_trace``, the
device ms of each pass per study, the idle gaps by host piece, and the
mean study time traced and untraced.  With ``--out`` the profile lands in
``<out>/trace.xplane.pb``, beside ``studies.json`` (the traced studies
and the chips' device ids); source files in its op metadata are named
relative to the checkout.  Needs a TPU, as ``run.py`` does (exit 3
without one).
"""
import argparse
import dataclasses
import glob
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

from chipbench import program_trace, spec, tracing  # noqa: E402

SEED = 2**31 + 101


@dataclasses.dataclass
class Study:
    index: int
    start: float                # host clock, s
    end: float
    cloudlets: int              # completed, summed over the study's lanes
    lanes_short: int
    iterations: int             # loop trips, the most of any lane
    events: int                 # events retired, summed over lanes


def dispatch_counted(system, inputs):
    """``System.dispatch`` of the ``waves`` deployment kind with the
    program's loop counters on: ``(final state, RunStats)``."""
    dc, vm_p, task_p = inputs
    steps = system.mix.max_steps
    if system.mix.runner == "engine.run":
        return system.engine.run(dc, max_steps=steps, stats=True)
    where = {"sharded": False} if system.mesh is None else \
        {"mesh": system.mesh}
    return system.sweep.run_grid(dc, vm_p, task_p, max_steps=steps,
                                 stats=True, **where)


def run_studies(jax, system, mix, first: int, count: int):
    """``count`` studies from index ``first``, as the harness runs them."""
    import numpy as np
    span = jax.profiler.TraceAnnotation
    out = []
    for i in range(first, first + count):
        with span(tracing.SPAN_STUDY, index=i):
            s0 = time.perf_counter()
            with span("prepare"):
                dc = system.prepare(mix.study(i))
            with span("dispatch"):
                final, stats = dispatch_counted(system, dc)
            with span("wait"):
                jax.block_until_ready(final)
            with span("fetch"):
                cloudlets, short = system.summary(final)
            s1 = time.perf_counter()
        stats = jax.device_get(stats)
        out.append(Study(i, s0, s1, cloudlets, short,
                         int(np.max(stats.iterations)),
                         int(np.sum(stats.events))))
    return out


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
    mix = cell.kind.make_mix(cell.config, cell.traffic, SEED)
    system = cell.kind.System(mix, jax.devices()[:cell.chips])
    warm, _ = dispatch_counted(system, system.prepare(mix.warmup()))
    system.summary(warm)
    del warm

    trace_dir = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        jax.profiler.start_trace(trace_dir)
        traced = run_studies(jax, system, mix, 0, a.studies)
        jax.profiler.stop_trace()
        untraced = run_studies(jax, system, mix, a.studies, a.studies)
        path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        device_ids = [d.id for d in system.devices]
        pt = program_trace.load_file(path, device_ids)
        if a.out:
            os.makedirs(a.out, exist_ok=True)
            shutil.copy(path, os.path.join(a.out, "trace.xplane.pb"))
            with open(os.path.join(a.out, "studies.json"), "w") as f:
                json.dump({"device_ids": device_ids,
                           "studies": [dataclasses.asdict(s)
                                       for s in traced]}, f, indent=1)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    per_study = pt.trace.study_busy_s()
    mean_ms = lambda ss: 1e3 * sum(s.end - s.start for s in ss) / len(ss)
    result = {
        "workload": a.workload, "hosts": int(cell.config["hosts"]["count"]),
        "studies": [dataclasses.asdict(s) for s in traced],
        "readings": pt.readings([s.iterations for s in traced]),
        "study_device_ms": (1e3 * sum(map(max, per_study)) / len(per_study)
                            if pt.trace.chips else None),
        "passes_ms": dict(sorted(pt.pass_ms().items(),
                                 key=lambda kv: -kv[1])),
        "idle_ms_by_host": {k: 1e3 * v / len(per_study)
                            for k, v in pt.trace.idle_by_host()},
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
