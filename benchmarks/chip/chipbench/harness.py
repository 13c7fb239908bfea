"""One run of one cell: set-up, a closed loop of studies, the check.

Set-up builds the cell's scenario through its deployment kind
(``deployments/<kind>.py``, found by ``chipbench.spec``), compiles (or
fetches from the persistent cache) by running one warm-up study of the
cell's own shapes, and ends when the first timed study starts.  The
window is a closed loop: the next study starts once the previous study's
summary is on the host, as a researcher's script or sweep loop runs them, and
the window ends with the first study that ends ``--seconds`` after the
window began.  With ``--trace 1`` the profiler records the studies that
start in the window's first ``TRACE_SECONDS``, and the run reports the
per-layer metrics; with ``--trace 0`` it reports the end-to-end ones.
Once the window has closed, the kind compares the studies' outputs with
its plain reference, ``chipbench.check`` holds each number to its limit,
and the kind reads each study's counters from the same outputs.  The
metric readers then read the ``Record``: the host clock's readings, each
study's counters and, in a traced run, the ``tracing.Trace`` of the one
profile, read before its directory is removed.

The last line on standard output is one JSON object; the last lines on
standard error are the compared numbers beside their limits.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from chipbench import check, spec, tracing
from chipbench.clock import CompileClock

ROOT = os.path.dirname(os.path.dirname(spec.BENCH_DIR))
TRACE_SECONDS = 2.0


@dataclasses.dataclass
class StudyRun:
    index: int
    start: float                # host clock, s
    end: float
    cloudlets: int              # completed, summed over the study's lanes
    lanes_short: int
    # the program's counters of the study, named by the deployment kind
    # (``counters``), read from its kept outputs after the window
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Record:
    """What the metric readers (``metrics/<name>.py``) read of a run."""
    chips: int
    setup_s: float
    build_s: float
    compile_s: float
    cache_hits: int
    studies: List[StudyRun]
    memory_peak_bytes: int
    trace: Optional[tracing.Trace] = None

    @property
    def window_s(self) -> float:
        return self.studies[-1].end - self.studies[0].start


class NoChip(RuntimeError):
    pass


def _args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _log(msg: str):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def run(args, t_start: float, *, root: str = ROOT,
        bench_dir: str = spec.BENCH_DIR, require_tpu: bool = True) -> dict:
    """One run; returns the result object.  Raises ``NoChip`` where JAX
    finds no TPU, or fewer chips than the cell asks for."""
    cell = spec.load_cell(root, args.workload, bench_dir)
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, "
                     f"JAX found {len(devices)}")
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro import compat
    _log(f"compile cache: {compat.use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock(jax)
    try:
        return _run_cell(args, t_start, cell, devices, clock)
    finally:
        clock.close()


def _run_cell(args, t_start, cell, devices, clock) -> dict:
    import jax

    kind, used = cell.kind, devices[:cell.chips]
    seed = args.seed % 2**64
    t0 = time.perf_counter()
    mix = kind.make_mix(cell.config, cell.traffic, seed)
    system = kind.System(mix, used)
    build_s = time.perf_counter() - t0
    warm = system.dispatch(system.prepare(mix.warmup()))
    system.summary(warm)
    del warm
    compile_s, n_spans = clock.seconds(), len(clock.spans)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    studies = []
    runs: List[StudyRun] = []
    kept = []
    setup_s = time.perf_counter() - t_start
    tracing_on, traced = bool(trace_dir), 0
    if tracing_on:
        jax.profiler.start_trace(trace_dir)
    w0 = time.perf_counter()
    span = jax.profiler.TraceAnnotation
    i = 0
    while True:
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
        del out, dc
        studies.append(study)
        runs.append(StudyRun(i, s0, s1, cloudlets, short))
        i += 1
        if tracing_on and s1 - w0 >= min(TRACE_SECONDS, args.seconds):
            jax.profiler.stop_trace()
            tracing_on, traced = False, i
        if s1 - w0 >= args.seconds:
            break
    compiles_in_window = len(clock.spans) - n_spans
    peak = system.peak_bytes()
    device_ids = [d.id for d in used]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}

    # -- correct: the window's outputs against the plain reference, once
    # they are on the host and the program's device state is freed --------
    t_check = time.perf_counter()
    outputs = jax.device_get(kept)
    del kept, system
    readings = kind.readings(mix, studies, outputs)
    readings["lanes_short"] = sum(r.lanes_short for r in runs)
    for r, counters in zip(runs, kind.counters(mix, studies, outputs),
                           strict=True):
        r.counters = counters
    n_checked = sum(mix.lanes(s) for s in studies)
    correct, table = check.verdict(readings, cell.config["checks"])
    correct = correct and n_checked > 0
    ms = sorted(1e3 * (r.end - r.start) for r in runs)
    _log(f"studies={len(runs)} window_s={runs[-1].end - runs[0].start:.3f} "
         f"study_ms_median={ms[len(ms) // 2]:.3f} "
         f"study_ms_p95={ms[min(len(ms) - 1, int(0.95 * len(ms)))]:.3f} "
         f"setup_s={setup_s:.3f} build_s={build_s:.3f} "
         f"compile_s={compile_s:.3f} cache_hits={clock.hits} "
         f"compiles_in_window={compiles_in_window} lanes_checked="
         f"{n_checked} check_s={time.perf_counter() - t_check:.3f}")

    record = Record(cell.chips, setup_s, build_s, compile_s, clock.hits,
                    runs, peak)
    result = {"correct": bool(correct), "attempted": len(runs),
              "failed": sum(r.lanes_short > 0 for r in runs)}
    if args.trace:
        record.trace = tracing.load(trace_dir, device_ids)
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = record.trace.busy_s()
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = record.trace.window_s()
        _log(f"traced studies={traced} chips={record.trace.chips}")
        metrics = cell.per_layer
    else:
        metrics = cell.end_to_end
    values = {}
    for m in metrics:
        v = m.read(record)
        if v is not None:
            values[m.name] = {"value": float(v), "unit": m.unit}
    result.update(metrics=values, device=device)
    if args.trace:
        result["breakdown"] = {"device_ops": record.trace.top_ops(),
                               "idle_gaps": record.trace.idle_by_host()}
    result["checks"] = table
    return result


def main(argv, t_start: float) -> int:
    args = _args(argv)
    try:
        result = run(args, t_start)
    except NoChip as e:
        print(f"[chipbench] refusing to run: {e}", file=sys.stderr)
        return 3
    for name, row in result["checks"].items():
        print(f"[check] {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
