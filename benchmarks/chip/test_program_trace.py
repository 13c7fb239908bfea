"""The reduction of the program's own spans, pass scopes and loop
counters (``chipbench.program_trace``), on hand-made traces, on a trace
recorded on a TPU v5e, and ``record_trace.py`` on the CPU at a small
size."""
import argparse
import contextlib
import json
import os
import signal
from types import SimpleNamespace as NS

import pytest

from chipbench import harness, program_trace, spec, tracing

HERE = os.path.dirname(os.path.abspath(__file__))

# reader of each per-layer metric the harness has, found as it finds it
READ = {name: spec._reader(HERE, name)
        for name in ("study_device_ms", "device_idle_pct")}


def record_of(trace):
    return harness.Record(chips=len(trace.chips), setup_s=1.0, build_s=0.1,
                          compile_s=0.2, cache_hits=0, studies=[],
                          memory_peak_bytes=0, trace=trace)


def ev(name, start, dur, path=None):
    """A trace event; ``path`` is the name path its op's metadata holds."""
    return NS(name=name, start_ns=start, duration_ns=dur, path=path)


def planes(program: bool):
    """One chip, two studies of 100 ns.  Each study dispatches (10-20, the
    program busy 20-70), waits, and fetches (80-100, two eager summary
    programs of 5 ns).  With ``program`` the host spans of the program
    nest in the pieces, and the ops carry their pass scope; a ``while``
    container holds the loop's ops."""
    line = lambda name, *events: NS(name=name, events=list(events))
    path = (lambda p: p) if program else (lambda p: None)
    host, modules, ops = [], [], []
    for t in (0, 120):
        host += [ev("study", t, 100), ev("prepare", t, 10),
                 ev("dispatch", t + 10, 10), ev("wait", t + 20, 60),
                 ev("fetch", t + 80, 20)]
        if program:
            host += [ev("repro.grid", t + 11, 8),
                     ev("repro.grid.flags", t + 11, 5),
                     ev("repro.grid.launch", t + 16, 3),
                     ev("repro.summarize", t + 81, 12)]
        modules += [ev("jit_run_grid", t + 20, 50),
                    ev("jit_reduce", t + 84, 5), ev("jit_reduce", t + 90, 5)]
        loop = "jit(run_grid)/while/body"
        ops += [ev("%while.1 = (f32[8]) while()", t + 20, 50, path(loop)),
                ev("%fusion.1 = f32[8] fusion(), calls=%fc.1", t + 20, 30,
                   path(loop + "/cond/branch_1_fun/vmap(provision)/add")),
                ev("%fusion.2 = f32[8] fusion(), calls=%fc.2", t + 50, 10,
                   path(loop + "/vmap(commit)/mul")),
                ev("%copy.3 = f32[8] copy()", t + 60, 10,
                   path(loop + "/freeze/select_n")),
                ev("%reduce.4 = f32[] reduce()", t + 84, 5,
                   path("jit(reduce)/reduce_sum")),
                ev("%reduce.4 = f32[] reduce()", t + 90, 5,
                   path("jit(reduce)/reduce_sum"))]
    chip = NS(name="/device:TPU:0", lines=[line("XLA Modules", *modules),
                                           line("XLA Ops", *ops)])
    return NS(planes=[NS(name="/host:CPU", lines=[line("python", *host)]),
                      chip])


def extract_of(program: bool) -> program_trace.ProgramTrace:
    """The ``ProgramTrace`` of ``planes(program)``, with the ops' name
    paths."""
    profile = planes(program)
    paths = {p.name: {e.name: e.path for line in p.lines
                      for e in line.events if e.path is not None}
             for p in profile.planes}
    return program_trace.extract(profile, [0], paths)


def test_program_spans_and_scopes_leave_the_harness_readings():
    """The program's spans and the ops' scope metadata change nothing the
    harness reads of a trace."""
    old = tracing.extract(planes(program=False), [0])
    new = tracing.extract(planes(program=True), [0])
    assert new.studies() == old.studies() and new.spans == old.spans
    assert new.busy_s() == old.busy_s()
    assert new.study_busy_s() == old.study_busy_s()
    assert new.top_ops() == old.top_ops()
    assert new.idle_by_host() == old.idle_by_host()
    for name, read in READ.items():
        assert read(record_of(new)) == read(record_of(old))
    assert extract_of(program=True).trace == new


def test_readings_need_the_program():
    """Without the program's spans, scopes and counters every reading is
    None; with them, each but ``loop_iter_us`` (which needs the counters)
    reads a value."""
    bare = extract_of(program=False)
    assert bare.spans == [] and bare.pass_ms() == {"other": 60.0 * 1e-6}
    assert all(v is None for v in bare.readings().values())
    got = extract_of(program=True)
    assert len(got.spans) == 8
    readings = got.readings()
    assert readings.pop("loop_iter_us") is None
    assert all(v is not None for v in readings.values())


def test_idle_inside_program_spans_goes_to_its_reading():
    """Idle time inside ``repro.grid`` (nested in ``dispatch``) and inside
    ``repro.summarize`` (nested in ``fetch``), per study, and never more
    than the idle time of the pieces around them."""
    pt = extract_of(program=True)
    got = pt.readings()
    # repro.grid spans 11-19, the device idle until 20: 8 ns a study
    assert got["runner_idle_ms"] == pytest.approx(8e-6)
    # repro.summarize 81-93 around programs at 84-89 and 90-95: 3 + 1 ns
    assert got["summary_idle_ms"] == pytest.approx(4e-6)
    gaps = dict(pt.trace.idle_by_host())
    assert (got["runner_idle_ms"] + got["summary_idle_ms"]
            <= 1e3 * (gaps["dispatch"] + gaps["fetch"]) / 2)


def test_pass_sums_skip_containers():
    """Scope sums count leaf operations only, so the passes of a study
    never exceed its device time; ops outside every pass are ``other``."""
    pt = extract_of(program=True)
    assert pt.pass_ns == [pytest.approx({"provision": 60.0, "commit": 20.0,
                                         "freeze": 20.0, "other": 20.0})]
    study_ms = READ["study_device_ms"](record_of(pt.trace))
    assert sum(pt.pass_ms().values()) <= study_ms
    assert pt.readings()["provision_device_ms"] == pytest.approx(30e-6)
    assert pt.readings()["provision_device_ms"] <= study_ms
    assert program_trace.opcode("%while.1 = (f32[8]{0:T(128)}, s32[]) while("
                                "(f32[8]{0}, s32[]) %t), condition=%c, "
                                "body=%b") == "while"
    assert program_trace.opcode("%cond.2 = f32[8]{0:T(128)S(1)} conditional("
                                "pred[] %p, f32[8] %a)") == "conditional"
    assert program_trace.opcode("%fusion.3 = f32[4]{0:T(128)S(1)} fusion("
                                "f32[4] %x), kind=kLoop, calls=%fc") \
        == "fusion"


def test_scope_of_reads_the_innermost_pass():
    scope_of = program_trace.scope_of
    assert scope_of("jit(_run)/while/body/provision/cond/add") == "provision"
    assert scope_of("jit(run_grid)/while/body/cond/branch_1_fun/vmap(leap)/"
                    "while/body/vmap(probes)/mul") == "probes"
    assert scope_of("jit(_run)/while/cond/lt") == program_trace.OTHER
    assert scope_of("") == program_trace.OTHER


def test_loop_iter_us_reads_the_program_counters():
    pt = extract_of(program=True)
    assert pt.readings([5])["loop_iter_us"] is None     # one of two studies
    assert pt.readings([5, 0])["loop_iter_us"] is None  # a study not counted
    # 60 ns of device time a study (50 + two 5 ns summary programs)
    assert pt.readings([5, 10])["loop_iter_us"] == pytest.approx(
        1e6 * (60e-9 / 5 + 60e-9 / 10) / 2)


# -- a trace recorded on a TPU v5e -------------------------------------------

RECORDED = os.path.join(HERE, "testdata", "fig89.single-300-hosts")


def test_recorded_tpu_trace_reads_the_program():
    """One ``fig89.single`` study of the paper's deployment cut to 300
    hosts, recorded on a TPU v5e by ``record_trace.py --hosts 300 --out``:
    the extract finds the program's spans and pass scopes, and every
    reading is a value within the readings it is part of."""
    with open(RECORDED + ".studies.json") as f:
        meta = json.load(f)
    pt = program_trace.load_file(RECORDED + ".xplane.pb", meta["device_ids"])
    tr = pt.trace
    assert tr.chips == ["/device:TPU:0"] and len(tr.studies()) == 1
    assert {n for n, _, _ in pt.spans} == {
        "repro.run", "repro.run.flags", "repro.run.launch", "repro.summarize"}
    passes = pt.pass_ms()
    assert {"provision", "rates", "commit", "leap"} <= set(passes)
    trips = [s["iterations"] for s in meta["studies"]]
    got = pt.readings(trips)
    assert all(v is not None and v > 0 for v in got.values()), got
    study_ms = READ["study_device_ms"](record_of(tr))
    assert got["provision_device_ms"] <= study_ms
    assert sum(passes.values()) <= study_ms
    gaps = dict(tr.idle_by_host())
    assert (got["runner_idle_ms"] + got["summary_idle_ms"]
            <= 1e3 * (gaps["dispatch"] + gaps["fetch"]))
    assert got["loop_iter_us"] == pytest.approx(1e3 * study_ms / trips[0])


@contextlib.contextmanager
def time_limit(seconds: int):
    """Fail the test once it has run ``seconds`` seconds."""
    def fail(signum, frame):
        raise TimeoutError(f"test ran over its {seconds} s limit")
    before = signal.signal(signal.SIGALRM, fail)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)


def test_record_trace_runs_on_the_cpu(tmp_path):
    """``record_trace.py`` end to end on the CPU, the paper's deployment
    cut to 64 hosts: every lane completes, the counters come back, and the
    saved profile holds the program's spans (a CPU trace has no TPU plane,
    so the device readings are None)."""
    import record_trace
    args = argparse.Namespace(workload="fig89.single", hosts=64, studies=1,
                              out=str(tmp_path))
    with time_limit(120):
        rc, result = record_trace.record(args, require_tpu=False)
    assert rc == 0, result
    study, = result["studies"]
    assert study["cloudlets"] == 500 and study["lanes_short"] == 0
    assert study["iterations"] >= 1 and study["events"] >= 1
    with open(tmp_path / "studies.json") as f:
        assert json.load(f)["studies"] == result["studies"]
    pt = program_trace.load_file(str(tmp_path / "trace.xplane.pb"), [0])
    assert {n for n, _, _ in pt.spans} == {
        "repro.run", "repro.run.flags", "repro.run.launch", "repro.summarize"}
    assert all(v is None for v in result["readings"].values())
