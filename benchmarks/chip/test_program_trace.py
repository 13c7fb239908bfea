"""The reduction of the program's own spans, pass scopes and loop
counters, as the metric readers read them (``chipbench.tracing``, the
readers in ``metrics/``), on hand-made traces, on a trace recorded on a
TPU v5e, and ``record_trace.py`` on the CPU at a small size."""
import argparse
import contextlib
import dataclasses
import json
import os
import signal
from types import SimpleNamespace as NS

import pytest

from chipbench import harness, spec, tracing

HERE = os.path.dirname(os.path.abspath(__file__))

# the program's readings, each read by its metric file
PROGRAM = ("runner_idle_ms", "summary_idle_ms", "provision_device_ms",
           "loop_iter_us")
# reader of each per-layer metric, found as the harness finds it
READ = {name: spec._reader(HERE, name)
        for name in ("study_device_ms", "device_idle_pct") + PROGRAM}
# the pass scopes of the ``waves`` deployment kind
SCOPES = spec._module(HERE, "deployments", "waves", "kind").SCOPES


def record_of(trace, trips=()):
    """A ``Record`` of ``trace``; ``trips[k]`` is study ``k``'s counter
    ``iterations``."""
    studies = [harness.StudyRun(k, 0.0, 1.0, 1, 0, {"iterations": n})
               for k, n in enumerate(trips)]
    return harness.Record(chips=len(trace.chips), setup_s=1.0, build_s=0.1,
                          compile_s=0.2, cache_hits=0, studies=studies,
                          memory_peak_bytes=0, trace=trace)


def readings(trace, trips=()):
    return {name: READ[name](record_of(trace, trips)) for name in PROGRAM}


def ev(name, start, dur, path=None):
    """A trace event; ``path`` is the name path its op's metadata holds."""
    return NS(name=name, start_ns=start, duration_ns=dur, path=path)


def planes(program: bool, scope: str = "provision"):
    """One chip, two studies of 100 ns.  Each study dispatches (10-20, the
    program busy 20-70), waits, and fetches (80-100, two eager summary
    programs of 5 ns).  With ``program`` the host spans of the program
    nest in the pieces, and the ops carry their pass scope (the first
    one's is ``scope``); a ``while`` container holds the loop's ops."""
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
                   path(loop + f"/cond/branch_1_fun/vmap({scope})/add")),
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


def extract_of(program: bool, scope: str = "provision") -> tracing.Trace:
    """The ``Trace`` of ``planes(program, scope)``, with the ops' name
    paths."""
    profile = planes(program, scope)
    paths = {p.name: {e.name: e.path for line in p.lines
                      for e in line.events if e.path is not None}
             for p in profile.planes}
    return tracing.extract(profile, [0], paths)


def test_program_spans_and_scopes_leave_the_harness_readings():
    """The program's spans and the ops' scope metadata change nothing the
    harness read of a trace before the program's readings."""
    old = tracing.extract(planes(program=False), [0])
    new = tracing.extract(planes(program=True), [0])
    assert new.studies() == old.studies() and new.spans == old.spans
    assert new.busy_s() == old.busy_s()
    assert new.study_busy_s() == old.study_busy_s()
    assert new.top_ops() == old.top_ops()
    assert new.idle_by_host() == old.idle_by_host()
    for name in ("study_device_ms", "device_idle_pct"):
        assert READ[name](record_of(new)) == READ[name](record_of(old))
    with_paths = extract_of(program=True)
    assert dataclasses.replace(with_paths, leaf_ns=[]) == \
        dataclasses.replace(new, leaf_ns=[])


def test_readings_need_the_program():
    """Without the program's spans, scopes and counters every reading is
    None; with them, each but ``loop_iter_us`` (which needs the counters)
    reads a value, and with the counters it does too."""
    bare = extract_of(program=False)
    assert bare.host == {}
    assert passes_ms(bare) == {"other": 60.0 * 1e-6}
    assert all(v is None for v in readings(bare).values())
    got = extract_of(program=True)
    assert sorted(got.host) == ["repro.grid", "repro.grid.flags",
                                "repro.grid.launch", "repro.summarize"]
    values = readings(got)
    assert values.pop("loop_iter_us") is None
    assert all(v is not None for v in values.values())
    assert all(v is not None for v in readings(got, [5, 10]).values())


def test_idle_inside_program_spans_goes_to_its_reading():
    """Idle time inside ``repro.grid`` (nested in ``dispatch``) and inside
    ``repro.summarize`` (nested in ``fetch``), per study, and never more
    than the idle time of the pieces around them."""
    tr = extract_of(program=True)
    got = readings(tr)
    # repro.grid spans 11-19, the device idle until 20: 8 ns a study
    assert got["runner_idle_ms"] == pytest.approx(8e-6)
    # repro.summarize 81-93 around programs at 84-89 and 90-95: 3 + 1 ns
    assert got["summary_idle_ms"] == pytest.approx(4e-6)
    gaps = dict(tr.idle_by_host())
    assert (got["runner_idle_ms"] + got["summary_idle_ms"]
            <= 1e3 * (gaps["dispatch"] + gaps["fetch"]) / 2)


def passes_ms(trace):
    import record_trace
    return record_trace.passes_ms(trace, SCOPES)


def test_pass_sums_skip_containers():
    """Scope sums count leaf operations only, so the passes of a study
    never exceed its device time; ops outside every pass are ``other``."""
    tr = extract_of(program=True)
    assert passes_ms(tr) == pytest.approx(
        {"provision": 30e-6, "commit": 10e-6, "freeze": 10e-6,
         "other": 10e-6})
    study_ms = READ["study_device_ms"](record_of(tr))
    assert sum(passes_ms(tr).values()) <= study_ms
    assert readings(tr)["provision_device_ms"] == pytest.approx(30e-6)
    assert readings(tr)["provision_device_ms"] <= study_ms
    assert tracing.opcode("%while.1 = (f32[8]{0:T(128)}, s32[]) while("
                          "(f32[8]{0}, s32[]) %t), condition=%c, "
                          "body=%b") == "while"
    assert tracing.opcode("%cond.2 = f32[8]{0:T(128)S(1)} conditional("
                          "pred[] %p, f32[8] %a)") == "conditional"
    assert tracing.opcode("%fusion.3 = f32[4]{0:T(128)S(1)} fusion("
                          "f32[4] %x), kind=kLoop, calls=%fc") == "fusion"


def test_scope_of_reads_the_innermost_pass():
    """A name path's scopes, out of their transforms: ``scope_ms`` counts
    an op under every scope of its path, ``record_trace.py``'s breakdown
    under the innermost the kind names."""
    scopes = tracing.scopes
    assert scopes("jit(_run)/while/body/provision/cond/add") == (
        "_run", "while", "body", "provision", "cond", "add")
    nested = ("jit(run_grid)/while/body/cond/branch_1_fun/vmap(leap)/"
              "while/body/vmap(probes)/mul")
    assert {"leap", "probes"} <= set(scopes(nested))
    assert scopes("") == ()
    studies = [("study", 0, 100)]
    tr = tracing.Trace(["/device:TPU:0"], [[(0, 90)]], [{}], studies, {},
                       [[{nested: 40.0, "jit(_run)/while/cond/lt": 50.0}]])
    assert tr.scope_ms("leap") == tr.scope_ms("probes") == \
        pytest.approx(40e-6)
    assert tr.scope_ms("provision") is None
    assert passes_ms(tr) == pytest.approx({"probes": 40e-6,
                                           "other": 50e-6})


def test_loop_iter_us_reads_the_program_counters():
    tr = extract_of(program=True)
    assert readings(tr, [5])["loop_iter_us"] is None     # one of two
    assert readings(tr, [5, 0])["loop_iter_us"] is None  # a study not counted
    # 60 ns of device time a study (50 + two 5 ns summary programs)
    assert readings(tr, [5, 10])["loop_iter_us"] == pytest.approx(
        1e6 * (60e-9 / 5 + 60e-9 / 10) / 2)


def stalled(stall: float) -> tracing.Trace:
    """Three studies of the shape of ``planes``; in the second the host
    stalls ``stall`` ns inside ``repro.summarize``, before the summary's
    programs run."""
    spans, busy, leaves = [], [], []
    host = {"repro.grid": [], "repro.summarize": []}
    t = 0.0
    for k in range(3):
        x = stall if k == 1 else 0.0
        spans += [("study", t, t + 100 + x), ("prepare", t, t + 10),
                  ("dispatch", t + 10, t + 20), ("wait", t + 20, t + 80),
                  ("fetch", t + 80, t + 100 + x)]
        host["repro.grid"].append((t + 11, t + 19))
        host["repro.summarize"].append((t + 81, t + 93 + x))
        busy += [(t + 20, t + 70), (t + 84 + x, t + 89 + x),
                 (t + 90 + x, t + 95 + x)]
        leaves.append({"jit(run_grid)/while/body/vmap(provision)/add": 30.0})
        t += 120 + x
    return tracing.Trace(["/device:TPU:0"], [busy], [{}], spans, host,
                         [leaves])


def test_a_stalled_study_moves_no_median():
    """One study in three stalls on the host for 100x its length: the
    program's readings, each a median over the traced studies, stay as
    they were; the idle share of the whole traced window does not."""
    plain, stall = stalled(0.0), stalled(10_000.0)
    trips = [5, 5, 5]
    assert readings(stall, trips) == pytest.approx(readings(plain, trips))
    assert readings(plain, trips) == pytest.approx(
        {"runner_idle_ms": 8e-6, "summary_idle_ms": 4e-6,
         "provision_device_ms": 30e-6, "loop_iter_us": 1e6 * 60e-9 / 5})
    idle = [READ["device_idle_pct"](record_of(tr)) for tr in (plain, stall)]
    assert idle[1] > idle[0] + 50


def test_a_new_scope_is_read_by_a_metric_file(tmp_path):
    """A scope no deployment kind runs today, read by a metric file of its
    own found by name, with nothing of the harness changed."""
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "admit_device_ms.py").write_text(
        "def read(record):\n"
        "    return record.trace.scope_ms('admit')\n")
    read = spec._reader(str(tmp_path), "admit_device_ms")
    assert read(record_of(extract_of(True, scope="admit"))) == \
        pytest.approx(30e-6)
    assert read(record_of(extract_of(True))) is None


# -- a trace recorded on a TPU v5e -------------------------------------------

RECORDED = os.path.join(HERE, "testdata", "fig89.single-300-hosts")
PROGRAM_SPANS = {"repro.run", "repro.run.flags", "repro.run.launch",
                 "repro.summarize"}


def recorded():
    """The recorded trace and its studies' loop trips."""
    with open(RECORDED + ".studies.json") as f:
        meta = json.load(f)
    trace = tracing.load_file(RECORDED + ".xplane.pb", meta["device_ids"])
    return trace, [s["iterations"] for s in meta["studies"]]


def test_recorded_tpu_trace_reads_the_program():
    """One ``fig89.single`` study of the paper's deployment cut to 300
    hosts, recorded on a TPU v5e by ``record_trace.py --hosts 300 --out``:
    the extract finds the program's spans and pass scopes, and every
    reading is a value within the readings it is part of."""
    tr, trips = recorded()
    assert tr.chips == ["/device:TPU:0"] and len(tr.studies()) == 1
    assert {n for n in tr.host if n.startswith("repro.")} == PROGRAM_SPANS
    passes = passes_ms(tr)
    assert {"provision", "rates", "commit", "leap"} <= set(passes)
    got = readings(tr, trips)
    assert all(v is not None and v > 0 for v in got.values()), got
    study_ms = READ["study_device_ms"](record_of(tr))
    assert got["provision_device_ms"] <= study_ms
    assert sum(passes.values()) <= study_ms
    gaps = dict(tr.idle_by_host())
    assert (got["runner_idle_ms"] + got["summary_idle_ms"]
            <= 1e3 * (gaps["dispatch"] + gaps["fetch"]))
    assert got["loop_iter_us"] == pytest.approx(1e3 * study_ms / trips[0])


def test_recorded_tpu_trace_readings_pinned():
    """The metric files read the recorded trace as ``record_trace.py``
    read it before the readings were metrics, to the last digits."""
    tr, trips = recorded()
    assert readings(tr, trips) == pytest.approx(
        {"runner_idle_ms": 4.53308, "summary_idle_ms": 5.865357,
         "provision_device_ms": 0.43467, "loop_iter_us": 290.70525},
        rel=1e-12)
    assert passes_ms(tr) == pytest.approx(
        {"commit": 1.468446, "leap": 1.078822, "rates": 1.014002,
         "provision": 0.43467, "other": 0.181892, "horizon": 0.010794},
        rel=1e-12)


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
    tr = tracing.load_file(str(tmp_path / "trace.xplane.pb"), [0])
    assert {n for n in tr.host if n.startswith("repro.")} == PROGRAM_SPANS
    assert set(PROGRAM) <= set(result["readings"])
    assert all(v is None for v in result["readings"].values())
