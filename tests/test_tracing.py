"""Program tracing: the pass scopes the runners compile in, the loop
counters they return (``RunStats``), and the host spans they write on
the profiler's clock (docs/observability.md, "Program tracing").

Every test runs at a small size on the CPU under its own time limit.
"""
import contextlib
import dataclasses
import glob
import re
import signal
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_conformance import (POLICY_GRID, make_dynamic_scenario,
                              make_networked_scenario, make_scenario)

from repro import compat
from repro.core import engine, sweep
from repro.core.state import INF

PASSES = {"events", "autoscaler", "provision", "phases", "rates",
          "migration", "flows", "horizon", "commit", "probes", "leap",
          "record", "freeze"}
ALWAYS = {"provision", "rates", "horizon", "commit", "record"}


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


def _assert_trees_bitwise(a, b, ctx):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), ctx
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=ctx)


def scopes_in(lowered) -> set:
    """The pass scopes named in a lowered program's op locations (a
    scope under a transform reads ``vmap(provision)``)."""
    text = lowered.as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*)"', text)
    parts = {part for name in names for part in name.split("/")}
    return {re.sub(r"^(\w+\()+|\)+$", "", p) for p in parts} & PASSES


def expected(*, dynamic, networked, elastic, probed, leap=True):
    want = set(ALWAYS)
    want |= {"events", "migration"} if dynamic else set()
    want |= {"phases", "flows"} if networked else set()
    want |= {"autoscaler"} if elastic else set()
    want |= {"probes"} if probed else set()
    want |= {"leap"} if leap else set()
    return want


FLAGS = {
    "static": dict(dynamic=False, networked=False, elastic=False,
                   probed=False),
    "every_pass": dict(dynamic=True, networked=True, elastic=True,
                       probed=True),
}


@pytest.mark.parametrize("flags", sorted(FLAGS))
def test_run_program_names_each_compiled_pass_scope(flags):
    """``engine._run`` lowered with debug info names exactly the pass
    scopes its static flags compile in."""
    with time_limit(180):
        f = FLAGS[flags]
        dc = make_dynamic_scenario(0, *POLICY_GRID[1])  # has an event table
        lowered = engine._run.lower(dc, max_steps=64, horizon=float("inf"),
                                    provision_policy=0, leap=True, **f)
        assert scopes_in(lowered) == expected(**f)


@pytest.mark.parametrize("flags", sorted(FLAGS))
def test_batched_run_names_each_compiled_pass_scope(flags):
    """``engine.batched_run`` names its step variants' pass scopes and
    the ``freeze`` select."""
    with time_limit(240):
        f = FLAGS[flags]
        batch = sweep.stack_scenarios(
            [make_dynamic_scenario(s, *POLICY_GRID[s]) for s in (0, 1)])
        lowered = engine.batched_run.lower(batch, max_steps=64, **f)
        assert scopes_in(lowered) == expected(**f) | {"freeze"}


@partial(jax.jit, static_argnames=("max_steps", "dynamic", "networked"))
def _loop_without_counters(dc, *, max_steps, dynamic, networked):
    """``engine._run``'s loop as it was before it counted: the reference
    that ``run(stats=False)`` must reproduce bit for bit."""
    horizon = jnp.minimum(jnp.asarray(float("inf"), jnp.float32), INF)

    def cond(carry):
        dc, n, alive = carry
        return alive & (n < max_steps) & (dc.time < horizon)

    def body(carry):
        dc, n, _ = carry
        new, rec = engine.step(dc, dynamic=dynamic, networked=networked,
                               leap=True,
                               leap_budget=jnp.int32(max_steps) - n - 1,
                               leap_horizon=horizon)
        return new, n + rec.n_events, rec.active

    out, _, _ = jax.lax.while_loop(cond, body, (dc, jnp.int32(0),
                                                jnp.bool_(True)))
    return out


SCENARIOS = {
    "static": (make_scenario, False, False),
    "dynamic": (make_dynamic_scenario, True, False),
    "networked": (make_networked_scenario, True, True),
}


@pytest.mark.parametrize("kind", sorted(SCENARIOS))
def test_stats_off_returns_the_uncounted_run_bitwise(kind):
    """``run(stats=False)`` is the final state alone, bitwise equal to
    the loop without counters and to ``run(stats=True)``'s state; the
    event counter equals the active steps of a leap-off trace."""
    with time_limit(300):
        make, dynamic, networked = SCENARIOS[kind]
        for seed, (vp, tp) in zip((3, 4), POLICY_GRID[1:3]):
            dc = make(seed, vp, tp)
            kw = dict(max_steps=2048, dynamic=dynamic, networked=networked)
            plain = engine.run(dc, **kw)
            assert isinstance(plain, type(dc))
            ref = _loop_without_counters(dc, **kw)
            _assert_trees_bitwise(ref, plain, f"{kind} seed {seed}")
            final, stats = engine.run(dc, stats=True, **kw)
            _assert_trees_bitwise(plain, final, f"{kind} seed {seed}")
            assert stats.iterations.shape == () == stats.events.shape
            _, rec = engine.run_trace(dc, num_steps=512, dynamic=dynamic,
                                      networked=networked)
            assert int(stats.events) == int(np.sum(rec.active)) > 0
            assert 1 <= int(stats.iterations) <= int(stats.events) + 1
            _, off = engine.run(dc, stats=True, leap=False, **kw)
            # leap off: one event a trip, and one last trip to find
            # quiescence
            assert int(off.events) == int(stats.events)
            assert int(off.iterations) == int(off.events) + 1


PARTITIONERS = ("fused", "gspmd", "shard_map", "dispatch")


@pytest.mark.parametrize("partitioner", PARTITIONERS)
def test_grid_lane_counters_equal_single_runs(partitioner):
    """``run_grid(stats=True)`` counters are [P, B], and each lane's
    equal those of the matching single ``engine.run``; every path
    (fused, and the one-device mesh's three partitioners) agrees."""
    with time_limit(300):
        scs = [make_scenario(s, *POLICY_GRID[0], per_vm=1 + s)
               for s in (0, 1, 2)]
        batch = sweep.stack_scenarios(scs)
        vm_p, task_p = sweep.policy_grid()
        kw = (dict(sharded=False) if partitioner == "fused" else
              dict(mesh=compat.make_mesh("sweep"), partitioner=partitioner))
        final, stats = sweep.run_grid(batch, vm_p, task_p, max_steps=1024,
                                      stats=True, **kw)
        assert stats.iterations.shape == stats.events.shape == (4, 3)
        plain = sweep.run_grid(batch, vm_p, task_p, max_steps=1024, **kw)
        _assert_trees_bitwise(final, plain, partitioner)
        for p in range(4):
            for b in range(3):
                lane = sweep.pad_scenario(
                    dataclasses.replace(scs[b], vm_policy=vm_p[p],
                                        task_policy=task_p[p]),
                    n_hosts=batch.hosts.num_pes.shape[1],
                    n_vms=batch.vms.req_pes.shape[1],
                    n_cloudlets=batch.cloudlets.vm.shape[1],
                    n_events=batch.events.shape[1],
                    n_spot=batch.scaler.spot_t.shape[1])
                _, one = engine.run(lane, max_steps=1024, stats=True)
                got = (int(stats.iterations[p, b]), int(stats.events[p, b]))
                assert got == (int(one.iterations), int(one.events)), (p, b)
                assert got[0] >= 1 and got[1] >= 1


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith("repro.")]
    return spans


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_trace_holds_the_runner_spans(tmp_path):
    """A CPU profiler trace of one ``engine.run`` + ``summarize_batch``
    and one ``run_grid`` + ``summarize_batch`` holds the program's host
    spans, the flags and launch pieces nested in their runner's span and
    in that order, the summary outside it."""
    with time_limit(240):
        dc = make_scenario(5, *POLICY_GRID[3])
        batch = sweep.stack_scenarios([dc, make_scenario(6, *POLICY_GRID[0])])
        vm_p, task_p = sweep.policy_grid()

        def studies():
            single = engine.run(dc, max_steps=512)
            jax.block_until_ready(sweep.summarize_batch(single))
            grid = sweep.run_grid(batch, vm_p, task_p, max_steps=512,
                                  sharded=False)
            jax.block_until_ready(sweep.summarize_batch(grid))

        studies()                              # compile outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            studies()
        finally:
            jax.profiler.stop_trace()
        spans = _host_spans(tmp_path)
        names = [n for n, _, _ in spans]
        for runner in ("repro.run", "repro.grid"):
            assert names.count(runner) == 1, names
            outer, = [s for s in spans if s[0] == runner]
            flags, = [s for s in spans if s[0] == runner + ".flags"]
            launch, = [s for s in spans if s[0] == runner + ".launch"]
            assert _inside(flags, outer) and _inside(launch, outer)
            assert flags[2] <= launch[1]
        summaries = [s for s in spans if s[0] == "repro.summarize"]
        assert len(summaries) == 2
        run_span, = [s for s in spans if s[0] == "repro.run"]
        assert all(s[1] >= run_span[2] for s in summaries)
