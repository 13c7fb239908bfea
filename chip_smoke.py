"""Bring-up smoke: the simulator's main path on one TPU chip.

    python chip_smoke.py                # one chip, every phase below
    python chip_smoke.py --four-chips   # only the sharded grid, 4 chips

Phases (one chip):

  kernel  the Pallas ``simstep`` kernel compiled for the chip at
          [4096, 128] vs its pure-jnp reference;
  fig8_9  paper §5 Figures 8/9 at full scale through ``engine.run``:
          10,000 hosts, 50 one-PE VMs, 10 waves of 50 cloudlets of
          1.2M MI every 600 s, space- and time-shared, vs the f64 oracle;
  grid    the fused 2x2 policy grid x 256 seeded heavy-tailed scenarios
          (1,024 lanes of 1,000 hosts x 2 PEs and 64 VMs) through
          ``sweep.run_grid``, sampled lanes vs the f64 oracle;
  stream  100,000 arrivals through ``engine.run_stream`` (W=64, chunk
          4096) vs the f64 streaming oracle.

``--four-chips`` runs the same grid on a four-chip mesh under each
partitioner spelling (``auto``, ``dispatch``, ``gspmd``) and compares each,
lane by lane, with the grid on one chip; a spelling that outlives its time
limit ends the process.

Compile and run seconds printed per phase are one smoke run, not a
benchmark.  Any failed check exits non-zero before the last line; on
success the last line is ``{"ok": true, "device": {...}}``.  The script
refuses to run anywhere but a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
RTOL = 1e-3                      # engine (f32) vs f64 oracle, conformance
GRID_LANE_WAVES = [1, 1, 2, 2, 3, 3, 4, 8]   # heavy tail, bench_sharded's
SPELLING_LIMIT_S = 600


class CheckFailed(Exception):
    pass


def check(cond, what):
    if not cond:
        raise CheckFailed(what)


def rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching a
    compiled program from the persistent cache), read from JAX's own
    monitoring spans, plus the count of persistent-cache hits.  Spans
    nest (an outer jit's trace contains its inner jits' traces), so the
    clock counts the union of their intervals."""

    def __init__(self, jax):
        self.spans = []
        self.hits = 0

        def on_span(name, start, end, **_):
            if name.startswith("/jax/core/compile/"):
                self.spans.append((start, end))

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_time_span_listener(on_span)
        jax.monitoring.register_event_listener(on_event)

    def seconds(self, first=0):
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans[first:]):
            total += max(0.0, end - max(start, reach))
            reach = max(reach, end)
        return total

    def timed(self, label, fn):
        """Call ``fn`` once, wait for its result, print compile vs run."""
        import jax
        n0, h0 = len(self.spans), self.hits
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        comp = self.seconds(n0)
        print(f"[time] {label}: compile_s={comp:.3f} run_s={wall - comp:.3f}"
              f" cache_hits={self.hits - h0} (one smoke run)", flush=True)
        return out


# ---------------------------------------------------------------------------
# Scenarios
# ---------------------------------------------------------------------------
def fig89_scenario(task_policy, n_hosts=10_000, n_vms=50, waves=10):
    from repro.core import broker as B, state as S
    hosts = S.make_uniform_hosts(n_hosts)
    vms = B.build_fleet([B.VmSpec(count=n_vms, pes=1, mips=1000.0,
                                  ram=512.0, bw=10.0, size=1000.0)])
    cl = B.build_waves(n_vms, B.WaveSpec(waves=waves, length_mi=1_200_000.0,
                                         period=600.0))
    return S.make_datacenter(hosts, vms, cl, vm_policy=S.SPACE_SHARED,
                             task_policy=task_policy, reserve_pes=True)


def grid_scenario(seed, n_hosts=1000, n_vms=64):
    from benchmarks.bench_policies import _stagger
    from repro.core import broker as B, state as S
    rng = np.random.default_rng(seed)
    hosts = S.make_uniform_hosts(n_hosts, pes=2, ram=2048.0,
                                 idle_w=100.0, peak_w=250.0)
    vms = B.build_fleet([B.VmSpec(count=n_vms, pes=1, mips=1000.0,
                                  ram=512.0, bw=10.0, size=1000.0)])
    cl = _stagger(B.build_waves(n_vms, B.WaveSpec(
        waves=GRID_LANE_WAVES[seed % len(GRID_LANE_WAVES)],
        length_mi=600_000.0, period=300.0)), rng)
    return S.make_datacenter(hosts, vms, cl, reserve_pes=True)


def grid_batch(n_scen, **kw):
    from repro.core import sweep
    return sweep.stack_scenarios([grid_scenario(s, **kw)
                                  for s in range(n_scen)])


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def phase_kernel(clock, v=4096, k=128):
    import jax.numpy as jnp
    from repro.kernels.simstep import simstep, simstep_ref
    rng = np.random.default_rng(0)
    rem = rng.uniform(0.0, 5000.0, (v, k)).astype(np.float32)
    rem[rng.uniform(size=(v, k)) < 0.15] = 0.0
    args = (jnp.asarray(rem), jnp.asarray(rng.uniform(size=(v, k)) < 0.7),
            jnp.asarray(rng.uniform(100.0, 2000.0, v).astype(np.float32)),
            jnp.asarray(rng.integers(1, 4, v).astype(np.float32)))
    for policy in (0, 1):
        r, d = clock.timed(f"kernel[policy={policy}]",
                           lambda: simstep(*args, policy))
        r_ref, d_ref = simstep_ref(*args, policy)
        err_r = rel_err(np.asarray(r)[np.asarray(r_ref) > 0],
                        np.asarray(r_ref)[np.asarray(r_ref) > 0])
        err_d = rel_err(d, d_ref)
        print(f"[kernel] policy={policy} [{v},{k}] max_rel_err rates="
              f"{err_r:.3g} dtmin={err_d:.3g}")
        check(np.array_equal(np.asarray(r) > 0, np.asarray(r_ref) > 0),
              "kernel: runnable set differs from the reference")
        check(err_r <= 1e-6 and err_d <= 1e-6,
              "kernel: rates/dtmin differ from the reference beyond 1e-6")


def phase_fig89(clock, n_hosts=10_000, n_vms=50, waves=10):
    from repro.core import engine, state as S
    from repro.oracle import simulate_dense
    n_cl = n_vms * waves
    for name, pol in (("space", S.SPACE_SHARED), ("time", S.TIME_SHARED)):
        dc = fig89_scenario(pol, n_hosts, n_vms, waves)
        out = clock.timed(f"fig8_9[{name}] engine.run",
                          lambda: engine.run(dc, max_steps=8192))
        _, trace = clock.timed(
            f"fig8_9[{name}] engine.run_trace",
            lambda: engine.run_trace(dc, num_steps=2 * n_cl + 64))
        t0 = time.perf_counter()
        res = simulate_dense(dc)
        print(f"[fig8_9] {name}: oracle host_s="
              f"{time.perf_counter() - t0:.3f}")
        state = np.asarray(out.cloudlets.state)
        ft = np.asarray(out.cloudlets.finish_time, np.float64)
        st = np.asarray(out.cloudlets.start_time, np.float64)
        n_done = int((state == S.CL_DONE).sum())
        n_events = int(np.asarray(trace.active).sum())
        exec_s = ft - st
        err = rel_err(ft, res.finish_time)
        print(f"[fig8_9] {name}: done={n_done}/{n_cl} exec="
              f"{exec_s.min():.3f}..{exec_s.max():.3f}s makespan="
              f"{ft.max():.3f}s events={n_events} (oracle "
              f"{res.n_events}) finish max_rel_err={err:.3g}")
        check(n_done == n_cl, f"fig8_9[{name}]: {n_done}/{n_cl} done")
        check(n_done == res.n_done, f"fig8_9[{name}]: n_done != oracle")
        check(n_events == res.n_events, f"fig8_9[{name}]: n_events != oracle")
        check(np.array_equal(state, res.cl_state),
              f"fig8_9[{name}]: cloudlet states != oracle")
        check(err <= RTOL, f"fig8_9[{name}]: finish times off the oracle")
        if pol == S.SPACE_SHARED:
            check(np.all(exec_s == 1200.0),
                  "fig8: space-shared exec time is not 1200 s everywhere")
            check(ft.max() == 1200.0 * waves,
                  f"fig8: makespan {ft.max()} != {1200.0 * waves}")


def compare_lanes(got, want, label):
    """Every lane of two [P, B] grids: counts exact, times/energy 1e-3."""
    import jax
    from repro.core import state as S
    from repro.core.energy import energy_total_j
    g_state = np.asarray(got.cloudlets.state)
    check(np.array_equal(g_state, np.asarray(want.cloudlets.state)),
          f"{label}: cloudlet states differ")
    for f in ("state", "host"):
        check(np.array_equal(np.asarray(getattr(got.vms, f)),
                             np.asarray(getattr(want.vms, f))),
              f"{label}: vm {f} differs")
    done = g_state == S.CL_DONE
    errs = {}
    for f in ("start_time", "finish_time"):
        errs[f] = rel_err(np.asarray(getattr(got.cloudlets, f))[done],
                          np.asarray(getattr(want.cloudlets, f))[done])
    errs["energy"] = rel_err(energy_total_j(got), energy_total_j(want))
    bitwise = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
                  zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    print(f"[{label}] lanes={done.shape[0] * done.shape[1]} "
          f"done={int(done.sum())} max_rel_err "
          + " ".join(f"{k}={v:.3g}" for k, v in errs.items())
          + f" bitwise={bitwise}")
    check(all(v <= RTOL for v in errs.values()),
          f"{label}: times/energy differ beyond {RTOL}")


def phase_grid(clock, n_scen=256, max_steps=8192, **kw):
    import jax
    from repro.core import state as S, sweep
    from repro.oracle import simulate_dense
    t0 = time.perf_counter()
    stacked = jax.block_until_ready(grid_batch(n_scen, **kw))
    vm_p, task_p = sweep.policy_grid()
    n_pol = int(vm_p.shape[0])
    print(f"[grid] built {n_pol}x{n_scen} lanes in "
          f"{time.perf_counter() - t0:.3f}s (set-up)")
    out = clock.timed(f"grid[{n_pol}x{n_scen}] sweep.run_grid",
                      lambda: sweep.run_grid(stacked, vm_p, task_p,
                                             max_steps=max_steps))
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[grid] peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    state = np.asarray(out.cloudlets.state)
    real = np.asarray(stacked.cloudlets.state) != S.CL_EMPTY     # [B, C]
    n_real = real.sum(-1)
    per_lane = (state == S.CL_DONE).sum(-1)                      # [P, B]
    check(np.array_equal(per_lane, np.broadcast_to(n_real, per_lane.shape)),
          "grid: some lane did not finish every cloudlet")
    # two lanes per policy pair: the 8-wave tail and a short lane
    picks = [(p, b) for p in range(n_pol)
             for b in ((8 * p + 7) % n_scen, (8 * p + 3) % n_scen)]
    t0 = time.perf_counter()
    worst = 0.0
    for p, b in picks:
        dc = dataclasses.replace(jax.tree.map(lambda x: x[b], stacked),
                                 vm_policy=vm_p[p], task_policy=task_p[p])
        res = simulate_dense(dc)
        got = jax.tree.map(lambda x: x[p, b], out)
        g_state = np.asarray(got.cloudlets.state)
        check(np.array_equal(g_state, res.cl_state),
              f"grid lane {(p, b)}: cloudlet states != oracle")
        check(np.array_equal(np.asarray(got.vms.host), res.vm_host),
              f"grid lane {(p, b)}: placements != oracle")
        done = res.cl_state == S.CL_DONE
        err = max(rel_err(np.asarray(got.cloudlets.finish_time)[done],
                          res.finish_time[done]),
                  rel_err(np.asarray(got.cloudlets.start_time)[done],
                          res.start_time[done]),
                  rel_err(np.asarray(got.hosts.energy_j).sum(),
                          res.energy_total_j))
        worst = max(worst, err)
        check(err <= RTOL, f"grid lane {(p, b)}: times/energy off oracle")
    print(f"[grid] {len(picks)} sampled lanes == oracle (counts exact, "
          f"max_rel_err={worst:.3g}); oracle host_s="
          f"{time.perf_counter() - t0:.3f}")
    return stacked, out


def phase_stream(clock, n=100_000, window=64, chunk=4096):
    from benchmarks.bench_policies import _streaming_scenario
    from repro.core import engine, state as S
    from repro.oracle.reference import simulate_stream
    hosts, vms, vm, length, sub = _streaming_scenario(n)
    stream = S.make_stream(vm, length, sub, chunk=chunk)
    dc = S.make_datacenter(hosts, vms, S.make_window(window),
                           vm_policy=S.SPACE_SHARED,
                           task_policy=S.SPACE_SHARED)
    _, st, recs = clock.timed(
        f"stream[{n}] engine.run_stream",
        lambda: engine.run_stream(dc, stream, reservoir=64,
                                  max_steps_per_chunk=4 * chunk))
    t0 = time.perf_counter()
    res = simulate_stream(dc, stream, reservoir=64)
    s = st.stats
    n_events = int(np.asarray(recs.n_events).sum())
    errs = {f: rel_err(getattr(s, f), getattr(res, f))
            for f in ("makespan", "sum_exec", "sum_response", "sum_len")}
    print(f"[stream] retired={int(s.n_retired)} failed={int(s.n_failed)} "
          f"events={n_events} (oracle {res.n_events}) max_rel_err "
          + " ".join(f"{k}={v:.3g}" for k, v in errs.items())
          + f"; oracle host_s={time.perf_counter() - t0:.3f}")
    check(int(s.n_retired) == res.n_retired == n,
          f"stream: retired {int(s.n_retired)} of {n}")
    check(int(s.n_failed) == res.n_failed == 0, "stream: arrivals failed")
    check(np.array_equal(np.asarray(s.per_vm_done), res.per_vm_done),
          "stream: per-VM completions != oracle")
    check(np.array_equal(np.asarray(s.res_sid), res.res_sid),
          "stream: sampled arrival ids != oracle")
    check(all(v <= RTOL for v in errs.values()),
          f"stream: aggregates differ from the oracle beyond {RTOL}")


def phase_four_chips(clock, n_scen=256, max_steps=8192, **kw):
    """The grid on a four-chip mesh per partitioner vs on one chip."""
    import jax
    from repro import compat
    from repro.core import sweep
    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = compat.make_mesh("sweep", devs[:4])
    stacked = jax.block_until_ready(grid_batch(n_scen, **kw))
    vm_p, task_p = sweep.policy_grid()
    label = f"{int(vm_p.shape[0])}x{n_scen}"
    ref = clock.timed(f"grid[{label}] one chip",
                      lambda: sweep.run_grid(stacked, vm_p, task_p,
                                             max_steps=max_steps,
                                             sharded=False))
    failed = []
    for part in ("auto", "dispatch", "gspmd"):
        watchdog = threading.Timer(SPELLING_LIMIT_S, _time_out, (part,))
        watchdog.start()
        try:
            got = clock.timed(
                f"grid[{label}] 4 chips partitioner={part}",
                lambda: sweep.run_grid(stacked, vm_p, task_p,
                                       max_steps=max_steps, mesh=mesh,
                                       partitioner=part))
            compare_lanes(got, ref, f"four_chips:{part}")
        except Exception:
            traceback.print_exc()
            failed.append(part)
        finally:
            watchdog.cancel()
    check(not failed, f"four chips: partitioners failed: {failed}")


def _time_out(part):
    print(f"[four_chips:{part}] exceeded {SPELLING_LIMIT_S}s; ending",
          flush=True)
    os._exit(124)


# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded grid on four chips")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}")
    vers = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            vers[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            vers[pkg] = "not installed"
    print("[device] " + " ".join(f"{k}={v}" for k, v in vers.items()))
    if dev.platform != "tpu":
        print(f"[device] no TPU (platform {dev.platform!r}); refusing to run",
              file=sys.stderr)
        return 2

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro import compat
    print(f"[cache] {compat.use_compile_cache()}")
    clock = CompileClock(jax)

    if args.four_chips:
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("kernel", phase_kernel), ("fig8_9", phase_fig89),
                  ("grid", phase_grid), ("stream", phase_stream)]
    failed = []
    for name, fn in phases:
        print(f"== {name}", flush=True)
        try:
            fn(clock)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    print(f"[time] total compile_s={clock.seconds():.3f} "
          f"cache_hits={clock.hits} (one smoke run)")
    if failed:
        print(f"FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
