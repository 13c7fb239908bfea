"""Deployment kind ``waves``: a uniform fleet under a wave workload.

A deployment of this kind (``configs/<name>.json``) fixes the fleet
(``hosts``), the VM class (``vms``) and the wave workload (``cloudlets``:
``waves`` waves of one ``length_mi`` cloudlet per VM, ``period_s``
apart).  A traffic mix (``traffic/<name>.json``) says which runner a
study goes through, which (vm, task) policy pairs it runs and how many
copies of the scenario a grid study stacks.  The seed orders the policy
pairs and nothing else, so every seed runs the same work: the same seed
gives the same studies, in every run and on every machine.

Keys of a traffic file:

``runner``
    ``"engine.run"``: a study is the scenario under one policy pair,
    taken in seeded order from a shuffled cycle of ``policy_pairs``.
    ``"sweep.run_grid"``: a study is ``replicates`` copies of the
    scenario x all ``policy_pairs`` in an order drawn from the seed,
    fused into one call, sharded over the cell's chips where it has more
    than one (``run_grid``'s default partitioner).
``policy_pairs``
    ``[[vm_policy, task_policy], ...]``; 0 is space-shared, 1 time-shared.
``replicates``
    scenarios a grid study stacks (1 for ``engine.run``).
``max_steps``
    the runner's event budget per lane.

The module fills the contract of a deployment kind (``chipbench.spec``):
``make_mix``, ``System``, ``reference``, ``readings``, ``counters``,
``reference_outputs`` and ``tiny``; ``SCOPES`` names the program's pass
scopes for ``record_trace.py``'s breakdown.  The reference and the comparison:
after the window has closed, the plain reference replays the scenario
once under each policy pair, and every lane of every study the window ran
is compared with the replay of its pair, slot by slot: VM placements and
cloudlet states exactly, start/finish times and the clock at quiescence,
and per-host energy, by relative error.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, \
    Tuple

import numpy as np

from chipbench.check import rel_err

RUNNERS = ("engine.run", "sweep.run_grid")
Pair = Tuple[int, int]
# the named scopes ``engine.step`` and ``engine.batched_run`` run their
# passes under
SCOPES = ("events", "autoscaler", "provision", "phases", "rates",
          "migration", "flows", "horizon", "commit", "probes", "leap",
          "record", "freeze")


# -- the generator: a deployment file and a traffic file make studies -----

@dataclasses.dataclass(frozen=True)
class Hosts:
    num_pes: np.ndarray         # i32[H]
    mips: np.ndarray            # f32[H] MIPS per PE
    ram: np.ndarray             # f32[H] MB
    bw: np.ndarray              # f32[H] MB/s
    storage: np.ndarray         # f32[H] MB
    idle_w: np.ndarray          # f32[H] watts at utilisation 0
    peak_w: np.ndarray          # f32[H] watts at utilisation 1


@dataclasses.dataclass(frozen=True)
class Vms:
    pes: np.ndarray             # i32[V]
    mips: np.ndarray            # f32[V] MIPS per PE
    ram: np.ndarray
    bw: np.ndarray
    size: np.ndarray
    submit: np.ndarray          # f32[V] seconds


@dataclasses.dataclass(frozen=True)
class Cloudlets:
    vm: np.ndarray              # i32[C] owning VM, grouped by VM
    length: np.ndarray          # f32[C] MI
    submit: np.ndarray          # f32[C] seconds


@dataclasses.dataclass(frozen=True)
class Lane:
    """One datacenter under one policy pair: what one simulation is fed."""
    hosts: Hosts
    vms: Vms
    cloudlets: Cloudlets
    vm_policy: int
    task_policy: int
    reserve_pes: bool


def _full(n, value, dtype=np.float32):
    return np.full(n, value, dtype)


class Mix:
    """Studies of one cell, from its deployment, its traffic and a seed.
    A study is the tuple of its policy pairs, in lane order."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.seed = int(seed)
        self.runner = traffic["runner"]
        if self.runner not in RUNNERS:
            raise ValueError(f"unknown runner {self.runner!r}")
        self.pairs = tuple((int(v), int(t))
                           for v, t in traffic["policy_pairs"])
        self.max_steps = int(traffic["max_steps"])
        self.replicates = int(traffic.get("replicates", 1))
        if self.runner == "engine.run" and self.replicates != 1:
            raise ValueError("an engine.run study is one scenario")
        self.reserve_pes = bool(config["reserve_pes"])
        h, v, c = config["hosts"], config["vms"], config["cloudlets"]
        n = int(h["count"])
        self.hosts = Hosts(
            _full(n, h["pes"], np.int32), _full(n, h["mips"]),
            _full(n, h["ram"]), _full(n, h["bw"]), _full(n, h["storage"]),
            _full(n, h["idle_w"]), _full(n, h["peak_w"]))
        m, waves = int(v["count"]), int(c["waves"])
        self.vms = Vms(
            _full(m, v["pes"], np.int32), _full(m, v["mips"]),
            _full(m, v["ram"]), _full(m, v["bw"]), _full(m, v["size"]),
            _full(m, 0.0))
        self.cloudlets = Cloudlets(
            vm=np.repeat(np.arange(m, dtype=np.int32), waves),
            length=_full(m * waves, c["length_mi"]),
            submit=(np.tile(np.arange(waves, dtype=np.float32), m)
                    * np.float32(c["period_s"])))

    def warmup(self) -> Tuple[Pair, ...]:
        """A study of the cell's own shapes, not among the timed ones."""
        return self.pairs if self.runner == "sweep.run_grid" \
            else self.pairs[:1]

    def study(self, index: int) -> Tuple[Pair, ...]:
        n = len(self.pairs)
        if self.runner == "sweep.run_grid":
            order = np.random.default_rng([self.seed, 2, index])
            return tuple(self.pairs[k] for k in order.permutation(n))
        block = np.random.default_rng([self.seed, 2, index // n])
        return (self.pairs[block.permutation(n)[index % n]],)

    def lanes(self, study: Tuple[Pair, ...]) -> int:
        """Simulations one study runs."""
        return len(study) * self.replicates

    def lane(self, pair: Pair) -> Lane:
        """The plain inputs of a lane under ``pair``, for the reference."""
        return Lane(self.hosts, self.vms, self.cloudlets, pair[0], pair[1],
                    self.reserve_pes)


def make_mix(config: dict, traffic: dict, seed: int) -> Mix:
    return Mix(config, traffic, seed)


def tiny(config: dict, traffic: dict) -> Tuple[dict, dict]:
    """The deployment and the traffic cut to a size the CPU runs in a
    second: 32 hosts, 4 VMs, 3 waves, 2 replicates a grid study."""
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["hosts"]["count"], config["vms"]["count"] = 32, 4
    config["cloudlets"]["waves"] = 3
    if "replicates" in traffic:
        traffic["replicates"] = 2
    return config, traffic


# -- the system under test, entered only through its public API -----------
#
# ``state.make_*`` builds each scenario, ``sweep.stack_scenarios`` stacks a
# grid's replicates, ``engine.run`` or ``sweep.run_grid`` runs a study, and
# ``sweep.summarize_batch`` reduces it to the summary a researcher's script
# fetches.  The scenario is built once, in set-up, on the host's CPU
# backend where JAX has one, and put on the chip in one transfer (on every
# chip of the mesh, whole, where the cell has more than one); a study only
# swaps in its policy pairs.

class Outputs(NamedTuple):
    """What the comparison reads of a study's final state, [P, R, ...]
    for a grid and unbatched for a single run, and the program's loop
    counters (``engine.RunStats``), which ``counters`` reads; the
    reference makes no counters."""
    cl_state: object
    start_time: object
    finish_time: object
    vm_host: object
    energy_j: object
    time: object
    iterations: object = None   # loop trips while the lane was live
    events: object = None       # events retired


class System:
    def __init__(self, mix: Mix, devices: Sequence):
        import jax
        from repro import compat
        from repro.core import engine, state as S, sweep
        self.jax, self.engine, self.sweep = jax, engine, sweep
        self.mix = mix
        self.devices = list(devices)
        self.mesh = (compat.make_mesh("sweep", self.devices)
                     if len(self.devices) > 1 else None)
        # where the study's inputs live: the program's jit takes them on
        # the devices its mesh spans
        self.home = (jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
            if self.mesh is not None else self.devices[0])
        if mix.runner == "engine.run" and len(self.devices) > 1:
            raise ValueError("an engine.run study runs on one chip")
        v0, t0 = mix.pairs[0]
        h, v, c = mix.hosts, mix.vms, mix.cloudlets
        try:        # many small eager ops: quicker on the host's backend
            build_on = jax.devices("cpu")[0]
        except RuntimeError:            # JAX_PLATFORMS left the CPU out
            build_on = self.devices[0]
        with jax.default_device(build_on):
            dc = S.make_datacenter(
                S.make_hosts(h.num_pes, h.mips, h.ram, h.bw, h.storage,
                             idle_w=h.idle_w, peak_w=h.peak_w),
                S.make_vms(v.pes, v.mips, v.ram, v.bw, v.size, v.submit),
                S.make_cloudlets(c.vm, c.length, c.submit),
                vm_policy=v0, task_policy=t0, reserve_pes=mix.reserve_pes)
            if mix.runner == "sweep.run_grid":
                dc = sweep.stack_scenarios([dc] * mix.replicates)
        self.template = jax.block_until_ready(jax.device_put(dc, self.home))
        self._pairs = {}

    def _policies(self, pairs: Tuple[Pair, ...]):
        """The study's policy arrays on the chip, put there once per
        order: (vm, task) scalars for a single run, i32[P] each for a
        grid."""
        if pairs not in self._pairs:
            put = lambda x: self.jax.device_put(np.asarray(x, np.int32),
                                                self.home)
            if self.mix.runner == "engine.run":
                (v, t), = pairs
                self._pairs[pairs] = (put(v), put(t))
            else:
                self._pairs[pairs] = (put([p[0] for p in pairs]),
                                      put([p[1] for p in pairs]))
        return self._pairs[pairs]

    def prepare(self, pairs: Tuple[Pair, ...]):
        """The study's inputs, on the chip."""
        vm_p, task_p = self._policies(pairs)
        if self.mix.runner == "engine.run":
            return dataclasses.replace(self.template, vm_policy=vm_p,
                                       task_policy=task_p), None, None
        return self.template, vm_p, task_p

    def dispatch(self, inputs):
        """``(final state, RunStats)``: the program's loop counters come
        back beside the state (``stats=True``; the same compiled program
        runs either way) and stay on the chip until the window closes."""
        dc, vm_p, task_p = inputs
        steps = self.mix.max_steps
        if self.mix.runner == "engine.run":
            return self.engine.run(dc, max_steps=steps, stats=True)
        where = {"sharded": False} if self.mesh is None else \
            {"mesh": self.mesh}
        return self.sweep.run_grid(dc, vm_p, task_p, max_steps=steps,
                                   stats=True, **where)

    def summary(self, out) -> Tuple[int, int]:
        """The study's summary, fetched to the host: (cloudlets completed,
        summed over lanes; lanes that did not complete every cloudlet)."""
        final, _ = out
        n_done = self.jax.device_get(
            self.sweep.summarize_batch(final)).n_done
        return (int(n_done.sum()),
                int(np.sum(np.asarray(n_done) != len(self.mix.cloudlets.vm))))

    @staticmethod
    def keep(out) -> Outputs:
        """The leaves the comparison reads and the loop counters; the rest
        of ``out`` is freed."""
        final, stats = out
        return Outputs(final.cloudlets.state, final.cloudlets.start_time,
                       final.cloudlets.finish_time, final.vms.host,
                       final.hosts.energy_j, final.time, stats.iterations,
                       stats.events)

    def peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))


# -- the plain reference ---------------------------------------------------
#
# An event-by-event replay over Python objects (hosts, VMs, cloudlets), in
# f64, copied from the repository's oracle (``repro.oracle.reference``) and
# cut to what this kind's deployments use: first-fit FCFS VM provisioning
# with RAM/BW/storage/PE admission and the ``reserve_pes`` flag, the
# host-level VM scheduler and the VM-level cloudlet scheduler (space- and
# time-shared each, the paper's Figure 3 matrix), the discrete-event loop
# (next event = earliest completion or arrival, piecewise-constant rates in
# between) and per-host energy as the integral of a utilisation->power
# curve.  No dynamic events, migration, network, autoscaler or metrics: no
# deployment of this kind carries them.
#
# It imports nothing of the program and takes only the plain arrays that
# the generator makes from the seed.  ``precision="bfloat16"`` rounds every
# stored quantity to bfloat16: the control, which a sound comparison has to
# refuse.

SPACE_SHARED = 0
TIME_SHARED = 1
VM_PENDING, VM_ACTIVE, VM_FAILED = 1, 2, 3
CL_CREATED, CL_DONE, CL_FAILED = 1, 2, 3
INF = float(1e30)

# completion snap band, as the engine's: simultaneous completions
# collapse into one event on both sides
_SNAP_REL = 1e-5
_SNAP_ABS = 1e-9
_CURVE_POINTS = 11          # utilisations 0, 0.1, ..., 1.0


def _rounders(precision: str) -> tuple[Callable, Callable]:
    """(round one float, round an f64 array) to ``precision``."""
    if precision == "float64":
        return float, lambda a: a
    if precision == "bfloat16":
        import ml_dtypes
        bf16 = ml_dtypes.bfloat16
        return (lambda x: float(bf16(x)),
                lambda a: a.astype(bf16).astype(np.float64))
    raise ValueError(f"unknown precision {precision!r}")


@dataclasses.dataclass
class Host:
    index: int
    num_pes: int
    mips_per_pe: float
    ram: float
    bw: float
    storage: float
    idle_w: float
    peak_w: float
    power_curve: tuple
    free_ram: float = 0.0
    free_bw: float = 0.0
    free_storage: float = 0.0
    free_pes: float = 0.0
    vms: List["Vm"] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Vm:
    index: int
    req_pes: int
    req_mips: float
    ram: float
    bw: float
    size: float
    submit_time: float
    state: int = VM_PENDING
    host: Optional[Host] = None
    create_time: float = INF
    cloudlets: List["Cloudlet"] = dataclasses.field(default_factory=list)
    capacity: float = 0.0


@dataclasses.dataclass
class Cloudlet:
    index: int
    vm: int
    length: float
    submit_time: float
    remaining: float = 0.0
    start_time: float = -1.0
    finish_time: float = INF
    state: int = CL_CREATED
    rate: float = 0.0


@dataclasses.dataclass
class Result:
    """Per-slot outcome, index for index with the lane's arrays."""
    start_time: np.ndarray      # f64[C] seconds (-1 if never started)
    finish_time: np.ndarray     # f64[C] seconds (INF if not done)
    cl_state: np.ndarray        # i32[C]
    vm_host: np.ndarray         # i32[V] (-1 if unplaced)
    energy_j: np.ndarray        # f64[H] joules per host
    time: float                 # clock at quiescence, seconds
    n_events: int


class Replay:
    """One datacenter, replayed event by event."""

    def __init__(self, lane, *, precision: str = "float64"):
        q, self.qa = _rounders(precision)
        self.q = q
        self.vm_policy = int(lane.vm_policy)
        self.task_policy = int(lane.task_policy)
        self.reserve_pes = bool(lane.reserve_pes)
        h = lane.hosts
        curve = tuple(q(x) for x in np.linspace(0.0, 1.0, _CURVE_POINTS))
        self.hosts = [
            Host(i, int(h.num_pes[i]), q(h.mips[i]), q(h.ram[i]),
                 q(h.bw[i]), q(h.storage[i]), q(h.idle_w[i]),
                 q(h.peak_w[i]), curve)
            for i in range(len(h.num_pes))]
        v = lane.vms
        self.vms = [
            Vm(i, int(v.pes[i]), q(v.mips[i]), q(v.ram[i]), q(v.bw[i]),
               q(v.size[i]), q(v.submit[i]))
            for i in range(len(v.pes))]
        c = lane.cloudlets
        self.cloudlets = [
            Cloudlet(i, int(c.vm[i]), q(c.length[i]), q(c.submit[i]))
            for i in range(len(c.vm))]
        for cl in self.cloudlets:
            cl.remaining = cl.length
            self.vms[cl.vm].cloudlets.append(cl)
        for host in self.hosts:
            host.free_ram, host.free_bw = host.ram, host.bw
            host.free_storage = host.storage
            host.free_pes = float(host.num_pes)
        # energy per host, f64 (one accumulator per host, as a scalar
        # loop would keep); a host without VMs draws its idle power
        self.energy_j = np.zeros(len(self.hosts))
        self.idle_power = np.array([self._power(h, 0.0)
                                    for h in self.hosts])
        self.time = 0.0
        self.n_events = 0

    # -- provisioning: first-fit FCFS over the hosts in index order ------
    def _feasible(self, host: Host, vm: Vm) -> bool:
        pes_ok = (host.free_pes >= vm.req_pes if self.reserve_pes
                  else host.num_pes >= vm.req_pes)
        return (host.free_ram >= vm.ram and host.free_bw >= vm.bw
                and host.free_storage >= vm.size
                and host.mips_per_pe >= vm.req_mips and pes_ok)

    def _provision(self):
        due = [v for v in self.vms
               if v.state == VM_PENDING and v.submit_time <= self.time]
        for vm in sorted(due, key=lambda v: (v.submit_time, v.index)):
            placed = next((h for h in self.hosts if self._feasible(h, vm)),
                          None)
            if placed is None:
                vm.state = VM_FAILED
                for cl in vm.cloudlets:
                    if cl.state == CL_CREATED:
                        cl.state = CL_FAILED
                continue
            placed.free_ram -= vm.ram
            placed.free_bw -= vm.bw
            placed.free_storage -= vm.size
            if self.reserve_pes:
                placed.free_pes -= vm.req_pes
            placed.vms.append(vm)
            vm.host = placed
            vm.state = VM_ACTIVE
            vm.create_time = self.time

    # -- rates: hosts grant capacity to VMs, VMs divide it among tasks ---
    def _runnable(self, cl: Cloudlet, vm: Vm) -> bool:
        return (cl.state == CL_CREATED and cl.submit_time <= self.time
                and cl.remaining > 0.0 and vm.state == VM_ACTIVE)

    def _update_rates(self):
        q = self.q
        for cl in self.cloudlets:
            cl.rate = 0.0
        for vm in self.vms:
            vm.capacity = 0.0
        for host in self.hosts:
            if not host.vms:
                continue
            eligible = [vm for vm in host.vms if vm.state == VM_ACTIVE and (
                self.reserve_pes
                or any(self._runnable(cl, vm) for cl in vm.cloudlets))]
            eligible.sort(key=lambda v: (v.create_time, v.index))
            demands = [q(v.req_pes * min(v.req_mips, host.mips_per_pe))
                       for v in eligible]
            if self.vm_policy == SPACE_SHARED:
                # FCFS whole-PE grants with strict head-of-line blocking
                cum = 0
                for vm, demand in zip(eligible, demands):
                    cum += vm.req_pes
                    vm.capacity = demand if cum <= host.num_pes else 0.0
            else:
                total = q(sum(demands))
                host_cap = q(host.num_pes * host.mips_per_pe)
                scale = q(min(1.0, host_cap / total)) if total > 0.0 else 0.0
                for vm, demand in zip(eligible, demands):
                    vm.capacity = q(demand * scale)
        for vm in self.vms:
            if vm.state != VM_ACTIVE:
                continue
            runnable = [cl for cl in vm.cloudlets if self._runnable(cl, vm)]
            if not runnable:
                continue
            pes = max(float(vm.req_pes), 1.0)
            if self.task_policy == SPACE_SHARED:
                per_pe = q(vm.capacity / pes)
                for rank, cl in enumerate(runnable):   # submission order
                    cl.rate = per_pe if rank < int(pes) else 0.0
            else:
                share = q(vm.capacity / max(float(len(runnable)), pes))
                for cl in runnable:
                    cl.rate = share

    # -- the event loop ---------------------------------------------------
    def _next_dt(self) -> tuple:
        dt = arrive = INF
        for cl in self.cloudlets:
            if cl.state != CL_CREATED:
                continue
            if cl.rate > 0.0:
                dt = min(dt, self.q(cl.remaining / cl.rate))
            if cl.submit_time > self.time:
                arrive = min(arrive, cl.submit_time)
        for vm in self.vms:
            if vm.state == VM_PENDING and vm.submit_time > self.time:
                arrive = min(arrive, vm.submit_time)
        return dt, arrive

    def _power(self, host: Host, util: float) -> float:
        curve = host.power_curve
        u = min(max(util, 0.0), 1.0) * (len(curve) - 1)
        lo = min(int(u), len(curve) - 2)
        frac = u - lo
        c = curve[lo] * (1.0 - frac) + curve[lo + 1] * frac
        return self.q(host.idle_w + (host.peak_w - host.idle_w) * c)

    def _accrue_energy(self, dt: float):
        """Rates are constant over [time, time+dt), so power * dt is exact."""
        power = self.idle_power.copy()
        for host in self.hosts:
            if not host.vms:
                continue
            cap = host.num_pes * host.mips_per_pe
            used = sum(cl.rate for vm in host.vms for cl in vm.cloudlets)
            util = used / cap if cap > 0.0 else 0.0
            power[host.index] = self._power(host, util)
        self.energy_j = self.qa(self.energy_j + self.qa(power * dt))

    def _advance(self, dt: float, t_next: float):
        q = self.q
        snap = dt * (1.0 + _SNAP_REL) + _SNAP_ABS
        for cl in self.cloudlets:
            if cl.state != CL_CREATED:
                continue
            if cl.rate > 0.0 and cl.start_time < 0.0:
                cl.start_time = self.time
            if cl.rate > 0.0 and q(cl.remaining / cl.rate) <= snap:
                cl.remaining = 0.0
                cl.finish_time = t_next
                cl.state = CL_DONE
            else:
                cl.remaining = q(max(cl.remaining - q(cl.rate * dt), 0.0))
        self.time = t_next

    def run(self, max_events: int = 100_000) -> Result:
        while self.n_events < max_events:
            self._provision()
            self._update_rates()
            dt, arrive = self._next_dt()
            dt_arr = self.q(arrive - self.time) if arrive < INF else INF
            head = min(dt, dt_arr)
            if head >= INF:
                break
            # arrivals win ties: the clock lands on the exact arrival time
            t_next = arrive if dt_arr <= dt else self.q(self.time + head)
            self._accrue_energy(head)
            self._advance(head, t_next)
            self.n_events += 1
        return Result(
            start_time=np.array([c.start_time for c in self.cloudlets]),
            finish_time=np.array([c.finish_time for c in self.cloudlets]),
            cl_state=np.array([c.state for c in self.cloudlets], np.int32),
            vm_host=np.array([v.host.index if v.host is not None else -1
                              for v in self.vms], np.int32),
            energy_j=self.energy_j,
            time=self.time, n_events=self.n_events)


def reference(lane: Lane, precision: str = "float64") -> Result:
    """Replay one lane to quiescence."""
    return Replay(lane, precision=precision).run()


# -- the comparison ----------------------------------------------------------

def lane_readings(got: dict, ref: Result) -> Dict[str, float]:
    """The compared numbers of the lanes in ``got`` (host arrays, with
    any number of leading lane axes), all run under ``ref``'s pair."""
    done = ref.cl_state == CL_DONE
    return {
        "placements_wrong": int(np.sum(got["vm_host"] != ref.vm_host)),
        "states_wrong": int(np.sum(got["cl_state"] != ref.cl_state)),
        "time_rel_err": max(
            rel_err(got["start_time"][..., done], ref.start_time[done]),
            rel_err(got["finish_time"][..., done], ref.finish_time[done]),
            rel_err(got["time"], ref.time)),
        "energy_rel_err": rel_err(got["energy_j"], ref.energy_j),
    }


def pair_of(outputs: dict, mix: Mix, pair: int) -> dict:
    """The lanes of one policy pair in a study's fetched outputs: [R, ...]
    for a grid, one lane for a single run; padding cut off."""
    n = {"cl_state": len(mix.cloudlets.vm), "start_time": len(
        mix.cloudlets.vm), "finish_time": len(mix.cloudlets.vm),
        "vm_host": len(mix.vms.pes), "energy_j": len(mix.hosts.num_pes)}
    idx = () if mix.runner == "engine.run" else (pair,)
    out = {k: np.asarray(outputs[k])[idx][..., :m] for k, m in n.items()}
    out["time"] = np.asarray(outputs["time"])[idx]
    return out


def readings(mix: Mix, studies: Sequence[Tuple[Pair, ...]],
             outputs: Sequence[Outputs]) -> Dict[str, float]:
    """Worst readings over every lane of ``studies``: ``outputs[s]`` is
    study ``s``'s ``Outputs`` as host arrays."""
    worst = {"placements_wrong": 0, "states_wrong": 0,
             "time_rel_err": 0.0, "energy_rel_err": 0.0}
    replays: Dict[Pair, Result] = {}
    for pair in sorted({p for study in studies for p in study}):
        replays[pair] = reference(mix.lane(pair))
    for study, out in zip(studies, outputs):
        out = out._asdict()
        for p, pair in enumerate(study):
            got = pair_of(out, mix, p)
            for k, v in lane_readings(got, replays[pair]).items():
                worst[k] = max(worst[k], v)
    return worst


def counters(mix: Mix, studies: Sequence[Tuple[Pair, ...]],
             outputs: Sequence[Outputs]) -> List[Dict[str, int]]:
    """Each study's loop counters, from its kept ``RunStats`` (host
    arrays): ``iterations``, the loop trips of the lane that ran longest,
    and ``events``, the events retired summed over its lanes."""
    return [{"iterations": int(np.max(out.iterations)),
             "events": int(np.sum(out.events))} for out in outputs]


def reference_outputs(mix: Mix, studies: Sequence[Tuple[Pair, ...]],
                      precision: str) -> List[Outputs]:
    """Each study's ``Outputs`` as the reference in ``precision`` makes
    them, in the program's place: every lane of a pair holds the replay of
    that pair."""
    replays: Dict[Pair, Result] = {}
    for pair in sorted({p for study in studies for p in study}):
        replays[pair] = reference(mix.lane(pair), precision)

    def lanes(pairs, leaf):
        rows = [np.asarray(getattr(replays[p], leaf)) for p in pairs]
        if mix.runner == "engine.run":
            return rows[0]
        return np.broadcast_to(np.stack(rows)[:, None],
                               (len(pairs), mix.replicates) + rows[0].shape)
    compared = [f for f in Outputs._fields
                if f not in Outputs._field_defaults]     # not the counters
    return [Outputs(*(lanes(study, leaf) for leaf in compared))
            for study in studies]
