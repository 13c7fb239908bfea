"""Plain CloudSim reference for the benchmark's static deployments.

An event-by-event replay over Python objects (hosts, VMs, cloudlets), in
f64, copied from the repository's oracle (``repro.oracle.reference``) and
cut to what the benchmark's cells use: first-fit FCFS VM provisioning
with RAM/BW/storage/PE admission and the ``reserve_pes`` flag, the
host-level VM scheduler and the VM-level cloudlet scheduler (space- and
time-shared each, the paper's Figure 3 matrix), the discrete-event loop
(next event = earliest completion or arrival, piecewise-constant rates
in between) and per-host energy as the integral of a utilisation->power
curve.  No dynamic events, migration, network, autoscaler or metrics:
no cell's deployment carries them.

It imports nothing of the program and takes only the plain arrays that
the benchmark's generator makes from the seed.  ``precision="bfloat16"``
rounds every stored quantity to bfloat16: the control, which a sound
comparison has to refuse.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import numpy as np

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


def simulate(lane, *, precision: str = "float64") -> Result:
    """Replay one lane (``traffic.Lane``) to quiescence."""
    return Replay(lane, precision=precision).run()
