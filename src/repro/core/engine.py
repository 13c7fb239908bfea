"""Tensorized discrete-event engine — CloudSim's SimJava layer, TPU-native.

CloudSim advances time with a shared event queue serviced by Java threads
(§4.1): each Datacenter asks every Host -> VM -> Cloudlet for its next
completion time and the smallest one becomes the next internal event.

Between two events every execution rate is constant (piecewise-constant-rate
processor sharing), so the *entire* event queue collapses into dense
min-reductions:

    next event = min( t + remaining/rate  over running cloudlets,
                      submit times        of future cloudlets,
                      submit times        of pending VMs,
                      times               of pending dynamic events,
                      migration-copy      completions,
                      0                   if a migration triggers now )

and the state advance is one fused multiply-subtract.  The engine is a pure
``step`` function driven by ``lax.while_loop`` (run to completion) or
``lax.scan`` (fixed step count, with a telemetry trace).  Because ``step``
is pure and shape-stable it can be ``vmap``-ed over scenario batches
(sweep.py fuses policy grids into the same batch axis and shards it over
devices) and ``shard_map``-ed over datacenter shards (see federation.py).

Dynamic datacenters (paper §3.1 lifecycle; arXiv:0907.4878 migration):
``DatacenterState.events`` is a fixed-shape f32[E, 4] table of timed VM
create/destroy and host fail/recover rows applied at the top of ``step``,
and ``core/migration.py`` contributes a per-event live-migration pass.
Both are gated by the *static* ``dynamic`` flag: static scenarios
(``dynamic=False``, auto-detected by the public entry points) compile to
exactly the pre-dynamic program, so the subsystem costs nothing when off.

Units, here and everywhere downstream of ``DatacenterState``: simulated
time in seconds (f32), cloudlet lengths/progress in MI (million
instructions), rates in MIPS, RAM/storage/transfer sizes in MB, money in
dollars.  Entity axes are H hosts, V VMs, C cloudlets, E events.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy, market, metrics, migration, network, scheduling
from repro.core.network import wants_network
from repro.core.provisioning import (FIRST_FIT, alive_fleet, alive_mask,
                                     provision_pending)
from repro.core.state import (
    CL_CREATED,
    CL_DONE,
    CL_FAILED,
    EV_HOST_FAIL,
    EV_HOST_RECOVER,
    EV_NONE,
    EV_VM_CREATE,
    EV_VM_DESTROY,
    ArrivalStream,
    DatacenterState,
    INF,
    MIG_OFF,
    MIG_THRESHOLD,
    NET_PRE,
    NET_STAGE_OUT,
    StreamState,
    VM_ACTIVE,
    VM_DESTROYED,
    VM_EMPTY,
    VM_FAILED,
    VM_PENDING,
    make_stream_state,
)

__all__ = ["step", "run", "run_trace", "batched_run", "run_stream",
           "StepRecord", "RunStats", "StreamChunkRecord", "apply_due_events",
           "apply_autoscaler", "wants_dynamic", "wants_network",
           "wants_elastic", "wants_probes"]

_EPS_MI = 1e-3      # absolute snap threshold, in million instructions

# Event-horizon leaping (``step(..., leap=True)``) is the default for the
# while_loop runners; ``run_trace`` keeps it off so the scan trace stays
# one record per event.  Tests force both settings and assert bitwise
# equality (tests/test_leap_parity.py).
_LEAP_DEFAULT = True

# Host spans on the profiler's clock around the public runners' host work;
# without a running profiler each costs well under a microsecond.
_span = jax.profiler.TraceAnnotation


class StepRecord(NamedTuple):
    """Telemetry emitted once per simulation event (scan trace)."""
    time: jnp.ndarray          # f32[] time *after* the step
    n_running: jnp.ndarray     # i32[] cloudlets with rate > 0 during step
    n_done: jnp.ndarray        # i32[] cumulative completed cloudlets
    utilization: jnp.ndarray   # f32[] consumed MIPS / total host MIPS
    watts: jnp.ndarray         # f32[] fleet power drawn *during* the step
    active: jnp.ndarray        # bool[] this step advanced the simulation
    n_migrating: jnp.ndarray   # i32[] VMs mid-migration *after* the step
    migrations: jnp.ndarray    # i32[] cumulative migrations performed
    hosts_down: jnp.ndarray    # i32[] real hosts currently failed
    transferred_mb: jnp.ndarray  # f32[] cumulative staged MB *after* the step
    n_flows: jnp.ndarray       # i32[] transfers drawing bandwidth during step
    n_events: jnp.ndarray      # i32[] events committed by this step (>= 1;
    #                                  > 1 when the horizon leap fired)
    fleet: jnp.ndarray         # i32[] alive (PENDING|ACTIVE) VMs *after* step
    spot_cost: jnp.ndarray     # f32[] cumulative spot spend *after* the step


class RunStats(NamedTuple):
    """Loop counters the while_loop runners return beside the final state
    (scalar for ``run``, one per lane for ``batched_run``)."""
    iterations: jnp.ndarray    # i32 loop trips while the lane was live
    events: jnp.ndarray        # i32 events retired, the leap's included


def _hit(n: int, idx: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """bool[n] — slots targeted by at least one masked event row."""
    return jnp.zeros((n,), jnp.int32).at[idx].add(
        mask.astype(jnp.int32)) > 0


def apply_due_events(dc: DatacenterState) -> DatacenterState:
    """Apply every pending event row due at ``dc.time``; mark rows fired.

    Kind order within one instant (mirrored by the oracle): VM destroys
    (resources returned to surviving hosts), VM creates (EMPTY ->
    PENDING; the VM provisions at ``max(event time, submit_time)``),
    host failures (valid=False, pools reset to capacity, resident VMs
    evicted back to PENDING for immediate re-provisioning — their
    original submit times are already due — with their cloudlet progress
    kept), host recoveries (invalid real hosts return with full free
    pools).  With every row already fired this is a bit-exact identity,
    preserving the quiescence fixed point.

    ``vms.submit_time`` is deliberately *never* rewritten: besides
    keeping CloudSim's FCFS-by-original-request order on re-provisioning,
    it keeps the provisioner's lexsort keys loop-invariant — the pinned
    jaxlib's CPU SPMD partitioner miscompiles a loop-variant sort inside
    ``shard_map`` into a cross-device all-reduce whose rendezvous
    deadlocks when lanes quiesce at different step counts (see the
    ROADMAP landmine note).
    """
    if dc.events.shape[0] == 0:
        return dc
    hosts, vms, cl = dc.hosts, dc.vms, dc.cloudlets
    nh = hosts.num_pes.shape[0]
    nv = vms.req_pes.shape[0]

    ev_t = dc.events[:, 0]
    ev_k = dc.events[:, 1].astype(jnp.int32)
    ev_tgt = dc.events[:, 2].astype(jnp.int32)
    due = (~dc.event_fired) & (ev_k != EV_NONE) & (ev_t <= dc.time)
    # rows with out-of-range targets fire but act on nothing (the oracle's
    # dict-lookup no-op), so clipped scatters never hit a wrong slot
    due_v = due & (ev_tgt >= 0) & (ev_tgt < nv)
    due_h = due & (ev_tgt >= 0) & (ev_tgt < nh)
    tv = jnp.clip(ev_tgt, 0, nv - 1)
    th = jnp.clip(ev_tgt, 0, nh - 1)

    # ---- 1. VM destroys ---------------------------------------------------
    destroy = (_hit(nv, tv, due_v & (ev_k == EV_VM_DESTROY))
               & alive_mask(vms))
    returning = destroy & (vms.state == VM_ACTIVE) & (vms.host >= 0)
    hclip = jnp.clip(vms.host, 0, nh - 1)
    w = returning.astype(jnp.float32)
    give = lambda pool, x: pool.at[hclip].add(w * x)
    reserve = jnp.where(dc.reserve_pes == 1,
                        vms.req_pes.astype(jnp.float32), 0.0)
    free_ram = give(hosts.free_ram, vms.ram)
    free_bw = give(hosts.free_bw, vms.bw)
    free_storage = give(hosts.free_storage, vms.size)
    free_pes = give(hosts.free_pes, reserve)
    vm_state = jnp.where(destroy, VM_DESTROYED, vms.state)
    vm_host = jnp.where(destroy, -1, vms.host)
    mig_rem = jnp.where(destroy, 0.0, vms.mig_remaining)

    # ---- 2. VM creates ----------------------------------------------------
    create = (_hit(nv, tv, due_v & (ev_k == EV_VM_CREATE))
              & (vm_state == VM_EMPTY))
    vm_state = jnp.where(create, VM_PENDING, vm_state)

    # ---- 3. host failures -------------------------------------------------
    real = hosts.num_pes > 0
    fail = (_hit(nh, th, due_h & (ev_k == EV_HOST_FAIL))
            & hosts.valid & real)
    evict = ((vm_state == VM_ACTIVE) & (vm_host >= 0)
             & fail[jnp.clip(vm_host, 0, nh - 1)])
    vm_state = jnp.where(evict, VM_PENDING, vm_state)
    vm_create_t = jnp.where(evict, INF, vms.create_time)
    vm_host = jnp.where(evict, -1, vm_host)
    mig_rem = jnp.where(evict, 0.0, mig_rem)
    valid = hosts.valid & ~fail
    free_ram = jnp.where(fail, hosts.ram, free_ram)
    free_bw = jnp.where(fail, hosts.bw, free_bw)
    free_storage = jnp.where(fail, hosts.storage, free_storage)
    free_pes = jnp.where(fail, hosts.num_pes.astype(jnp.float32), free_pes)

    # ---- 4. host recoveries ----------------------------------------------
    recover = (_hit(nh, th, due_h & (ev_k == EV_HOST_RECOVER))
               & ~valid & real)
    valid = valid | recover
    free_ram = jnp.where(recover, hosts.ram, free_ram)
    free_bw = jnp.where(recover, hosts.bw, free_bw)
    free_storage = jnp.where(recover, hosts.storage, free_storage)
    free_pes = jnp.where(recover, hosts.num_pes.astype(jnp.float32),
                         free_pes)

    # cloudlets of destroyed VMs can never run
    owner = jnp.clip(cl.vm, 0, nv - 1)
    cancel = (cl.state == CL_CREATED) & (cl.vm >= 0) & destroy[owner]
    cl_state = jnp.where(cancel, CL_FAILED, cl.state)

    return dataclasses.replace(
        dc,
        hosts=dataclasses.replace(
            hosts, free_ram=free_ram, free_bw=free_bw,
            free_storage=free_storage, free_pes=free_pes, valid=valid),
        vms=dataclasses.replace(
            vms, state=vm_state, host=vm_host,
            create_time=vm_create_t, mig_remaining=mig_rem),
        cloudlets=dataclasses.replace(cl, state=cl_state),
        event_fired=dc.event_fired | due,
    )


def apply_autoscaler(dc: DatacenterState) -> DatacenterState:
    """One closed-loop evaluation of the autoscaler (docs/elasticity.md).

    Runs between the dynamic-event pass and provisioning, mirroring the
    oracle's loop position.  Fleet utilization is the integer ratio of
    busy ACTIVE VMs (>= 1 runnable-now cloudlet) over alive (PENDING |
    ACTIVE) VMs; outside the cooldown window, ``util > util_high`` flips
    up to ``scale_step`` lowest-index ``VM_EMPTY`` slots to
    ``VM_PENDING`` (their build-time ``submit_time`` is left untouched —
    the provisioner's lexsort keys stay loop-invariant, ROADMAP landmine
    #2) and ``util < util_low`` destroys up to ``scale_step``
    highest-index *drained* VMs (alive, no unfinished cloudlet assigned,
    not mid-migration) with exact ``EV_VM_DESTROY`` semantics.  A spot
    track with ``price_sensitivity > 0`` vetoes scale-ups while the
    current price exceeds the sensitivity.  Actions fire only while any
    ``CL_CREATED`` cloudlet exists, so a quiesced lane is a bit-exact
    fixed point (post-quiescence scan steps stay no-ops).  With no
    action due this whole pass is a bit-exact identity.
    """
    hosts, vms, cl = dc.hosts, dc.vms, dc.cloudlets
    sc = dc.scaler
    nv = vms.req_pes.shape[0]
    nh = hosts.num_pes.shape[0]

    alive = alive_mask(vms)
    fleet = alive_fleet(vms)
    owner = jnp.clip(cl.vm, 0, nv - 1)
    assigned = (cl.state == CL_CREATED) & (cl.vm >= 0)
    n_assigned = jax.ops.segment_sum(assigned.astype(jnp.int32), owner,
                                     num_segments=nv)
    current = assigned & (cl.submit_time <= dc.time) & (cl.remaining > 0.0)
    n_current = jax.ops.segment_sum(current.astype(jnp.int32), owner,
                                    num_segments=nv)
    busy = (vms.state == VM_ACTIVE) & (n_current > 0)
    # integer ratio — engine f32 and oracle f64 round the same small-int
    # quotients identically for watermark comparisons on coarse grids
    util = (jnp.sum(busy.astype(jnp.int32)).astype(jnp.float32)
            / jnp.maximum(fleet, 1).astype(jnp.float32))
    work_exists = jnp.any(cl.state == CL_CREATED)
    ready = (dc.time - sc.last_action) >= sc.cooldown
    price = market.spot_price_at(sc, dc.time)
    price_ok = ((sc.spot_enabled == 0) | (sc.price_sensitivity <= 0.0)
                | (price <= sc.price_sensitivity))
    want_up = (work_exists & ready & (util > sc.util_high)
               & (fleet < sc.max_fleet) & price_ok)
    want_down = (~want_up & work_exists & ready & (util < sc.util_low)
                 & (fleet > sc.min_fleet))

    # ---- scale-up: lowest-index EMPTY slots -> PENDING --------------------
    empty = vms.state == VM_EMPTY
    up_quota = jnp.minimum(sc.scale_step, sc.max_fleet - fleet)
    create = (want_up & empty
              & (jnp.cumsum(empty.astype(jnp.int32)) <= up_quota))
    n_up = jnp.sum(create.astype(jnp.int32))

    # ---- scale-down: highest-index drained VMs, EV_VM_DESTROY semantics ---
    drained = alive & (n_assigned == 0) & (vms.mig_remaining <= 0.0)
    down_quota = jnp.minimum(sc.scale_step, fleet - sc.min_fleet)
    rank_hi = jnp.cumsum(drained.astype(jnp.int32)[::-1])[::-1]
    destroy = want_down & drained & (rank_hi <= down_quota)
    n_down = jnp.sum(destroy.astype(jnp.int32))

    returning = destroy & (vms.state == VM_ACTIVE) & (vms.host >= 0)
    hclip = jnp.clip(vms.host, 0, nh - 1)
    w = returning.astype(jnp.float32)
    give = lambda pool, x: pool.at[hclip].add(w * x)
    reserve = jnp.where(dc.reserve_pes == 1,
                        vms.req_pes.astype(jnp.float32), 0.0)
    vm_state = jnp.where(destroy, VM_DESTROYED,
                         jnp.where(create, VM_PENDING, vms.state))
    vm_host = jnp.where(destroy, -1, vms.host)
    mig_rem = jnp.where(destroy, 0.0, vms.mig_remaining)
    # drained VMs carry no unfinished cloudlets, so this cancel is a
    # no-op — kept verbatim from apply_due_events for exact mirroring
    cancel = (cl.state == CL_CREATED) & (cl.vm >= 0) & destroy[owner]
    cl_state = jnp.where(cancel, CL_FAILED, cl.state)

    acted = (n_up + n_down) > 0
    return dataclasses.replace(
        dc,
        hosts=dataclasses.replace(
            hosts,
            free_ram=give(hosts.free_ram, vms.ram),
            free_bw=give(hosts.free_bw, vms.bw),
            free_storage=give(hosts.free_storage, vms.size),
            free_pes=give(hosts.free_pes, reserve)),
        vms=dataclasses.replace(vms, state=vm_state, host=vm_host,
                                mig_remaining=mig_rem),
        cloudlets=dataclasses.replace(cl, state=cl_state),
        scaler=dataclasses.replace(
            sc,
            last_action=jnp.where(acted, dc.time, sc.last_action),
            up_count=sc.up_count + n_up,
            down_count=sc.down_count + n_down),
    )


def _next_event_deltas(dc: DatacenterState, rates: jnp.ndarray):
    """(dt_finish, finish_dt[C], arrive) — the event-queue head, split.

    Completions are *deltas* (``remaining / rate``) so a completion 1e-6 s
    away still advances the state even when ``time + dt == time`` in f32.
    Arrivals (cloudlet / VM submit times) are the *absolute* table values:
    when an arrival wins the queue the clock is set to that exact f32
    value rather than ``time + (arrive - time)`` — whose rounding can land
    one ulp short and spawn a phantom micro-step the f64 oracle never
    takes.
    """
    cl, vms = dc.cloudlets, dc.vms
    finish_dt = jnp.where(rates > 0.0, cl.remaining / jnp.maximum(rates,
                                                                  1e-30), INF)
    dt_finish = jnp.min(finish_dt, initial=INF)

    future_cl = (cl.state == CL_CREATED) & (cl.submit_time > dc.time)
    arr_cl = jnp.min(jnp.where(future_cl, cl.submit_time, INF), initial=INF)

    future_vm = (vms.state == VM_PENDING) & (vms.submit_time > dc.time)
    arr_vm = jnp.min(jnp.where(future_vm, vms.submit_time, INF), initial=INF)

    return dt_finish, finish_dt, jnp.minimum(arr_cl, arr_vm)


def _dynamic_deltas(dc: DatacenterState, trig_next: jnp.ndarray):
    """(dt, arrive) — earliest dynamic wakeup.

    ``dt``: migration-copy completions (deltas, like cloudlet remaining)
    and a zero-dt chain event when another migration already triggers on
    the post-migration state (same-instant cascades).  ``arrive``: the
    earliest pending event-table time (absolute, exact)."""
    if dc.events.shape[0]:
        ev_t, ev_k = dc.events[:, 0], dc.events[:, 1]
        pend = (~dc.event_fired) & (ev_k != float(EV_NONE))
        arr_ev = jnp.min(jnp.where(pend & (ev_t > dc.time), ev_t, INF),
                         initial=INF)
    else:
        arr_ev = INF
    mig = dc.vms.mig_remaining
    dt_mig = jnp.min(jnp.where(mig > 0.0, mig, INF), initial=INF)
    dt_trig = jnp.where(trig_next, jnp.float32(0.0), INF)
    return jnp.minimum(dt_mig, dt_trig), arr_ev


def _occupancy(dc: DatacenterState) -> jnp.ndarray:
    """i32[H] — placed ACTIVE VMs per host (loop-invariant inside a leap
    window: no provisioning, migration, or destroy can occur there)."""
    nh = dc.hosts.num_pes.shape[0]
    placed = (dc.vms.state == VM_ACTIVE) & (dc.vms.host >= 0)
    return jnp.zeros((nh,), jnp.int32).at[
        jnp.clip(dc.vms.host, 0, nh - 1)].add(placed.astype(jnp.int32))


def _drain_safe(pre: DatacenterState, post: DatacenterState,
                occ: jnp.ndarray, *, networked: bool) -> jnp.ndarray:
    """bool[] — the commit ``pre -> post`` cannot change any surviving rate.

    Completions reshuffle the two-level shares in exactly two ways:

      * VM-level reshare — a VM running more task units than virtual PEs
        re-splits its capacity when one finishes (TIME divides by
        ``max(n, pes)``; SPACE promotes a queued unit into the freed PE).
        Safe only when ``n_runnable <= req_pes`` (the divisor is pinned to
        ``pes`` and every unit already holds a PE, so survivors keep their
        exact f32 rate).
      * eligibility flip — without ``reserve_pes`` a VM that drains its
        last runnable unit stops competing for host capacity
        (``vm_has_work``), changing its host's level-1 split.  Safe when
        the VM keeps work, PEs are reserved (eligibility is then
        placement-only), or the VM is alone on its host (the level-1
        segments of other hosts are untouched and its own rates are
        already zero).

    Conservative: False forgoes a leap, never corrupts one.
    """
    nv = pre.vms.req_pes.shape[0]
    nh = pre.hosts.num_pes.shape[0]
    owner = jnp.clip(pre.cloudlets.vm, 0, nv - 1)
    run_pre = scheduling.cloudlet_runnable(pre, networked=networked)
    run_post = scheduling.cloudlet_runnable(post, networked=networked)
    n_pre = jax.ops.segment_sum(run_pre.astype(jnp.int32), owner,
                                num_segments=nv)
    n_post = jax.ops.segment_sum(run_post.astype(jnp.int32), owner,
                                 num_segments=nv)
    pes = jnp.maximum(pre.vms.req_pes, 1)
    placed = (pre.vms.state == VM_ACTIVE) & (pre.vms.host >= 0)
    alone = placed & (occ[jnp.clip(pre.vms.host, 0, nh - 1)] == 1)
    keeps_work = (n_post >= 1) | (pre.reserve_pes == 1) | alone
    safe = (n_post == n_pre) | ((n_pre <= pes) & keeps_work)
    return jnp.all(safe)


def _interval_probes(state: DatacenterState, rates: jnp.ndarray
                     ) -> tuple[jnp.ndarray, ...]:
    """(util, fleet, backlog, busy_hosts) observed over the interval a
    commit is about to book — all derived from the post-passes state and
    its fixed ``rates``, which are constant until the next event.  The
    exact same f32 arithmetic serves the ``step`` commit and the leap
    body (on frozen re-masked rates, elementwise-equal by the leap
    gate), so the metrics plane inherits leap-on/off bitwise parity.
    """
    cl = state.cloudlets
    nv = state.vms.req_pes.shape[0]
    nh = state.hosts.num_pes.shape[0]
    host_mips = jnp.sum(jnp.where(state.hosts.valid,
                                  state.hosts.capacity_mips, 0.0))
    util = jnp.sum(rates) / jnp.maximum(host_mips, 1e-30)
    fleet = alive_fleet(state.vms).astype(jnp.float32)
    # queue pressure: submitted, unfinished, but drawing no MIPS (under a
    # topology this includes staging cloudlets — documented)
    backlog = jnp.sum(((cl.state == CL_CREATED)
                       & (cl.submit_time <= state.time)
                       & (cl.remaining > 0.0)
                       & (rates <= 0.0)).astype(jnp.int32))
    hidx = jnp.clip(state.vms.host[jnp.clip(cl.vm, 0, nv - 1)], 0, nh - 1)
    busy = (jax.ops.segment_sum((rates > 0.0).astype(jnp.int32), hidx,
                                num_segments=nh) > 0).astype(jnp.float32)
    return util, fleet, backlog, busy


def _sla_bound(state: DatacenterState) -> jnp.ndarray:
    """f32[C] per-cloudlet SLA response bound — the
    ``experiments.sla_violations`` formula with the plane's factor."""
    nv = state.vms.req_pes.shape[0]
    owner = jnp.clip(state.cloudlets.vm, 0, nv - 1)
    ideal = state.cloudlets.length / jnp.maximum(
        state.vms.req_mips[owner], 1e-30)
    return state.metrics.sla_factor * ideal


def _probe_commit(pre: DatacenterState, new: DatacenterState,
                  rates: jnp.ndarray, host_watts: jnp.ndarray, dt,
                  frates, was_done) -> DatacenterState:
    """Book one ``step`` commit into the metrics plane (``probed=True``).

    ``pre`` is the post-passes state whose ``rates`` the commit used
    (observables are constant on [pre.time, new.time)); ``new`` is the
    committed state.  ``was_done`` is the DONE mask at *step entry* so
    retirements via ``advance_phases`` (STAGE_OUT drains completing at
    the top of the step) are booked exactly once too.
    """
    util, fleet, backlog, busy = _interval_probes(pre, rates)
    m = metrics.accrue_interval(
        pre.metrics, t0=pre.time, t1=new.time, util=util,
        watts=jnp.sum(host_watts), fleet=fleet, backlog=backlog,
        flows=(jnp.sum((frates > 0.0).astype(jnp.int32))
               if frates is not None else jnp.int32(0)),
        busy_hosts=busy, dt=dt)
    ncl = new.cloudlets
    m = metrics.fill_retirement(
        m, newly=(ncl.state == CL_DONE) & ~was_done,
        finish=ncl.finish_time, submit=ncl.submit_time,
        start=ncl.start_time, bound=_sla_bound(pre))
    return dataclasses.replace(new, metrics=m)


def _leap_window(pre: DatacenterState, new: DatacenterState,
                 rates: jnp.ndarray, active, dt_arr, dt_other, arrive,
                 trig_next, mig_done, budget, horizon,
                 next_arrival=None, *,
                 dynamic: bool, networked: bool, streaming: bool = False,
                 elastic: bool = False, probed: bool = False
                 ) -> tuple[DatacenterState, jnp.ndarray]:
    """Commit further queued events cheaply while no decision can intervene.

    ``pre`` is the post-passes state whose ``rates`` the main commit used;
    ``new`` is the state after that commit.  While the window gate holds,
    rates are *loop-invariant modulo masking*: the next event is a pure
    completion/copy countdown and its commit arithmetic — the exact f32
    ops of ``step``'s commit, on frozen rates — lands bit-for-bit where a
    full ``step`` would.  Decision points close the window:

      * an arrival (cloudlet/VM submit, event-table time) at or before the
        candidate clock — provisioning/events must run,
      * a completion failing ``_drain_safe`` — rates would reshuffle,
      * a migration trigger becoming possible — lanes leap only with the
        policy OFF, or THRESHOLD with no host over-threshold (utilization
        under frozen, shrinking rates is non-increasing, so no host can
        *become* overloaded mid-window; DRAIN triggers on *under*-loaded
        hosts, which completions can create, so DRAIN lanes never leap),
      * a migration copy finishing — the VM resumes and rates grow (the
        copy completion itself commits, then the window closes),
      * an enabled network topology (transfer wakes are decision points).

    No sort runs in here — deltas are elementwise mins and segment sums,
    so every lexsort key stays loop-invariant (ROADMAP landmine #2).
    Returns ``(state, extra_events_committed)``.
    """
    r0 = rates
    occ = _occupancy(new)
    gate = active & (dt_arr > dt_other) & (arrive > new.time)
    gate &= _drain_safe(pre, new, occ, networked=networked)
    if streaming:
        # a backlogged arrival (submit in the past, capacity-blocked) is
        # invisible to ``arrive`` — but any completion in the window
        # frees a slot and makes its admission due, so the window must
        # not open at all while a backlog exists
        gate &= next_arrival > new.time
    if dynamic:
        gate &= ~trig_next & ~jnp.any(mig_done)
        cl1 = new.cloudlets
        r1 = jnp.where((cl1.state == CL_CREATED) & (cl1.remaining > 0.0),
                       r0, 0.0)
        util = energy.host_utilization(new, r1)
        loaded = new.hosts.valid & (occ > 0)
        gate &= ((new.mig_policy == MIG_OFF)
                 | ((new.mig_policy == MIG_THRESHOLD)
                    & ~jnp.any(loaded & (util > new.mig_threshold))))
    if networked:
        gate &= new.net.enabled == 0
    if elastic:
        # the autoscaler evaluates at every event and spot boundaries are
        # events of their own — both are decision points, so enabled
        # elastic lanes never leap (disabled ones still do)
        gate &= (new.scaler.enabled == 0) & (new.scaler.spot_enabled == 0)
    budget = (jnp.int32(2 ** 30) if budget is None
              else jnp.asarray(budget, jnp.int32))
    horizon = (jnp.float32(INF) if horizon is None
               else jnp.minimum(jnp.asarray(horizon, jnp.float32), INF))

    def cond(carry):
        state, k, going = carry
        return going & (k < budget) & (state.time < horizon)

    def body(carry):
        state, k, going = carry
        cl = state.cloudlets
        # frozen rates, re-masked: survivors keep their exact f32 rate
        # (guaranteed by _drain_safe), finished/zeroed ones drop out
        r = jnp.where((cl.state == CL_CREATED) & (cl.remaining > 0.0),
                      r0, 0.0)
        dt_fin, finish_dt, arr = _next_event_deltas(state, r)
        dt_o = dt_fin
        if dynamic:
            dt_dyn, arr_ev = _dynamic_deltas(state, jnp.bool_(False))
            dt_o = jnp.minimum(dt_o, dt_dyn)
            arr = jnp.minimum(arr, arr_ev)
        if streaming:
            # the stream's next unadmitted arrival is an event too: the
            # window closes before it (a backlogged arrival — submit in
            # the past, capacity-blocked — creates no event; completions
            # wake the admission pass in the driver instead)
            arr = jnp.minimum(arr, jnp.where(next_arrival > state.time,
                                             next_arrival, INF))
        d_arr = jnp.where(arr < INF, arr - state.time, INF)
        dt = jnp.minimum(dt_o, d_arr)
        act = dt < INF
        dt = jnp.where(act, dt, 0.0)
        t_next = state.time + dt
        # ---- the exact commit arithmetic of step() ------------------------
        snap = dt * (1.0 + 1e-5) + 1e-9
        fin = (cl.state == CL_CREATED) & (r > 0.0) & (finish_dt <= snap)
        executed = r * dt
        remaining = jnp.where(fin, 0.0,
                              jnp.maximum(cl.remaining - executed, 0.0))
        nv = state.vms.req_pes.shape[0]
        nh = state.hosts.num_pes.shape[0]
        mips_pe = state.hosts.mips_per_pe[jnp.clip(
            state.vms.host[jnp.clip(cl.vm, 0, nv - 1)], 0, nh - 1)]
        pe_seconds = jnp.sum(executed / jnp.maximum(mips_pe, 1e-30))
        moved_mb = jnp.sum(jnp.where(fin, cl.file_size + cl.output_size,
                                     0.0))
        host_watts = energy.step_power(state, r)
        vms = state.vms
        stop = jnp.bool_(False)
        if dynamic:
            mig = vms.mig_remaining
            m_done = (mig > 0.0) & (mig <= snap)
            vms = dataclasses.replace(
                vms, mig_remaining=jnp.where(
                    m_done, 0.0,
                    jnp.where(mig > 0.0, jnp.maximum(mig - dt, 0.0), mig)))
            stop = jnp.any(m_done)      # VM resumes -> rates grow -> close
        cand = dataclasses.replace(
            state,
            hosts=dataclasses.replace(
                state.hosts,
                energy_j=state.hosts.energy_j + host_watts * dt),
            vms=vms,
            cloudlets=dataclasses.replace(
                cl, remaining=remaining,
                finish_time=jnp.where(fin, t_next, cl.finish_time),
                state=jnp.where(fin, CL_DONE, cl.state)),
            acct=dataclasses.replace(
                state.acct,
                cpu_cost=(state.acct.cpu_cost
                          + state.rates.cost_per_cpu_sec * pe_seconds),
                bw_cost=(state.acct.bw_cost
                         + state.rates.cost_per_bw * moved_mb)),
            time=t_next,
        )
        if probed:
            # the exact probe arithmetic of step()'s commit, on the
            # frozen re-masked rates (elementwise-equal by the gate) —
            # metrics stay bitwise under leap-on/off
            cand = _probe_commit(state, cand, r, host_watts, dt, None,
                                 cl.state == CL_DONE)
        do = (going & act & (d_arr > dt_o) & (arr > t_next)
              & _drain_safe(state, cand, occ, networked=networked))
        nxt = jax.tree.map(lambda a, b: jnp.where(do, a, b), cand, state)
        return nxt, k + do.astype(jnp.int32), do & ~stop

    out, extra, _ = jax.lax.while_loop(cond, body,
                                       (new, jnp.int32(0), gate))
    return out, extra


def step(dc: DatacenterState, *, provision_policy=FIRST_FIT,
         dynamic: bool = True, networked: bool = False,
         elastic: bool = False, leap: bool = False,
         leap_budget=None, leap_horizon=None,
         streaming: bool = False, next_arrival=None,
         probed: bool = False
         ) -> tuple[DatacenterState, StepRecord]:
    """Process exactly one simulation event (pure; jit/vmap/scan-safe).

    Takes and returns an *unbatched* ``DatacenterState`` (leaves [H]/[V]/
    [C]/scalar); batching is layered on by the callers' vmap.  At
    quiescence (no runnable work, no future submissions, no pending
    events) ``step`` is an exact fixed point — it returns the state
    bit-for-bit unchanged with ``StepRecord.active == False`` — which is
    what makes padded batch lanes and early-finishing lanes inert.

    Order inside an event instant mirrors CloudSim: (0) pending dynamic
    events due now apply (``apply_due_events``), (1) the VMProvisioner
    places VMs whose submission is due — including VMs just evicted by a
    host failure, (1b) due staging-phase transitions run
    (``network.advance_phases`` — arm input transfers, promote staged-in
    cloudlets to CPU, complete staged-out ones), (2)
    ``updateVMsProcessing`` — the two-level share computation — fixes
    every rate (MIPS), (2b) the migration policy may move one VM and
    rates are recomputed (core/migration.py), (2c) transfer flow rates
    (MB/s) are fixed (``network.flow_rates``), (3) the clock jumps ``dt``
    seconds to the earliest completion/arrival/event/transfer wakeup,
    (4) progress (rate * dt MI), completions, migration-copy and
    transfer countdowns, market costs ($), and per-host energy
    (watts * dt J — rates are constant over the interval, so exact) are
    committed; compute-finished cloudlets under an enabled topology arm
    their output transfer instead of completing.

    ``dynamic``, ``networked``, and ``elastic`` are *static* flags: False
    compiles the pre-dynamic / pre-network / pre-elastic program for
    scenarios that carry none of them — the public runners auto-detect
    via ``wants_dynamic`` / ``wants_network`` / ``wants_elastic``.
    ``elastic`` adds the closed-loop pass (``apply_autoscaler``, between
    the event pass and provisioning so scale-ups provision in the same
    instant), spot-segment boundaries as absolute arrival events, and
    the exact spot accrual ``spot_cost += price(t) * fleet * dt``.

    ``streaming`` (static, ``run_stream`` lanes only): the cloudlet axis
    is a recycled active-slot *window*, so (a) the space-shared FCFS rank
    switches to the admission-counter form (scheduling.vm_level_rates)
    and (b) ``next_arrival`` — the submit time of the stream's next
    unadmitted arrival, or INF — joins the event queue as an absolute
    arrival so the clock lands exactly on it (admission itself happens in
    the driver, between steps).  ``streaming=False`` compiles today's
    resident program bit-for-bit.

    ``probed`` (static, auto-detected via ``wants_probes``): collect the
    O(K) metrics plane (core/metrics.py) alongside the commit — bucketed
    timelines, retirement histograms, SLA watermarks.  ``probed=False``
    never touches ``dc.metrics`` and compiles the unprobed program
    unchanged; ``probed=True`` on a lane whose plane is disabled
    (``metrics.enabled == 0``) is a bitwise identity on it.
    """
    if probed:
        # DONE mask at step *entry*: retirement probes below must also
        # catch completions made by advance_phases (STAGE_OUT drains)
        was_done = dc.cloudlets.state == CL_DONE
    # Every pass below is a bit-exact identity when its trigger predicate
    # is False (verified pass by pass; the quiescence fixed point depends
    # on it), so each can sit behind a runtime lax.cond: quiesced lanes and
    # steps with nothing due skip the pass body instead of paying for the
    # full gather/scatter/scan machinery.  Under vmap the conds lower to
    # selects — both branches run — so batched callers lose nothing; the
    # unbatched while_loop runners (and lax.map inner loops) get real
    # branches.
    # Each pass runs under a ``jax.named_scope`` (events, autoscaler,
    # provision, phases, rates, migration, flows, horizon, commit, probes,
    # leap, record): op metadata only, so a device trace can sum time per
    # pass (docs/observability.md).
    if dynamic and dc.events.shape[0]:
        with jax.named_scope("events"):
            ev_k = dc.events[:, 1].astype(jnp.int32)
            due_any = jnp.any((~dc.event_fired) & (ev_k != EV_NONE)
                              & (dc.events[:, 0] <= dc.time))
            dc = jax.lax.cond(due_any, apply_due_events, lambda d: d, dc)
    if elastic:
        with jax.named_scope("autoscaler"):
            dc = jax.lax.cond(dc.scaler.enabled == 1, apply_autoscaler,
                              lambda d: d, dc)
    with jax.named_scope("provision"):
        pending_due = jnp.any((dc.vms.state == VM_PENDING)
                              & (dc.vms.submit_time <= dc.time))
        dc = jax.lax.cond(pending_due,
                          lambda d: provision_pending(d, provision_policy),
                          lambda d: d, dc)
    if networked:
        with jax.named_scope("phases"):
            dc = jax.lax.cond(dc.net.enabled == 1, network.advance_phases,
                              lambda d: d, dc)
    with jax.named_scope("rates"):
        rates = scheduling.cloudlet_rates(dc, networked=networked,
                                          streaming=streaming)
    if dynamic:
        with jax.named_scope("migration"):
            mig0 = migration.select_migration(dc, rates, networked=networked)

            def _mig_apply(op):
                d, r = op
                d2 = migration.apply_selected(d, mig0)
                r2 = scheduling.cloudlet_rates(d2, networked=networked,
                                               streaming=streaming)
                t2 = migration.select_migration(
                    d2, r2, networked=networked).trigger
                return d2, r2, t2

            def _mig_skip(op):
                # no-trigger apply is an identity and re-derives identical
                # rates/trigger, so the skip branch is bitwise equivalent
                d, r = op
                return d, r, jnp.bool_(False)

            dc, rates, trig_next = jax.lax.cond(mig0.trigger, _mig_apply,
                                                _mig_skip, (dc, rates))
    if networked:
        with jax.named_scope("flows"):
            def _net_on(d):
                fr = network.flow_rates(d)
                dtn, fdt = network.wake_deltas(d, fr)
                return fr, dtn, fdt

            def _net_off(d):
                # flow_rates/wake_deltas of a disabled topology, verbatim
                nc = d.cloudlets.remaining.shape[0]
                return (jnp.zeros((nc,), jnp.float32), jnp.float32(INF),
                        jnp.full((nc,), INF, jnp.float32))

            frates, dt_net, flow_dt = jax.lax.cond(dc.net.enabled == 1,
                                                   _net_on, _net_off, dc)

    with jax.named_scope("horizon"):
        dt_other, finish_dt, arrive = _next_event_deltas(dc, rates)
        if dynamic:
            dt_dyn, arr_ev = _dynamic_deltas(dc, trig_next)
            dt_other = jnp.minimum(dt_other, dt_dyn)
            arrive = jnp.minimum(arrive, arr_ev)
        if networked:
            dt_other = jnp.minimum(dt_other, dt_net)
        if streaming:
            # pending stream arrival — absolute, exact; a backlogged one
            # (submit <= now, window full) is no event: a completion frees a
            # slot first and run_stream's admission pass picks it up
            arrive = jnp.minimum(arrive, jnp.where(next_arrival > dc.time,
                                                   next_arrival, INF))
        if elastic:
            # spot-segment boundaries are absolute arrivals (exact f32 table
            # values), so the piecewise-constant accrual below is exact;
            # INF while the track is disabled, leaving ``arrive`` untouched
            arrive = jnp.minimum(arrive,
                                 market.next_spot_boundary(dc.scaler, dc.time))
        dt_arr = jnp.where(arrive < INF, arrive - dc.time, INF)
        dt = jnp.minimum(dt_other, dt_arr)
        active = dt < INF
        dt = jnp.where(active, dt, 0.0)
        # arrivals win ties so the clock lands on the exact submitted time
        t_next = jnp.where(active,
                           jnp.where(dt_arr <= dt_other, arrive, dc.time + dt),
                           dc.time)

    with jax.named_scope("commit"):
        cl = dc.cloudlets
        executed = rates * dt
        # completion snap band, shared by every countdown in this commit and
        # mirrored by the oracle's _SNAP_REL/_SNAP_ABS — keep in sync
        snap = dt * (1.0 + 1e-5) + 1e-9
        # the argmin task(s) finish *by construction* — immune to f32 rounding
        finished = ((cl.state == CL_CREATED)
                    & (rates > 0.0)
                    & (finish_dt <= snap))
        remaining = jnp.where(finished, 0.0,
                              jnp.maximum(cl.remaining - executed, 0.0))

        started = (rates > 0.0) & (cl.start_time < 0.0)
        start_time = jnp.where(started, dc.time, cl.start_time)
        net_phase, net_lat = cl.net_phase, cl.net_lat
        net_rem = cl.net_remaining
        if networked:
            # enabled lanes: compute completion arms the output transfer
            # instead of finishing (NET_STAGE_OUT; ``advance_phases`` marks
            # CL_DONE once it drains); disabled lanes keep old semantics.
            enabled = dc.net.enabled == 1
            done_now = finished & ~enabled
            arm_out = finished & enabled
            # transfer countdowns — the same snap band as completions, so
            # the wake event lands on the same step as the f64 oracle's
            lat_active = network.staging_mask(dc) & (cl.net_lat > 0.0)
            lat_done = lat_active & (cl.net_lat <= snap)
            net_lat = jnp.where(
                lat_done, 0.0,
                jnp.where(lat_active, jnp.maximum(cl.net_lat - dt, 0.0),
                          cl.net_lat))
            xfer_done = (frates > 0.0) & (flow_dt <= snap)
            net_rem = jnp.where(
                xfer_done, 0.0,
                jnp.where(frates > 0.0,
                          jnp.maximum(cl.net_remaining - frates * dt, 0.0),
                          cl.net_remaining))
            # a compute-finished cloudlet is in NET_RUN — never also a flow —
            # so arming cannot clash with the countdowns above
            net_phase = jnp.where(arm_out, NET_STAGE_OUT, cl.net_phase)
            net_lat = jnp.where(arm_out, network.stage_latency(dc), net_lat)
            net_rem = jnp.where(arm_out, cl.output_size, net_rem)
        else:
            done_now = finished
        finish_time = jnp.where(done_now, t_next, cl.finish_time)
        state = jnp.where(done_now, CL_DONE, cl.state)

        # ---- market accounting (§3.3) ------------------------------------
        nv = dc.vms.req_pes.shape[0]
        nh = dc.hosts.num_pes.shape[0]
        host_of_cl = dc.vms.host[jnp.clip(cl.vm, 0, nv - 1)]
        mips_pe = dc.hosts.mips_per_pe[jnp.clip(host_of_cl, 0, nh - 1)]
        pe_seconds = jnp.sum(executed / jnp.maximum(mips_pe, 1e-30))
        cpu_cost = dc.acct.cpu_cost + dc.rates.cost_per_cpu_sec * pe_seconds
        # networked lanes bill per drained transfer below
        # (``transfer_accounting``; ``done_now`` excludes them) — same total
        # per finished task
        moved_mb = jnp.sum(jnp.where(done_now, cl.file_size + cl.output_size,
                                     0.0))
        bw_cost = dc.acct.bw_cost + dc.rates.cost_per_bw * moved_mb

        # ---- energy accounting (core/energy.py) --------------------------
        # Rates are constant on [time, time+dt), so power is too: the exact
        # integral of the piecewise-constant power timeline is watts * dt per
        # event (the trapezoidal rule with equal endpoints).  At quiescence
        # dt == 0, so energy_j is a bit-exact fixed point like everything else.
        host_watts = energy.step_power(dc, rates)              # f32[H]
        energy_j = dc.hosts.energy_j + host_watts * dt

        transferred_mb = dc.net_transferred_mb
        if networked:
            # drained transfers book their whole size on this (active) step
            xfer_energy, moved = network.transfer_accounting(dc, xfer_done)
            energy_j = energy_j + xfer_energy
            bw_cost = bw_cost + dc.rates.cost_per_bw * moved
            transferred_mb = transferred_mb + moved

        vms = dc.vms
        if dynamic:
            # migration copy countdown — a delta like cloudlet ``remaining``,
            # with the same completion snap band so the resume event lands on
            # the same step on both the engine and the f64 oracle.
            mig = vms.mig_remaining
            mig_done = (mig > 0.0) & (mig <= snap)
            mig_rem = jnp.where(mig_done, 0.0,
                                jnp.where(mig > 0.0,
                                          jnp.maximum(mig - dt, 0.0), mig))
            vms = dataclasses.replace(vms, mig_remaining=mig_rem)

        scaler = dc.scaler
        if elastic:
            # spot spend: price and alive fleet are constant on [time, time+dt)
            # (fleet only changes inside the passes above), so price * fleet *
            # dt is the exact integral — like energy.  Zero-price when the
            # track is disabled, so the accrual is a bit-exact identity then.
            spot_rate = (market.spot_price_at(scaler, dc.time)
                         * alive_fleet(dc.vms).astype(jnp.float32))
            scaler = dataclasses.replace(
                scaler, spot_cost=scaler.spot_cost + spot_rate * dt)

        new = dataclasses.replace(
            dc,
            hosts=dataclasses.replace(dc.hosts, energy_j=energy_j),
            vms=vms,
            cloudlets=dataclasses.replace(
                cl, remaining=remaining, start_time=start_time,
                finish_time=finish_time, state=state, net_phase=net_phase,
                net_lat=net_lat, net_remaining=net_rem),
            acct=dataclasses.replace(dc.acct, cpu_cost=cpu_cost,
                                     bw_cost=bw_cost),
            time=t_next,
            net_transferred_mb=transferred_mb,
            scaler=scaler,
        )

    if probed:
        with jax.named_scope("probes"):
            new = _probe_commit(dc, new, rates, host_watts, dt,
                                frates if networked else None, was_done)

    n_events = active.astype(jnp.int32)
    if leap:
        with jax.named_scope("leap"):
            new, extra = _leap_window(
                dc, new, rates, active, dt_arr, dt_other, arrive,
                trig_next if dynamic else None,
                mig_done if dynamic else None,
                leap_budget, leap_horizon,
                next_arrival if streaming else None,
                dynamic=dynamic, networked=networked, streaming=streaming,
                elastic=elastic, probed=probed)
            n_events = n_events + extra

    with jax.named_scope("record"):
        host_mips = jnp.sum(jnp.where(dc.hosts.valid,
                                      dc.hosts.capacity_mips, 0.0))
        rec = StepRecord(
            time=new.time,
            n_running=jnp.sum((rates > 0.0).astype(jnp.int32)),
            n_done=jnp.sum((new.cloudlets.state == CL_DONE).astype(jnp.int32)),
            utilization=jnp.sum(rates) / jnp.maximum(host_mips, 1e-30),
            watts=jnp.sum(host_watts),
            active=active,
            n_migrating=jnp.sum((new.vms.mig_remaining > 0.0
                                 ).astype(jnp.int32)),
            migrations=new.mig_count,
            hosts_down=jnp.sum((~new.hosts.valid
                                & (new.hosts.num_pes > 0)).astype(jnp.int32)),
            transferred_mb=new.net_transferred_mb,
            n_flows=(jnp.sum((frates > 0.0).astype(jnp.int32)) if networked
                     else jnp.int32(0)),
            n_events=n_events,
            fleet=alive_fleet(new.vms),
            spot_cost=new.scaler.spot_cost,
        )
    return new, rec


def wants_dynamic(dc: DatacenterState) -> bool:
    """True when the scenario carries dynamic behaviour (events table,
    a migration policy, or an in-flight migration).  Host-side dispatch
    helper — on traced inputs it conservatively answers True.  Accepts
    unbatched ([E, 4]) and batched ([B, E, 4]) states: the event axis
    is always second-to-last."""
    if dc.events.shape[-2] > 0:
        return True
    try:
        return (bool(np.any(np.asarray(dc.mig_policy) != 0))
                or bool(np.any(np.asarray(dc.vms.mig_remaining) > 0.0)))
    except Exception:           # tracer — cannot inspect; take the safe path
        return True


def wants_elastic(dc: DatacenterState) -> bool:
    """True when the scenario carries an enabled autoscaler or spot track.
    Host-side dispatch helper like ``wants_dynamic`` — on traced inputs
    it conservatively answers True.  Accepts unbatched and batched
    states (the fields are scalars / [B] vectors either way)."""
    try:
        sc = dc.scaler
        return (bool(np.any(np.asarray(sc.enabled) != 0))
                or bool(np.any(np.asarray(sc.spot_enabled) != 0)))
    except Exception:           # tracer — cannot inspect; take the safe path
        return True


def wants_probes(dc: DatacenterState) -> bool:
    """True when any lane carries an enabled metrics plane
    (core/metrics.py).  Host-side dispatch helper like ``wants_dynamic``
    — on traced inputs it conservatively answers True.  Accepts
    unbatched and batched states (``enabled`` is scalar / [B])."""
    try:
        return bool(np.any(np.asarray(dc.metrics.enabled) != 0))
    except Exception:           # tracer — cannot inspect; take the safe path
        return True


@partial(jax.jit, static_argnames=("max_steps", "provision_policy",
                                   "dynamic", "networked", "elastic",
                                   "leap", "probed"))
def _run(dc: DatacenterState, *, max_steps: int, horizon: float,
         provision_policy: int, dynamic: bool,
         networked: bool, elastic: bool, leap: bool,
         probed: bool) -> tuple[DatacenterState, RunStats]:
    horizon = jnp.minimum(jnp.asarray(horizon, jnp.float32), INF)

    def cond(carry):
        dc, n, _, alive = carry
        return alive & (n < max_steps) & (dc.time < horizon)

    def body(carry):
        dc, n, it, _ = carry
        new, rec = step(dc, provision_policy=provision_policy,
                        dynamic=dynamic, networked=networked,
                        elastic=elastic, leap=leap,
                        leap_budget=jnp.int32(max_steps) - n - 1,
                        leap_horizon=horizon, probed=probed)
        return new, n + rec.n_events, it + 1, rec.active

    out, n, it, _ = jax.lax.while_loop(
        cond, body, (dc, jnp.int32(0), jnp.int32(0), jnp.bool_(True)))
    return out, RunStats(iterations=it, events=n)


def run(dc: DatacenterState, *, max_steps: int = 1_000_000,
        horizon: float = float("inf"), provision_policy: int = FIRST_FIT,
        dynamic: bool | None = None,
        networked: bool | None = None,
        elastic: bool | None = None,
        leap: bool | None = None,
        probed: bool | None = None, stats: bool = False
        ) -> DatacenterState | tuple[DatacenterState, RunStats]:
    """Run the simulation to quiescence with ``lax.while_loop``.

    Terminates when the event queue is empty (no runnable work, no future
    submissions, no pending dynamic events, no in-flight transfers), the
    ``horizon`` (simulated seconds) is passed, or ``max_steps`` events
    fire (a safety net against pathological scenarios).  Returns the
    final ``DatacenterState`` (same leaf shapes as the input; ``time`` is
    the quiescence clock in seconds).  ``dynamic=None`` / ``networked=
    None`` auto-detect via ``wants_dynamic`` / ``wants_network``; pass
    explicit bools when calling under a trace.

    ``leap`` (default on) enables event-horizon batching: when no
    provisioning/migration/network decision can intervene, one loop
    iteration commits a run of queued completions (``_leap_window``) —
    bit-for-bit identical results, fewer iterations.  ``leap=False``
    forces the one-event-per-iteration program (parity tests).

    ``stats=True`` returns ``(final, RunStats)``: the loop's iteration
    count and the events it retired (scalars).  Both settings run the
    same compiled program.  The call is wrapped in the host spans
    ``repro.run`` > ``repro.run.flags`` (the ``wants_*`` detection) and
    ``repro.run.launch`` (the asynchronous call into the program).
    """
    with _span("repro.run"):
        with _span("repro.run.flags"):
            if dynamic is None:
                dynamic = wants_dynamic(dc)
            if networked is None:
                networked = wants_network(dc)
            if elastic is None:
                elastic = wants_elastic(dc)
            if leap is None:
                leap = _LEAP_DEFAULT
            if probed is None:
                probed = wants_probes(dc)
        with _span("repro.run.launch"):
            out, run_stats = _run(dc, max_steps=max_steps, horizon=horizon,
                                  provision_policy=provision_policy,
                                  dynamic=dynamic, networked=networked,
                                  elastic=elastic, leap=leap, probed=probed)
    return (out, run_stats) if stats else out


@partial(jax.jit, static_argnames=("num_steps", "provision_policy",
                                   "dynamic", "networked", "elastic",
                                   "probed"))
def _run_trace(dc: DatacenterState, *, num_steps: int,
               provision_policy: int, dynamic: bool, networked: bool,
               elastic: bool, probed: bool
               ) -> tuple[DatacenterState, StepRecord]:
    def body(dc, _):
        new, rec = step(dc, provision_policy=provision_policy,
                        dynamic=dynamic, networked=networked,
                        elastic=elastic, probed=probed)
        return new, rec

    return jax.lax.scan(body, dc, None, length=num_steps)


def run_trace(dc: DatacenterState, *, num_steps: int,
              provision_policy: int = FIRST_FIT,
              dynamic: bool | None = None,
              networked: bool | None = None,
              elastic: bool | None = None,
              probed: bool | None = None
              ) -> tuple[DatacenterState, StepRecord]:
    """Run exactly ``num_steps`` events via ``lax.scan``, keeping telemetry.

    Returns ``(final state, StepRecord trace)`` where every trace leaf is
    stacked to [num_steps] (times in seconds).  Steps past quiescence are
    no-ops flagged ``active=False`` — the trace stays fixed-shape
    (required for jit) and downstream consumers filter.
    """
    if dynamic is None:
        dynamic = wants_dynamic(dc)
    if networked is None:
        networked = wants_network(dc)
    if elastic is None:
        elastic = wants_elastic(dc)
    if probed is None:
        probed = wants_probes(dc)
    return _run_trace(dc, num_steps=num_steps,
                      provision_policy=provision_policy, dynamic=dynamic,
                      networked=networked, elastic=elastic, probed=probed)


def _lane_dynamic(batch: DatacenterState) -> jnp.ndarray:
    """bool[L] — lanes that can still exhibit dynamic behaviour: a live
    migration policy, an in-flight copy, or unfired event rows.  Purely
    monotone (never flips back on), so once the reduction over live lanes
    goes False the dynamic pass stays off for the rest of the run."""
    lane = jnp.asarray(batch.mig_policy) != MIG_OFF
    lane |= jnp.any(batch.vms.mig_remaining > 0.0, axis=-1)
    if batch.events.shape[-2]:
        kinds = batch.events[..., 1].astype(jnp.int32)
        lane |= jnp.any((~batch.event_fired) & (kinds != EV_NONE), axis=-1)
    return lane


def _lane_elastic(batch: DatacenterState) -> jnp.ndarray:
    """bool[L] — lanes carrying an enabled autoscaler or spot track.
    Constant over the run (the flags never change), hence monotone."""
    return ((jnp.asarray(batch.scaler.enabled) == 1)
            | (jnp.asarray(batch.scaler.spot_enabled) == 1))


def _lane_probed(batch: DatacenterState) -> jnp.ndarray:
    """bool[L] — lanes carrying an enabled metrics plane.  Constant over
    the run, hence monotone: once every live probed lane quiesces the
    dispatch drops to the unprobed step (bitwise-identical for lanes
    this rejects — the probed step never touches a disabled plane)."""
    return jnp.asarray(batch.metrics.enabled) == 1


@partial(jax.jit, static_argnames=("max_steps", "provision_policy",
                                   "dynamic", "networked", "elastic",
                                   "leap", "probed"))
def batched_run(batch: DatacenterState, *, max_steps: int,
                horizon: float = float("inf"),
                provision_policy: int = FIRST_FIT, dynamic: bool = True,
                networked: bool = False, elastic: bool = False,
                leap: bool = _LEAP_DEFAULT,
                probed: bool = False
                ) -> tuple[DatacenterState, RunStats]:
    """Run a batched state (leading lane axis) to quiescence.

    Equivalent to ``vmap(run)`` lane for lane — finished lanes are frozen
    by a per-lane select exactly like vmap's batched while_loop — but the
    loop is engine-level, which buys the *dead-lane early-exit*: each
    iteration reduces ``any(live & lane_dynamic)`` / ``any(live &
    net.enabled)`` over the batch and dispatches (``lax.cond``, real
    branches — the predicates are scalars here) the cheapest step variant
    that is still exact for every live lane.  A fused policy grid where
    only some lanes migrate, or where the dynamic lanes quiesce early,
    stops paying the dynamic/networked tax the moment the last such lane
    drains.  The static variant is bitwise-identical to the dynamic one
    for lanes ``_lane_dynamic`` rejects (no due events, no trigger, no
    copy countdown — each gated pass skips), so switching variants
    mid-run never perturbs results.

    Returns ``(final, RunStats)`` with one counter per lane: loop trips
    while the lane was live, and the events it retired.  The per-lane
    select that freezes finished lanes runs under the named scope
    ``freeze``.
    """
    hor = jnp.minimum(jnp.asarray(horizon, jnp.float32), INF)
    lanes = batch.time.shape[0]

    def _vstep(dyn: bool, net: bool, ela: bool, prb: bool):
        def one(d, bud):
            return step(d, provision_policy=provision_policy, dynamic=dyn,
                        networked=net, elastic=ela, leap=leap,
                        leap_budget=bud, leap_horizon=hor, probed=prb)
        return lambda op: jax.vmap(one)(op[0], op[1])

    def body(carry):
        b, n, it, alive = carry
        live = alive & (n < max_steps) & (b.time < hor)
        bud = jnp.int32(max_steps) - n - 1
        op = (b, bud)
        if not (dynamic or networked or elastic or probed):
            new, rec = _vstep(False, False, False, False)(op)
        else:
            # nested binary dispatch over the *active* static dimensions:
            # each per-step predicate reduces over live lanes, picking the
            # cheapest step variant still exact for every live lane
            need = {}
            if dynamic:
                need["dyn"] = jnp.any(live & _lane_dynamic(b))
            if networked:
                need["net"] = jnp.any(live & (b.net.enabled == 1))
            if elastic:
                need["ela"] = jnp.any(live & _lane_elastic(b))
            if probed:
                need["prb"] = jnp.any(live & _lane_probed(b))

            def dispatch(names, flags):
                if not names:
                    return _vstep(flags.get("dyn", False),
                                  flags.get("net", False),
                                  flags.get("ela", False),
                                  flags.get("prb", False))
                name, rest = names[0], names[1:]
                on = dispatch(rest, {**flags, name: True})
                off = dispatch(rest, {**flags, name: False})
                return lambda o: jax.lax.cond(need[name], on, off, o)

            new, rec = dispatch(list(need), {})(op)
        # freeze finished lanes — the batching rule vmap applies to
        # while_loop, replicated here leaf by leaf
        with jax.named_scope("freeze"):
            sel = lambda a, o: jnp.where(
                live.reshape(live.shape + (1,) * (a.ndim - 1)), a, o)
            b2 = jax.tree.map(sel, new, b)
            n2 = jnp.where(live, n + rec.n_events, n)
            it2 = jnp.where(live, it + 1, it)
            alive2 = jnp.where(live, rec.active, alive)
        return b2, n2, it2, alive2

    def cond(carry):
        b, n, _, alive = carry
        return jnp.any(alive & (n < max_steps) & (b.time < hor))

    zeros = jnp.zeros((lanes,), jnp.int32)
    out, n, it, _ = jax.lax.while_loop(
        cond, body, (batch, zeros, zeros, jnp.ones((lanes,), bool)))
    return out, RunStats(iterations=it, events=n)


# ---------------------------------------------------------------------------
# Streaming arrivals (docs/streaming.md): bounded active-slot window +
# chunked arrival queue.  The cloudlet axis of a streamed lane is the
# *window* size W, not the trace length — a lax.scan over arrival chunks
# admits due arrivals into recycled slots and retires DONE/FAILED ones
# into StreamStats running aggregates + a strided reservoir, so memory is
# O(W + chunk) regardless of how many cloudlets flow through.
# ---------------------------------------------------------------------------
class StreamChunkRecord(NamedTuple):
    """Telemetry emitted once per arrival chunk (``run_stream`` scan ys)."""
    time: jnp.ndarray            # f32[] clock after the chunk drained/handed off
    occupancy: jnp.ndarray       # i32[] in-flight (CL_CREATED) slots now
    peak_occupancy: jnp.ndarray  # i32[] running max occupancy (whole run)
    max_backlog: jnp.ndarray     # i32[] running max due-but-unadmitted rows
    n_retired: jnp.ndarray       # i32[] cumulative DONE cloudlets folded out
    n_failed: jnp.ndarray        # i32[] cumulative FAILED cloudlets folded out
    n_events: jnp.ndarray        # i32[] engine events committed this chunk


def _retire_slot(stats, cl, sid, slot, nv: int):
    """Fold one slot's occupant (if any) into the running aggregates.

    ``sid`` is the arrival id occupying ``slot`` (-1 = never used).  Only
    DONE occupants contribute to the time/work sums; FAILED ones are
    counted.  The reservoir samples arrival ids divisible by the
    build-time stride into row ``sid // stride`` (scatter-dropped when
    out of range) — the f64 oracle reproduces the identical subset.
    """
    done = (sid >= 0) & (cl.state[slot] == CL_DONE)
    failed = (sid >= 0) & (cl.state[slot] == CL_FAILED)
    fin, sta = cl.finish_time[slot], cl.start_time[slot]
    vm = jnp.clip(cl.vm[slot], 0, nv - 1)
    r = stats.res_sid.shape[0]
    sample = (done | failed) & (sid % stats.stride == 0)
    ridx = jnp.where(sample, sid // stats.stride, r)
    return dataclasses.replace(
        stats,
        n_retired=stats.n_retired + done.astype(jnp.int32),
        n_failed=stats.n_failed + failed.astype(jnp.int32),
        makespan=jnp.where(done, jnp.maximum(stats.makespan, fin),
                           stats.makespan),
        sum_exec=stats.sum_exec + jnp.where(done, fin - sta, 0.0),
        sum_response=stats.sum_response
        + jnp.where(done, fin - cl.submit_time[slot], 0.0),
        sum_len=stats.sum_len + jnp.where(done, cl.length[slot], 0.0),
        per_vm_done=stats.per_vm_done.at[vm].add(done.astype(jnp.int32)),
        res_sid=stats.res_sid.at[ridx].set(sid, mode="drop"),
        res_start=stats.res_start.at[ridx].set(sta, mode="drop"),
        res_finish=stats.res_finish.at[ridx].set(fin, mode="drop"))


def _retire_remaining(dc: DatacenterState, st: StreamState) -> StreamState:
    """Fold every still-resident occupant after the last chunk drains.

    One vectorized pass — by quiescence the residents are terminal
    (DONE/FAILED) or permanently stuck, and across different chunk sizes
    the same slots remain resident (the event trajectory is chunking-
    invariant), so this fold is bitwise chunking-invariant too."""
    cl = dc.cloudlets
    stats = st.stats
    nv = stats.per_vm_done.shape[0]
    sid = st.slot_sid
    done = (sid >= 0) & (cl.state == CL_DONE)
    failed = (sid >= 0) & (cl.state == CL_FAILED)
    r = stats.res_sid.shape[0]
    sample = (done | failed) & (sid % stats.stride == 0)
    ridx = jnp.where(sample, sid // stats.stride, r)
    vm = jnp.clip(cl.vm, 0, nv - 1)
    stats = dataclasses.replace(
        stats,
        n_retired=stats.n_retired + jnp.sum(done.astype(jnp.int32)),
        n_failed=stats.n_failed + jnp.sum(failed.astype(jnp.int32)),
        makespan=jnp.maximum(
            stats.makespan,
            jnp.max(jnp.where(done, cl.finish_time, 0.0), initial=0.0)),
        sum_exec=stats.sum_exec + jnp.sum(
            jnp.where(done, cl.finish_time - cl.start_time, 0.0)),
        sum_response=stats.sum_response + jnp.sum(
            jnp.where(done, cl.finish_time - cl.submit_time, 0.0)),
        sum_len=stats.sum_len + jnp.sum(jnp.where(done, cl.length, 0.0)),
        per_vm_done=stats.per_vm_done.at[vm].add(done.astype(jnp.int32)),
        res_sid=stats.res_sid.at[ridx].set(sid, mode="drop"),
        res_start=stats.res_start.at[ridx].set(cl.start_time, mode="drop"),
        res_finish=stats.res_finish.at[ridx].set(cl.finish_time,
                                                 mode="drop"))
    return dataclasses.replace(st, stats=stats)


def _admit_due(dc: DatacenterState, st: StreamState, chunk
               ) -> tuple[DatacenterState, StreamState]:
    """Admit due arrivals from ``chunk`` into free window slots, in order.

    One arrival per iteration of a bounded while_loop; admission is
    strictly by global arrival index (the stream is sorted by submit time
    at build time), so the (arrival, slot) sequence — and with it every
    downstream f32 value — is invariant to how the stream is chunked.
    A slot is claimable when it does not hold an in-flight (CL_CREATED)
    cloudlet; claiming retires the previous occupant into the aggregates.
    An arrival naming a FAILED/DESTROYED VM is written already-FAILED
    (mirroring the provisioning-failure rule, which only marks cloudlets
    at provisioning instants) so it cannot clog the window.
    """
    m = chunk.vm.shape[0]
    w = dc.cloudlets.vm.shape[0]
    nv = dc.vms.req_pes.shape[0]

    def cond(c):
        d, s = c
        cur = jnp.minimum(s.cursor, m - 1)
        row = (s.cursor < m) & (chunk.vm[cur] >= 0)
        due = chunk.submit[cur] <= d.time
        free = jnp.sum((d.cloudlets.state == CL_CREATED
                        ).astype(jnp.int32)) < w
        return row & due & free

    def body(c):
        d, s = c
        cur = jnp.minimum(s.cursor, m - 1)
        vm_raw = chunk.vm[cur]
        vm = jnp.clip(vm_raw, 0, nv - 1)
        cl = d.cloudlets
        slot = jnp.argmax(cl.state != CL_CREATED)     # lowest free slot
        stats = _retire_slot(s.stats, cl, s.slot_sid[slot], slot, nv)
        vdead = ((d.vms.state[vm] == VM_FAILED)
                 | (d.vms.state[vm] == VM_DESTROYED))
        length = chunk.length[cur]
        cl2 = dataclasses.replace(
            cl,
            vm=cl.vm.at[slot].set(vm_raw),
            length=cl.length.at[slot].set(length),
            remaining=cl.remaining.at[slot].set(length),
            file_size=cl.file_size.at[slot].set(chunk.file_size[cur]),
            output_size=cl.output_size.at[slot].set(chunk.output_size[cur]),
            submit_time=cl.submit_time.at[slot].set(chunk.submit[cur]),
            start_time=cl.start_time.at[slot].set(-1.0),
            finish_time=cl.finish_time.at[slot].set(INF),
            rank_in_vm=cl.rank_in_vm.at[slot].set(s.vm_rank[vm]),
            state=cl.state.at[slot].set(
                jnp.where(vdead, CL_FAILED, CL_CREATED)),
            net_phase=cl.net_phase.at[slot].set(NET_PRE),
            net_remaining=cl.net_remaining.at[slot].set(0.0),
            net_lat=cl.net_lat.at[slot].set(0.0))
        occ = jnp.sum((cl2.state == CL_CREATED).astype(jnp.int32))
        s2 = dataclasses.replace(
            s, cursor=s.cursor + 1, next_sid=s.next_sid + 1,
            vm_rank=s.vm_rank.at[vm].add(1),
            slot_sid=s.slot_sid.at[slot].set(s.next_sid),
            peak_occupancy=jnp.maximum(s.peak_occupancy, occ),
            stats=stats)
        return dataclasses.replace(d, cloudlets=cl2), s2

    return jax.lax.while_loop(cond, body, (dc, st))


def _stream_core(dc: DatacenterState, st: StreamState, stream: ArrivalStream,
                 *, provision_policy: int, dynamic: bool, networked: bool,
                 elastic: bool, leap: bool, max_steps_per_chunk: int,
                 probed: bool
                 ) -> tuple[DatacenterState, StreamState, StreamChunkRecord]:
    """lax.scan over arrival chunks: admit -> step until the chunk drains.

    The inner loop interleaves the admission pass with ``step(streaming=
    True)``; ``next_arrival`` is the submit time of the next unadmitted
    row of the *current* chunk, or — once the chunk is exhausted — the
    head of the *next* chunk (precomputed host-side), so the clock can
    never jump past an arrival still sitting in a later chunk.  A chunk's
    loop exits once its rows are admitted and the clock has reached the
    next chunk's head (or, for the last chunk, at full quiescence — the
    final scan iteration doubles as the drain phase)."""
    m = stream.vm.shape[1]
    head = jnp.where(stream.vm[:, 0] >= 0, stream.submit[:, 0], INF)
    next_head = jnp.concatenate([head[1:], jnp.full((1,), INF, jnp.float32)])

    def chunk_body(carry, xs):
        dc, st = carry
        chunk, hnext = xs
        st = dataclasses.replace(st, cursor=jnp.int32(0))

        def pending(s):
            cur = jnp.minimum(s.cursor, m - 1)
            return (s.cursor < m) & (chunk.vm[cur] >= 0)

        def cond(c):
            d, s, n, alive = c
            return (alive & (n < max_steps_per_chunk)
                    & (pending(s) | (d.time < hnext)))

        def body(c):
            d, s, n, alive = c
            d, s = _admit_due(d, s, chunk)
            backlog = jnp.sum(((jnp.arange(m) >= s.cursor)
                               & (chunk.vm >= 0)
                               & (chunk.submit <= d.time)).astype(jnp.int32))
            s = dataclasses.replace(
                s, max_backlog=jnp.maximum(s.max_backlog, backlog))
            cur = jnp.minimum(s.cursor, m - 1)
            nxt = jnp.where(pending(s), chunk.submit[cur], hnext)
            # the admission above may have finished the chunk's job (all
            # rows admitted, next chunk's head already due) — stepping
            # then would commit an event *before* the next chunk's due
            # arrivals are admitted, so hand off to the next chunk instead
            go = pending(s) | (d.time < hnext)

            def _step(d_):
                return step(d_, provision_policy=provision_policy,
                            dynamic=dynamic, networked=networked,
                            elastic=elastic, leap=leap,
                            leap_budget=(jnp.int32(max_steps_per_chunk)
                                         - n - 1),
                            streaming=True, next_arrival=nxt,
                            probed=probed)

            def _handoff(d_):
                z = jnp.int32(0)
                rec = StepRecord(
                    time=d_.time, n_running=z, n_done=z,
                    utilization=jnp.float32(0.0), watts=jnp.float32(0.0),
                    active=jnp.bool_(False), n_migrating=z, migrations=z,
                    hosts_down=z, transferred_mb=jnp.float32(0.0),
                    n_flows=z, n_events=z, fleet=z,
                    spot_cost=jnp.float32(0.0))
                return d_, rec

            new, rec = jax.lax.cond(go, _step, _handoff, d)
            return new, s, n + rec.n_events, rec.active

        dc, st, n, _ = jax.lax.while_loop(
            cond, body, (dc, st, jnp.int32(0), jnp.bool_(True)))
        rec = StreamChunkRecord(
            time=dc.time,
            occupancy=jnp.sum((dc.cloudlets.state == CL_CREATED
                               ).astype(jnp.int32)),
            peak_occupancy=st.peak_occupancy,
            max_backlog=st.max_backlog,
            n_retired=st.stats.n_retired,
            n_failed=st.stats.n_failed,
            n_events=n)
        return (dc, st), rec

    (dc, st), recs = jax.lax.scan(chunk_body, (dc, st), (stream, next_head))
    return dc, _retire_remaining(dc, st), recs


_run_stream = jax.jit(_stream_core, static_argnames=(
    "provision_policy", "dynamic", "networked", "elastic", "leap",
    "max_steps_per_chunk", "probed"))


def run_stream(dc: DatacenterState, stream: ArrivalStream, *,
               reservoir: int = 64, provision_policy: int = FIRST_FIT,
               dynamic: bool | None = None, networked: bool | None = None,
               elastic: bool | None = None,
               leap: bool | None = None, max_steps_per_chunk: int = 4096,
               probed: bool | None = None
               ) -> tuple[DatacenterState, StreamState, StreamChunkRecord]:
    """Run a streamed-arrival scenario to quiescence (docs/streaming.md).

    ``dc`` carries the infrastructure plus an *empty* cloudlet window
    (``state.make_window(W)``); ``stream`` carries the actual workload as
    chunked arrivals (``state.make_stream``).  W bounds how many
    cloudlets may be in flight (admission-order FCFS overflow queueing —
    a semantic knob); the chunk size only tiles the arrival table in
    memory (a pure memory knob: all aggregates are bitwise invariant to
    it).  Every stream VM id must name a real (non-EMPTY) VM slot or the
    target of an EV_VM_CREATE row.

    Returns ``(final state, StreamState, per-chunk StreamChunkRecord)``;
    the workload answers (makespan, exec/response sums, per-VM counts,
    sampled per-cloudlet times) live in ``StreamState.stats``, while
    energy/cost/transfer totals stay on the ``DatacenterState`` as usual.
    """
    if dynamic is None:
        dynamic = wants_dynamic(dc)
    if networked is None:
        networked = wants_network(dc)
    if elastic is None:
        elastic = wants_elastic(dc)
    if leap is None:
        leap = _LEAP_DEFAULT
    if probed is None:
        probed = wants_probes(dc)
    st = make_stream_state(stream, dc.vms.req_pes.shape[0],
                           dc.cloudlets.vm.shape[0], reservoir=reservoir)
    return _run_stream(dc, st, stream, provision_policy=provision_policy,
                       dynamic=dynamic, networked=networked,
                       elastic=elastic, leap=leap,
                       max_steps_per_chunk=max_steps_per_chunk,
                       probed=probed)
