"""Batched scenario sweeps — many datacenters / policies in one compiled call.

Buyya et al.'s companion work (the federated-policy studies around
CloudSim) treats *sweeps* over allocation policies and workload scenarios
as the toolkit's main use; in CloudSim each run is a separate JVM
simulation.  Here a whole sweep is one XLA program: every field of
``DatacenterState`` is a dense array, so B independent scenarios stack
into a leading batch axis and ``engine.step``/``run`` vmap over it.

The policy grid is *fused* into the same batch axis rather than nested:
``run_grid`` broadcasts each of the P policy pairs over the B stacked
scenarios and runs one flat ``vmap`` over P*B lanes (lane ``p*B + b`` is
scenario ``b`` under policy pair ``p``), reshaping results back to
``[P, B, ...]``.  Policy codes are traced scalars inside the state, so
the whole grid is still a single compilation.

The fused lane axis is also the *sharding* axis: ``run_sharded`` splits
it across the devices of a 1-D mesh — with ``jax.shard_map``, or
with GSPMD lane-axis ``in_shardings`` on the CPU backend (see
``run_sharded``) — lanes are fully independent (no collectives), so
sweep throughput scales linearly in devices.  Lane counts that do not
divide the device count are padded with inert lanes (see below) and
unpadded on return.

Ragged scenarios (different host/VM/cloudlet counts) are padded to a
common shape first: padded hosts are invalid, padded VMs are ``VM_EMPTY``
(never provisioned), padded cloudlets are ``CL_EMPTY`` (never runnable),
so padding is exactly inert — a padded run reproduces its unpadded run's
results on the real slots.  ``pad_batch`` applies the same trick one
level up: a padding *lane* is a whole scenario of invalid entities, which
quiesces on its first step and costs nothing afterwards.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import engine
from repro.core.energy import energy_total_j
from repro.core.provisioning import FIRST_FIT
from repro.core.state import (
    CL_CREATED,
    CL_DONE,
    CL_EMPTY,
    ArrivalStream,
    DatacenterState,
    INF,
    StreamState,
    VM_EMPTY,
    VM_PENDING,
    make_stream_state,
)

__all__ = ["pad_scenario", "stack_scenarios", "run_batch", "run_grid",
           "run_grid_nested", "fuse_grid", "inert_lane", "pad_batch",
           "run_sharded", "policy_grid", "SweepSummary", "summarize_batch",
           "stack_streams", "run_stream_batch", "run_stream_grid",
           "StreamSweepSummary", "summarize_stream",
           "PolicyGrid", "policy_points", "fuse_policies",
           "run_policy_search"]


# ---------------------------------------------------------------------------
# Padding + stacking
# ---------------------------------------------------------------------------
def _pad_axis0(arr: jnp.ndarray, n: int, fill) -> jnp.ndarray:
    extra = n - arr.shape[0]
    if extra < 0:
        raise ValueError(f"cannot shrink axis 0: {arr.shape[0]} -> {n}")
    if extra == 0:
        return arr
    pad = jnp.full((extra,) + arr.shape[1:], fill, arr.dtype)
    return jnp.concatenate([arr, pad])


def pad_scenario(dc: DatacenterState, *, n_hosts: int | None = None,
                 n_vms: int | None = None, n_cloudlets: int | None = None,
                 n_events: int | None = None,
                 n_spot: int | None = None) -> DatacenterState:
    """Grow a scenario to fixed entity capacities with inert padding.

    Padded event rows are all-zero (kind ``EV_NONE``) and unfired — the
    engine never applies them, so the event axis pads as inertly as the
    entity axes.  Spot tables pad with *duplicates* of their final
    segment: duplicates add no new boundaries (``spot_t > time`` yields
    the same minimum) and leave the active-segment lookup's clipped
    index pointing at the same price, so a padded spot lane replays its
    unpadded trajectory event for event.
    """
    h, v, c = dc.hosts, dc.vms, dc.cloudlets
    nh = n_hosts if n_hosts is not None else h.num_pes.shape[0]
    nv = n_vms if n_vms is not None else v.req_pes.shape[0]
    nc = n_cloudlets if n_cloudlets is not None else c.vm.shape[0]
    ne = n_events if n_events is not None else dc.events.shape[0]

    hosts = dataclasses.replace(
        h,
        num_pes=_pad_axis0(h.num_pes, nh, 0),
        mips_per_pe=_pad_axis0(h.mips_per_pe, nh, 0.0),
        ram=_pad_axis0(h.ram, nh, 0.0),
        bw=_pad_axis0(h.bw, nh, 0.0),
        storage=_pad_axis0(h.storage, nh, 0.0),
        free_ram=_pad_axis0(h.free_ram, nh, 0.0),
        free_bw=_pad_axis0(h.free_bw, nh, 0.0),
        free_storage=_pad_axis0(h.free_storage, nh, 0.0),
        free_pes=_pad_axis0(h.free_pes, nh, 0.0),
        idle_w=_pad_axis0(h.idle_w, nh, 0.0),
        peak_w=_pad_axis0(h.peak_w, nh, 0.0),
        power_curve=_pad_axis0(h.power_curve, nh, 0.0),
        energy_j=_pad_axis0(h.energy_j, nh, 0.0),
        valid=_pad_axis0(h.valid, nh, False),
    )
    vms = dataclasses.replace(
        v,
        req_pes=_pad_axis0(v.req_pes, nv, 0),
        req_mips=_pad_axis0(v.req_mips, nv, 0.0),
        ram=_pad_axis0(v.ram, nv, 0.0),
        bw=_pad_axis0(v.bw, nv, 0.0),
        size=_pad_axis0(v.size, nv, 0.0),
        submit_time=_pad_axis0(v.submit_time, nv, 0.0),
        host=_pad_axis0(v.host, nv, -1),
        state=_pad_axis0(v.state, nv, VM_EMPTY),
        create_time=_pad_axis0(v.create_time, nv, INF),
        mig_remaining=_pad_axis0(v.mig_remaining, nv, 0.0),
    )
    cloudlets = dataclasses.replace(
        c,
        vm=_pad_axis0(c.vm, nc, -1),
        length=_pad_axis0(c.length, nc, 0.0),
        remaining=_pad_axis0(c.remaining, nc, 0.0),
        file_size=_pad_axis0(c.file_size, nc, 0.0),
        output_size=_pad_axis0(c.output_size, nc, 0.0),
        submit_time=_pad_axis0(c.submit_time, nc, 0.0),
        start_time=_pad_axis0(c.start_time, nc, -1.0),
        finish_time=_pad_axis0(c.finish_time, nc, INF),
        rank_in_vm=_pad_axis0(c.rank_in_vm, nc, 0),
        state=_pad_axis0(c.state, nc, CL_EMPTY),
        net_phase=_pad_axis0(c.net_phase, nc, 0),
        net_remaining=_pad_axis0(c.net_remaining, nc, 0.0),
        net_lat=_pad_axis0(c.net_lat, nc, 0.0),
    )
    sc = dc.scaler
    ns = n_spot if n_spot is not None else sc.spot_t.shape[0]
    return dataclasses.replace(
        dc, hosts=hosts, vms=vms, cloudlets=cloudlets,
        events=_pad_axis0(dc.events, ne, 0.0),
        event_fired=_pad_axis0(dc.event_fired, ne, False),
        net=dataclasses.replace(
            dc.net, cluster=_pad_axis0(dc.net.cluster, nh, 0)),
        scaler=dataclasses.replace(
            sc,
            spot_t=_pad_axis0(sc.spot_t, ns, sc.spot_t[-1]),
            spot_price=_pad_axis0(sc.spot_price, ns, sc.spot_price[-1])),
        metrics=dataclasses.replace(
            dc.metrics,
            host_busy_s=_pad_axis0(dc.metrics.host_busy_s, nh, 0.0)))


def stack_scenarios(dcs: Sequence[DatacenterState]) -> DatacenterState:
    """Stack scenarios into one batched state (leading axis B), auto-padding
    every entity block (hosts/VMs/cloudlets/events) to the sweep-wide
    maximum capacity."""
    if not dcs:
        raise ValueError("empty scenario list")
    nh = max(d.hosts.num_pes.shape[0] for d in dcs)
    nv = max(d.vms.req_pes.shape[0] for d in dcs)
    nc = max(d.cloudlets.vm.shape[0] for d in dcs)
    ne = max(d.events.shape[0] for d in dcs)
    ns = max(d.scaler.spot_t.shape[0] for d in dcs)
    padded = [pad_scenario(d, n_hosts=nh, n_vms=nv, n_cloudlets=nc,
                           n_events=ne, n_spot=ns)
              for d in dcs]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


# ---------------------------------------------------------------------------
# Batched runners
# ---------------------------------------------------------------------------
# Host spans on the profiler's clock (docs/observability.md)
_span = jax.profiler.TraceAnnotation

def _run_batch(batch: DatacenterState, *, max_steps: int,
               provision_policy: int, dynamic: bool,
               networked: bool, elastic: bool = False,
               probed: bool = False) -> DatacenterState:
    # engine.batched_run == vmap(engine.run) lane for lane (bitwise), plus
    # the dead-lane early-exit: the dynamic/networked/elastic passes switch
    # off the moment no live lane needs them (tests/test_leap_parity.py).
    out, _ = engine.batched_run(batch, max_steps=max_steps,
                                provision_policy=provision_policy,
                                dynamic=dynamic, networked=networked,
                                elastic=elastic, probed=probed)
    return out


def run_batch(batch: DatacenterState, *, max_steps: int = 1_000_000,
              provision_policy: int = FIRST_FIT,
              dynamic: bool | None = None,
              networked: bool | None = None,
              elastic: bool | None = None,
              probed: bool | None = None) -> DatacenterState:
    """vmap ``engine.run`` over a stacked scenario batch (one compiled call).

    Each lane runs to its own quiescence; lanes that finish early take
    inert no-op steps (``step`` is a fixed point at quiescence) until the
    whole batch quiesces, so per-lane results are identical to single runs.
    ``dynamic=None`` auto-detects whether any lane carries events or a
    migration policy (``engine.wants_dynamic``); ``networked=None``
    likewise auto-detects an enabled topology (``engine.wants_network``);
    ``elastic=None`` an enabled autoscaler or spot track
    (``engine.wants_elastic``); ``probed=None`` an enabled metrics plane
    (``engine.wants_probes``).  The whole batch then runs the
    dynamic/networked/elastic/probed program — inert for lanes without
    the matching subsystem.
    """
    if dynamic is None:
        dynamic = engine.wants_dynamic(batch)
    if networked is None:
        networked = engine.wants_network(batch)
    if elastic is None:
        elastic = engine.wants_elastic(batch)
    if probed is None:
        probed = engine.wants_probes(batch)
    return _run_batch(batch, max_steps=max_steps,
                      provision_policy=provision_policy, dynamic=dynamic,
                      networked=networked, elastic=elastic, probed=probed)


@partial(jax.jit, static_argnames=("max_steps", "provision_policy",
                                   "dynamic", "networked", "elastic",
                                   "probed"))
def _run_grid_nested(batch: DatacenterState, vm_policies: jnp.ndarray,
                     task_policies: jnp.ndarray, *, max_steps: int,
                     provision_policy: int, dynamic: bool, networked: bool,
                     elastic: bool = False,
                     probed: bool = False) -> DatacenterState:
    def one_policy(vp, tp):
        withp = dataclasses.replace(
            batch,
            vm_policy=jnp.broadcast_to(vp, batch.vm_policy.shape),
            task_policy=jnp.broadcast_to(tp, batch.task_policy.shape))
        return _run_batch(withp, max_steps=max_steps,
                          provision_policy=provision_policy,
                          dynamic=dynamic, networked=networked,
                          elastic=elastic, probed=probed)

    return jax.vmap(one_policy)(jnp.asarray(vm_policies, jnp.int32),
                                jnp.asarray(task_policies, jnp.int32))


def run_grid_nested(batch: DatacenterState, vm_policies: jnp.ndarray,
                    task_policies: jnp.ndarray, *, max_steps: int = 1_000_000,
                    provision_policy: int = FIRST_FIT,
                    dynamic: bool | None = None,
                    networked: bool | None = None,
                    elastic: bool | None = None,
                    probed: bool | None = None) -> DatacenterState:
    """Reference grid runner: outer vmap over policies, inner over scenarios.

    The PR-1 implementation, kept as the differential baseline for the
    fused path — ``tests/test_conformance.py`` pins ``run_grid`` ==
    ``run_grid_nested`` bit-for-bit.  Same [P, B, ...] result layout.
    """
    if dynamic is None:
        dynamic = engine.wants_dynamic(batch)
    if networked is None:
        networked = engine.wants_network(batch)
    if elastic is None:
        elastic = engine.wants_elastic(batch)
    if probed is None:
        probed = engine.wants_probes(batch)
    return _run_grid_nested(batch, vm_policies, task_policies,
                            max_steps=max_steps,
                            provision_policy=provision_policy,
                            dynamic=dynamic, networked=networked,
                            elastic=elastic, probed=probed)


def fuse_grid(batch: DatacenterState, vm_policies: jnp.ndarray,
              task_policies: jnp.ndarray) -> DatacenterState:
    """Flatten a [B] scenario batch x i32[P] policy pairs into [P*B] lanes.

    Lane ``p*B + b`` is scenario ``b`` with its ``vm_policy``/``task_policy``
    scalars overwritten by policy pair ``p``; every other leaf is broadcast
    and reshaped.  Called eagerly this materializes the P copies;
    ``run_grid`` therefore traces it inside its jitted pipeline, where
    XLA keeps the broadcast symbolic.  The inverse is a plain ``reshape``
    of each leaf to ``(P, B) + rest``.
    """
    vm_policies = jnp.asarray(vm_policies, jnp.int32)
    task_policies = jnp.asarray(task_policies, jnp.int32)
    if vm_policies.shape != task_policies.shape:
        raise ValueError("vm_policies and task_policies must pair up: "
                         f"{vm_policies.shape} vs {task_policies.shape}")
    n_pol = vm_policies.shape[0]
    n_scen = batch.time.shape[0]

    def tile(x):
        return jnp.broadcast_to(
            x[None], (n_pol,) + x.shape).reshape((n_pol * n_scen,)
                                                 + x.shape[1:])

    fused = jax.tree_util.tree_map(tile, batch)
    return dataclasses.replace(
        fused,
        vm_policy=jnp.repeat(vm_policies, n_scen),
        task_policy=jnp.repeat(task_policies, n_scen))


def inert_lane(batch: DatacenterState) -> DatacenterState:
    """One unbatched scenario that quiesces on its first step.

    All hosts invalid, all VMs ``VM_EMPTY``, all cloudlets ``CL_EMPTY`` —
    the event queue is empty from t=0, so ``engine.run`` takes zero active
    steps and the lane is a fixed point.  Used to pad a lane axis up to a
    multiple of the device count; the padded results are discarded.
    """
    lane = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x[0]), batch)
    return dataclasses.replace(
        lane,
        vms=dataclasses.replace(
            lane.vms,
            host=jnp.full_like(lane.vms.host, -1),
            state=jnp.full_like(lane.vms.state, VM_EMPTY),
            create_time=jnp.full_like(lane.vms.create_time, INF)),
        cloudlets=dataclasses.replace(
            lane.cloudlets,
            vm=jnp.full_like(lane.cloudlets.vm, -1),
            start_time=jnp.full_like(lane.cloudlets.start_time, -1.0),
            finish_time=jnp.full_like(lane.cloudlets.finish_time, INF),
            state=jnp.full_like(lane.cloudlets.state, CL_EMPTY)))


def pad_batch(batch: DatacenterState, n_lanes: int) -> DatacenterState:
    """Grow the leading lane axis to ``n_lanes`` with inert lanes."""
    have = batch.time.shape[0]
    if n_lanes < have:
        raise ValueError(f"cannot shrink lane axis: {have} -> {n_lanes}")
    if n_lanes == have:
        return batch
    pad = inert_lane(batch)
    grow = lambda x, p: jnp.concatenate(
        [x, jnp.broadcast_to(p[None], (n_lanes - have,) + p.shape)])
    return jax.tree_util.tree_map(grow, batch, pad)


def _lane_axis(mesh) -> str:
    """The (only) axis name of a 1-D sweep mesh; reject higher ranks."""
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"sweep meshes are 1-D; got axes {mesh.axis_names}")
    return mesh.axis_names[0]


def _resolve_partitioner(partitioner: str, *, n_dev: int = 1,
                         dispatch_ok: bool = False) -> str:
    """Validate/expand a partitioner choice (the CPU backend defaults
    away from shard_map — see ``_sharded_runner``).  ``dispatch_ok``
    admits the host-side chunked dispatcher (``run_sharded``), which
    ``"auto"`` prefers on CPU whenever the mesh actually has more than
    one device — single-device meshes keep the plain fused program."""
    if partitioner == "auto":
        if jax.default_backend() != "cpu":
            return "shard_map"
        return "dispatch" if dispatch_ok and n_dev > 1 else "gspmd"
    allowed = ("gspmd", "shard_map") + (("dispatch",) if dispatch_ok
                                        else ())
    if partitioner not in allowed:
        raise ValueError(f"unknown partitioner: {partitioner!r}")
    return partitioner


def _dispatch_cost(batch: DatacenterState) -> np.ndarray:
    """Host-side per-lane step-count estimate for the chunked dispatcher.

    Ordering heuristic only — any estimate is bitwise-safe (per-lane math
    never depends on co-scheduled lanes); a better estimate just packs
    slow lanes together so short chunks retire early.  Events and a live
    migration policy multiply a lane's event count well beyond its
    cloudlet count, hence the weights."""
    est = np.asarray(batch.cloudlets.state == CL_CREATED).sum(-1)
    est = est.astype(np.float64)
    est += 2.0 * np.asarray(batch.vms.state == VM_PENDING).sum(-1)
    if batch.events.shape[-2]:
        kinds = np.asarray(batch.events[..., 1]).astype(np.int32)
        fired = np.asarray(batch.event_fired)
        est += 4.0 * ((~fired) & (kinds != 0)).sum(-1)
    est *= np.where(np.asarray(batch.mig_policy) != 0, 4.0, 1.0)
    return est


def _dispatch_run(batch: DatacenterState, mesh, *, max_steps: int,
                  provision_policy: int, dynamic: bool, networked: bool,
                  elastic: bool = False, probed: bool = False,
                  chunk: int = 4
                  ) -> tuple[DatacenterState, engine.RunStats]:
    """Sorted-chunk dispatch: per-call sharding without SPMD.

    Lanes are sorted by estimated cost (descending) and cut into
    contiguous chunks of ``chunk`` lanes; chunks round-robin over the mesh
    devices as *separate* ``batched_run`` dispatches (async — XLA queues
    them per device).  Each chunk's while_loop retires when its own
    slowest lane quiesces, so a heavy-tailed sweep stops paying the fused
    program's cost of dragging every quiesced lane along to the global
    maximum step count — the win scales with max/mean of the per-lane
    step counts even on one physical core.  No SPMD program is built, so
    neither CPU-partitioner landmine (vmapped-step crash, loop-variant
    sort rendezvous) is reachable.  Results are reassembled in original
    lane order; per-lane bitwise equality to the fused path follows from
    ``batched_run`` == ``vmap(run)``.  Returns ``(final, RunStats)``, both
    in the original lane order.
    """
    devs = list(mesh.devices.flat)
    order = np.argsort(-_dispatch_cost(batch), kind="stable")
    outs = []
    for i in range(0, order.size, chunk):
        idx = jnp.asarray(order[i:i + chunk])
        dev = devs[(i // chunk) % len(devs)]
        sub = jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.take(x, idx, axis=0), dev), batch)
        outs.append(engine.batched_run(
            sub, max_steps=max_steps, provision_policy=provision_policy,
            dynamic=dynamic, networked=networked, elastic=elastic,
            probed=probed))
    cat = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate([jax.device_put(x, devs[0])
                                     for x in xs]), *outs)
    inv = jnp.asarray(np.argsort(order, kind="stable"))
    out, run_stats = jax.tree_util.tree_map(
        lambda x: jnp.take(x, inv, axis=0), cat)
    return out, run_stats


def _default_inner() -> str:
    """Per-device iteration scheme for the shard_map partitioner."""
    return "map" if jax.default_backend() == "cpu" else "vmap"


@lru_cache(maxsize=None)
def _sharded_runner(mesh, axis: str, max_steps: int, provision_policy: int,
                    inner: str, dynamic: bool, networked: bool,
                    elastic: bool = False, probed: bool = False):
    """jit(shard_map(map-or-vmap(run))) for one (mesh, statics) combination.

    Cached so repeated sweeps with the same mesh reuse the compiled
    executable (rebuilding the shard_map closure per call would defeat
    jit's cache).

    ``inner`` picks how a device iterates its lane block: ``"vmap"``
    batches the block into wide ops, ``"map"`` runs lanes back-to-back
    with ``lax.map``.  jaxlib 0.4.37's *CPU* SPMD partitioner
    hard-crashed (``TileAssignment::Reshape`` check failure) on a vmapped
    engine step inside ``shard_map``, so CPU defaults to ``"map"``; both
    spellings are bit-for-bit equal per lane.
    """
    spec = P(axis)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
             out_specs=spec, check_vma=False)
    def go(block: DatacenterState) -> DatacenterState:
        f = partial(engine.run, max_steps=max_steps,
                    provision_policy=provision_policy, dynamic=dynamic,
                    networked=networked, elastic=elastic, probed=probed)
        if inner == "vmap":
            return jax.vmap(f)(block)
        return jax.lax.map(f, block)

    return go


@lru_cache(maxsize=None)
def _gspmd_runner(mesh, axis: str, max_steps: int, provision_policy: int,
                  dynamic: bool, networked: bool, elastic: bool = False,
                  probed: bool = False):
    """jit(vmap(run)) with GSPMD in/out shardings over the lane axis.

    Same program as ``run_batch`` — XLA's automatic partitioner splits
    the lane-sharded arrays instead of an explicit ``shard_map``.  Keeps
    the inner vmap (wide vectorized lanes) on every backend, including
    the CPU backend whose manual-sharding partitioner cannot compile it
    (see ``_sharded_runner``).
    """
    shd = NamedSharding(mesh, P(axis))
    f = partial(engine.run, max_steps=max_steps,
                provision_policy=provision_policy, dynamic=dynamic,
                networked=networked, elastic=elastic, probed=probed)
    return jax.jit(jax.vmap(f), in_shardings=(shd,), out_shardings=shd)


def run_sharded(batch: DatacenterState, *, mesh=None, axis: str = "sweep",
                max_steps: int = 1_000_000,
                provision_policy: int = FIRST_FIT,
                partitioner: str = "auto",
                inner: str | None = None,
                dynamic: bool | None = None,
                networked: bool | None = None,
                elastic: bool | None = None,
                probed: bool | None = None) -> DatacenterState:
    """``run_batch`` with the lane axis split across the devices of a mesh.

    ``mesh`` is a 1-D ``jax.sharding.Mesh`` (default: all local devices,
    via ``compat.make_mesh``).  Lanes are independent simulations — each
    device runs ``engine.run`` over its own contiguous block and no
    collective ever runs, so results are bit-for-bit identical to the
    single-device path.  Lane counts not divisible by the device count
    are padded with ``inert_lane`` scenarios and unpadded on return.

    ``partitioner`` selects how lanes land on devices:

    * ``"shard_map"`` — explicit ``jax.shard_map`` over ``axis``; each
      device iterates its block per ``inner`` ("vmap" | "map", default
      "map" on CPU, where jaxlib 0.4.37 could not compile the vmapped
      engine under manual sharding, "vmap" elsewhere).
    * ``"gspmd"`` — ``jit`` with lane-axis ``in_shardings``; XLA's
      automatic partitioner splits the ordinary ``run_batch`` program,
      keeping wide vmap vectorization on every backend.
    * ``"dispatch"`` — host-side sorted-chunk dispatcher
      (``_dispatch_run``): no SPMD program at all; lanes are grouped by
      estimated cost into small chunks issued round-robin to the
      devices, so short lanes retire without dragging to the slowest
      lane's step count (``docs/performance.md``).
    * ``"auto"`` (default) — ``"dispatch"`` on CPU meshes with more than
      one device, ``"gspmd"`` on single-device CPU, ``"shard_map"`` on
      accelerator backends.

    All spellings are bit-for-bit equal (``tests/test_sweep_sharded.py``).
    """
    if mesh is None:
        mesh = compat.make_mesh(axis)
    else:
        axis = _lane_axis(mesh)
    if dynamic is None:
        dynamic = engine.wants_dynamic(batch)
    if networked is None:
        networked = engine.wants_network(batch)
    if elastic is None:
        elastic = engine.wants_elastic(batch)
    if probed is None:
        probed = engine.wants_probes(batch)
    n_dev = mesh.shape[axis]
    partitioner = _resolve_partitioner(partitioner, n_dev=n_dev,
                                       dispatch_ok=True)
    if partitioner == "dispatch":
        # chunks need no divisibility padding — any lane count dispatches
        out, _ = _dispatch_run(batch, mesh, max_steps=max_steps,
                               provision_policy=provision_policy,
                               dynamic=dynamic, networked=networked,
                               elastic=elastic, probed=probed)
        return out
    have = batch.time.shape[0]
    lanes = -(-have // n_dev) * n_dev
    padded = pad_batch(batch, lanes)
    if partitioner == "gspmd":
        out = _gspmd_runner(mesh, axis, max_steps, provision_policy,
                            dynamic, networked, elastic, probed)(padded)
    else:
        out = _sharded_runner(mesh, axis, max_steps, provision_policy,
                              inner if inner is not None
                              else _default_inner(), dynamic,
                              networked, elastic, probed)(padded)
    if lanes == have:
        return out
    return jax.tree_util.tree_map(lambda x: x[:have], out)


@lru_cache(maxsize=None)
def _grid_runner(mesh, max_steps: int, provision_policy: int,
                 partitioner: str, inner: str, dynamic: bool,
                 networked: bool, elastic: bool = False,
                 probed: bool = False):
    """One jitted fuse -> (shard) -> run -> reshape pipeline per config.

    The whole grid — policy broadcast, inert mesh padding, the flat lane
    vmap, and the [P, B] reshape — traces into a single XLA program, so
    the P-fold broadcast of the scenario batch is never materialized on
    the host side.  ``mesh=None`` is the unsharded single-device variant.
    The program is named ``run_grid`` in a trace and returns ``(final,
    RunStats)``, both [P, B].
    """
    run_lane = lambda dc: engine.run(dc, max_steps=max_steps,
                                     provision_policy=provision_policy,
                                     dynamic=dynamic, networked=networked,
                                     elastic=elastic, probed=probed,
                                     stats=True)

    def run_grid(batch, vm_policies, task_policies):
        n_pol = vm_policies.shape[0]
        n_scen = batch.time.shape[0]
        fused = fuse_grid(batch, vm_policies, task_policies)
        if mesh is None:
            out = engine.batched_run(fused, max_steps=max_steps,
                                     provision_policy=provision_policy,
                                     dynamic=dynamic, networked=networked,
                                     elastic=elastic, probed=probed)
        else:
            axis = _lane_axis(mesh)
            n_dev = mesh.shape[axis]
            lanes = -(-(n_pol * n_scen) // n_dev) * n_dev
            padded = pad_batch(fused, lanes)
            if partitioner == "gspmd":
                shd = NamedSharding(mesh, P(axis))
                padded = jax.lax.with_sharding_constraint(padded, shd)
                out = jax.lax.with_sharding_constraint(
                    jax.vmap(run_lane)(padded), shd)
            else:
                body = jax.vmap(run_lane) if inner == "vmap" \
                    else partial(jax.lax.map, run_lane)
                out = jax.shard_map(
                    body, mesh=mesh, in_specs=(P(axis),),
                    out_specs=P(axis), check_vma=False)(padded)
            out = jax.tree_util.tree_map(
                lambda x: x[:n_pol * n_scen], out)
        return jax.tree_util.tree_map(
            lambda x: x.reshape((n_pol, n_scen) + x.shape[1:]), out)

    return jax.jit(run_grid)


def run_grid(batch: DatacenterState, vm_policies: jnp.ndarray,
             task_policies: jnp.ndarray, *, max_steps: int = 1_000_000,
             provision_policy: int = FIRST_FIT, mesh=None,
             sharded: bool | None = None,
             partitioner: str = "auto",
             dynamic: bool | None = None,
             networked: bool | None = None,
             elastic: bool | None = None,
             probed: bool | None = None, stats: bool = False
             ) -> DatacenterState | tuple[DatacenterState, engine.RunStats]:
    """Scenarios x policy grid as ONE fused, device-sharded batch.

    ``vm_policies``/``task_policies`` are i32[P] (paired — e.g. the 2x2
    Figure 3 matrix is P=4).  The P policy pairs are broadcast over the B
    stacked scenarios into a single [P*B] lane axis (``fuse_grid``), run
    in one flat ``vmap`` — sharded over the 1-D ``mesh`` when ``sharded``
    is true (default: whenever more than one device is visible, or a
    ``mesh`` is given; any axis name works) — and reshaped back to a
    [P, B, ...] final state.  The entire pipeline is one jitted XLA call
    (``_grid_runner``); ``partitioner`` is as in ``run_sharded``.

    Every lane is bit-for-bit equal to the corresponding single
    ``engine.run`` (and to ``run_grid_nested``): fusing and sharding
    change the schedule, never the per-lane math.

    ``stats=True`` returns ``(final, RunStats)``, the counters i32[P, B]:
    each lane's loop trips while live and its events retired.  Both
    settings run the same compiled program.  The call is wrapped in the
    host spans ``repro.grid`` > ``repro.grid.flags`` (policy arrays,
    sharding, the ``wants_*`` detection) and ``repro.grid.launch`` (the
    runner lookup and the asynchronous call).
    """
    with _span("repro.grid"):
        with _span("repro.grid.flags"):
            vm_policies = jnp.asarray(vm_policies, jnp.int32)
            task_policies = jnp.asarray(task_policies, jnp.int32)
            if vm_policies.shape != task_policies.shape:
                raise ValueError(
                    "vm_policies and task_policies must pair up: "
                    f"{vm_policies.shape} vs {task_policies.shape}")
            if sharded is None:
                sharded = mesh is not None or jax.device_count() > 1
            if sharded and mesh is None:
                mesh = compat.make_mesh("sweep")
            if not sharded:
                mesh = None
            if dynamic is None:
                dynamic = engine.wants_dynamic(batch)
            if networked is None:
                networked = engine.wants_network(batch)
            if elastic is None:
                elastic = engine.wants_elastic(batch)
            if probed is None:
                probed = engine.wants_probes(batch)
            n_dev = mesh.shape[_lane_axis(mesh)] if mesh is not None else 1
            resolved = _resolve_partitioner(partitioner, n_dev=n_dev,
                                            dispatch_ok=mesh is not None)
        with _span("repro.grid.launch"):
            if resolved == "dispatch":
                # host-side path: materialize the fused grid once, dispatch
                # sorted chunks, reshape back — the [P, B] layout of
                # _grid_runner
                n_pol = int(vm_policies.shape[0])
                n_scen = int(batch.time.shape[0])
                fused = fuse_grid(batch, vm_policies, task_policies)
                flat = _dispatch_run(fused, mesh, max_steps=max_steps,
                                     provision_policy=provision_policy,
                                     dynamic=dynamic, networked=networked,
                                     elastic=elastic, probed=probed)
                out, run_stats = jax.tree_util.tree_map(
                    lambda x: x.reshape((n_pol, n_scen) + x.shape[1:]), flat)
            else:
                out, run_stats = _grid_runner(
                    mesh, max_steps, provision_policy, resolved,
                    _default_inner(), dynamic, networked, elastic,
                    probed)(batch, vm_policies, task_policies)
    return (out, run_stats) if stats else out


def policy_grid() -> tuple[jnp.ndarray, jnp.ndarray]:
    """The paper's full 2x2 (vm_policy, task_policy) matrix, paired."""
    vm_p = jnp.array([0, 0, 1, 1], jnp.int32)
    task_p = jnp.array([0, 1, 0, 1], jnp.int32)
    return vm_p, task_p


# ---------------------------------------------------------------------------
# Autoscaler policy search — the fused sweep as an optimizer: thousands of
# (watermark, cooldown, price-sensitivity) points run as one flat elastic
# lane axis, then reduced to Pareto fronts by ``core/experiments.py``.
# ---------------------------------------------------------------------------
class PolicyGrid(NamedTuple):
    """P autoscaler policy points, paired element-wise (docs/elasticity.md).

    Only the *searchable* knobs live here; structural scaler config
    (fleet bounds, spot tables) stays per-scenario on the batch.
    """
    util_high: jnp.ndarray          # f32[P] scale-up watermark
    util_low: jnp.ndarray           # f32[P] scale-down watermark
    cooldown: jnp.ndarray           # f32[P] min seconds between actions
    scale_step: jnp.ndarray         # i32[P] VMs per action
    price_sensitivity: jnp.ndarray  # f32[P] spot price ceiling (0 = off)


def policy_points(util_highs: Sequence[float], util_lows: Sequence[float],
                  cooldowns: Sequence[float],
                  price_sensitivities: Sequence[float] = (0.0,),
                  scale_steps: Sequence[int] = (1,)) -> PolicyGrid:
    """Cartesian product of knob axes, dropping inverted watermark pairs
    (``util_low >= util_high`` would thrash).  Host-side NumPy."""
    pts = [(uh, ul, cd, ps, ss)
           for uh in util_highs
           for ul in util_lows if ul < uh
           for cd in cooldowns
           for ps in price_sensitivities
           for ss in scale_steps]
    if not pts:
        raise ValueError("empty policy grid (check watermark ordering)")
    uh, ul, cd, ps, ss = zip(*pts)
    return PolicyGrid(
        util_high=jnp.asarray(uh, jnp.float32),
        util_low=jnp.asarray(ul, jnp.float32),
        cooldown=jnp.asarray(cd, jnp.float32),
        scale_step=jnp.asarray(ss, jnp.int32),
        price_sensitivity=jnp.asarray(ps, jnp.float32))


def fuse_policies(batch: DatacenterState, grid: PolicyGrid
                  ) -> DatacenterState:
    """Flatten a [B] batch x P autoscaler points into [P*B] elastic lanes.

    The ``fuse_grid`` analogue for the control loop: lane ``p*B + b`` is
    scenario ``b`` with its scaler's searchable knobs overwritten by
    point ``p`` and the loop force-enabled.  Fleet bounds and spot
    tables are scenario config and broadcast unchanged.
    """
    n_pol = grid.util_high.shape[0]
    n_scen = batch.time.shape[0]

    def tile(x):
        return jnp.broadcast_to(
            x[None], (n_pol,) + x.shape).reshape((n_pol * n_scen,)
                                                 + x.shape[1:])

    fused = jax.tree_util.tree_map(tile, batch)
    rep = lambda x: jnp.repeat(x, n_scen)
    return dataclasses.replace(
        fused,
        scaler=dataclasses.replace(
            fused.scaler,
            enabled=jnp.ones((n_pol * n_scen,), jnp.int32),
            util_high=rep(grid.util_high),
            util_low=rep(grid.util_low),
            cooldown=rep(grid.cooldown),
            scale_step=rep(grid.scale_step),
            price_sensitivity=rep(grid.price_sensitivity)))


def run_policy_search(batch: DatacenterState, grid: PolicyGrid, *,
                      max_steps: int = 1_000_000,
                      provision_policy: int = FIRST_FIT,
                      mesh=None, partitioner: str = "auto",
                      dynamic: bool | None = None,
                      networked: bool | None = None) -> DatacenterState:
    """Run every (scenario, autoscaler-point) cell in one elastic sweep.

    Returns the final state reshaped to ``[P, B, ...]`` — feed it to
    ``summarize_batch`` and ``experiments.pareto_front`` for the cost /
    SLA / energy trade-off study (``examples/elasticity_study.py``).
    Pass ``mesh`` to shard the fused lane axis (as in ``run_sharded``).
    """
    n_pol = int(grid.util_high.shape[0])
    n_scen = int(batch.time.shape[0])
    fused = fuse_policies(batch, grid)
    if mesh is None:
        out = run_batch(fused, max_steps=max_steps,
                        provision_policy=provision_policy,
                        dynamic=dynamic, networked=networked, elastic=True)
    else:
        out = run_sharded(fused, mesh=mesh, max_steps=max_steps,
                          provision_policy=provision_policy,
                          partitioner=partitioner, dynamic=dynamic,
                          networked=networked, elastic=True)
    return jax.tree_util.tree_map(
        lambda x: x.reshape((n_pol, n_scen) + x.shape[1:]), out)


# ---------------------------------------------------------------------------
# Streamed (windowed) lanes — engine.run_stream over a batch axis
# ---------------------------------------------------------------------------
def stack_streams(streams: Sequence[ArrivalStream]) -> ArrivalStream:
    """Stack per-lane arrival streams into one [B, K, M] chunk table.

    Every stream must share the chunk width M (``make_stream(chunk=...)``);
    ragged chunk *counts* are padded with inert all-padding chunks
    (``vm = -1 / submit = INF``), which the chunk scan drains in one
    inactive step each — the streamed analogue of ``pad_scenario``.
    """
    if not streams:
        raise ValueError("empty stream list")
    ms = {s.vm.shape[1] for s in streams}
    if len(ms) != 1:
        raise ValueError(f"streams must share a chunk width; got {ms}")
    kmax = max(s.vm.shape[0] for s in streams)

    def grow(s: ArrivalStream) -> ArrivalStream:
        extra = kmax - s.vm.shape[0]
        if extra == 0:
            return s
        m = s.vm.shape[1]
        pad_i = jnp.full((extra, m), -1, jnp.int32)
        pad_f = jnp.zeros((extra, m), jnp.float32)
        return ArrivalStream(
            vm=jnp.concatenate([s.vm, pad_i]),
            length=jnp.concatenate([s.length, pad_f]),
            file_size=jnp.concatenate([s.file_size, pad_f]),
            output_size=jnp.concatenate([s.output_size, pad_f]),
            submit=jnp.concatenate([s.submit,
                                    jnp.full((extra, m), INF, jnp.float32)]))

    padded = [grow(s) for s in streams]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


def _stack_stream_states(streams: ArrivalStream, n_vms: int, n_slots: int,
                         reservoir: int) -> StreamState:
    """Per-lane initial ``StreamState`` carries, stacked to the lane axis.

    The reservoir stride is a host-side per-lane constant (a pure
    function of each lane's arrival count), so states are built eagerly
    lane by lane and stacked — they are tiny (O(V + W + R) per lane).
    """
    n_lanes = streams.vm.shape[0]
    per_lane = [
        make_stream_state(
            jax.tree_util.tree_map(lambda x, b=b: x[b], streams),
            n_vms, n_slots, reservoir=reservoir)
        for b in range(n_lanes)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_lane)


@lru_cache(maxsize=None)
def _stream_batch_runner(provision_policy: int, dynamic: bool,
                         networked: bool, leap: bool,
                         max_steps_per_chunk: int, mesh=None,
                         axis: str | None = None, elastic: bool = False,
                         probed: bool = False):
    """jit(vmap(engine._stream_core)) for one static config.

    ``mesh`` adds GSPMD lane-axis in/out shardings (the only sharded
    spelling offered for streams: jaxlib 0.4.37's CPU manual-sharding
    partitioner could not compile a vmapped engine step under ``shard_map``
    — ROADMAP landmine #1 — and GSPMD keeps the wide-vmap program
    identical on every backend)."""
    f = partial(engine._stream_core, provision_policy=provision_policy,
                dynamic=dynamic, networked=networked, elastic=elastic,
                probed=probed, leap=leap,
                max_steps_per_chunk=max_steps_per_chunk)
    vf = jax.vmap(f)
    if mesh is None:
        return jax.jit(vf)
    shd = NamedSharding(mesh, P(axis))
    return jax.jit(vf, in_shardings=(shd, shd, shd),
                   out_shardings=(shd, shd, shd))


def _inert_stream_lane(streams: ArrivalStream, st: StreamState
                       ) -> tuple[ArrivalStream, StreamState]:
    """One unbatched (stream, state) pair that drains in K inactive steps."""
    lane = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x[0]), streams)
    lane = dataclasses.replace(
        lane, vm=jnp.full_like(lane.vm, -1),
        submit=jnp.full_like(lane.submit, INF))
    s0 = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x[0]), st)
    s0 = dataclasses.replace(
        s0, slot_sid=jnp.full_like(s0.slot_sid, -1),
        stats=dataclasses.replace(
            s0.stats, stride=jnp.int32(1),
            res_sid=jnp.full_like(s0.stats.res_sid, -1),
            res_start=jnp.full_like(s0.stats.res_start, -1.0),
            res_finish=jnp.full_like(s0.stats.res_finish, INF)))
    return lane, s0


def run_stream_batch(batch: DatacenterState,
                     streams: ArrivalStream | Sequence[ArrivalStream], *,
                     reservoir: int = 64,
                     provision_policy: int = FIRST_FIT,
                     dynamic: bool | None = None,
                     networked: bool | None = None,
                     elastic: bool | None = None,
                     probed: bool | None = None,
                     leap: bool | None = None,
                     max_steps_per_chunk: int = 4096,
                     mesh=None, axis: str = "sweep"
                     ) -> tuple[DatacenterState, StreamState,
                                engine.StreamChunkRecord]:
    """vmap ``engine.run_stream`` over stacked windowed lanes.

    ``batch`` is a stacked scenario batch whose cloudlet block is the
    *active window* (``state.make_window``); ``streams`` is a stacked
    ``[B, K, M]`` arrival table (or a sequence, stacked via
    ``stack_streams``).  Each lane admits/retires independently; lanes
    whose stream drains early take inert steps until the whole batch
    quiesces, exactly as in ``run_batch``.  Pass ``mesh`` (1-D) to shard
    the lane axis with GSPMD in/out shardings — lane counts that do not
    divide the device count are padded with inert stream lanes and
    unpadded on return.  Per-lane results are bitwise identical to
    ``engine.run_stream`` on the unstacked lane.
    """
    if not isinstance(streams, ArrivalStream):
        streams = stack_streams(list(streams))
    if dynamic is None:
        dynamic = engine.wants_dynamic(batch)
    if networked is None:
        networked = engine.wants_network(batch)
    if elastic is None:
        elastic = engine.wants_elastic(batch)
    if probed is None:
        probed = engine.wants_probes(batch)
    if leap is None:
        leap = engine._LEAP_DEFAULT
    sts = _stack_stream_states(streams, batch.vms.req_pes.shape[-1],
                               batch.cloudlets.vm.shape[-1], reservoir)
    if mesh is None:
        runner = _stream_batch_runner(provision_policy, dynamic, networked,
                                      leap, max_steps_per_chunk,
                                      elastic=elastic, probed=probed)
        return runner(batch, sts, streams)
    axis = _lane_axis(mesh)
    n_dev = mesh.shape[axis]
    have = batch.time.shape[0]
    lanes = -(-have // n_dev) * n_dev
    if lanes != have:
        pad_s, pad_st = _inert_stream_lane(streams, sts)
        grow = lambda x, p: jnp.concatenate(
            [x, jnp.broadcast_to(p[None], (lanes - have,) + p.shape)])
        batch = pad_batch(batch, lanes)
        streams = jax.tree_util.tree_map(grow, streams, pad_s)
        sts = jax.tree_util.tree_map(grow, sts, pad_st)
    runner = _stream_batch_runner(provision_policy, dynamic, networked,
                                  leap, max_steps_per_chunk, mesh, axis,
                                  elastic=elastic, probed=probed)
    out = runner(batch, sts, streams)
    if lanes == have:
        return out
    return tuple(jax.tree_util.tree_map(lambda x: x[:have], o) for o in out)


def run_stream_grid(batch: DatacenterState,
                    streams: ArrivalStream | Sequence[ArrivalStream],
                    vm_policies: jnp.ndarray, task_policies: jnp.ndarray, *,
                    reservoir: int = 64, provision_policy: int = FIRST_FIT,
                    dynamic: bool | None = None,
                    networked: bool | None = None,
                    elastic: bool | None = None,
                    probed: bool | None = None,
                    leap: bool | None = None,
                    max_steps_per_chunk: int = 4096,
                    mesh=None, axis: str = "sweep"
                    ) -> tuple[DatacenterState, StreamState,
                               engine.StreamChunkRecord]:
    """Streamed scenarios x policy grid, fused into one [P*B] lane axis.

    The windowed analogue of ``run_grid``: each of the P policy pairs is
    broadcast over the B streamed lanes (``fuse_grid`` for the scenario
    state; a plain tile for the stream table, which carries no policy),
    run as one flat ``run_stream_batch``, and reshaped to [P, B, ...].
    """
    if not isinstance(streams, ArrivalStream):
        streams = stack_streams(list(streams))
    vm_policies = jnp.asarray(vm_policies, jnp.int32)
    task_policies = jnp.asarray(task_policies, jnp.int32)
    n_pol = vm_policies.shape[0]
    n_scen = batch.time.shape[0]
    fused = fuse_grid(batch, vm_policies, task_policies)
    tile = lambda x: jnp.broadcast_to(
        x[None], (n_pol,) + x.shape).reshape((n_pol * x.shape[0],)
                                             + x.shape[1:])
    fused_streams = jax.tree_util.tree_map(tile, streams)
    out = run_stream_batch(fused, fused_streams, reservoir=reservoir,
                           provision_policy=provision_policy,
                           dynamic=dynamic, networked=networked,
                           elastic=elastic, probed=probed, leap=leap,
                           max_steps_per_chunk=max_steps_per_chunk,
                           mesh=mesh, axis=axis)
    reshape = lambda x: x.reshape((n_pol, n_scen) + x.shape[1:])
    return tuple(jax.tree_util.tree_map(reshape, o) for o in out)


class StreamSweepSummary(NamedTuple):
    """Per-lane scalars for streamed sweeps (from ``StreamStats``)."""
    n_retired: jnp.ndarray       # i32[...]  cloudlets folded out DONE
    n_failed: jnp.ndarray        # i32[...]  dead-VM / failed arrivals
    makespan: jnp.ndarray        # f32[...]  latest completion, s
    mean_response: jnp.ndarray   # f32[...]  mean finish - submit over done
    sum_len: jnp.ndarray         # f32[...]  MI completed (work conservation)
    peak_occupancy: jnp.ndarray  # i32[...]  max cloudlets in flight
    max_backlog: jnp.ndarray     # i32[...]  max due-but-unadmitted arrivals
    energy_j: jnp.ndarray        # f32[...]  total joules over valid hosts
    transferred_mb: jnp.ndarray  # f32[...]  MB staged by completed transfers


def summarize_stream(final: DatacenterState, st: StreamState
                     ) -> StreamSweepSummary:
    """Reduce streamed-lane results (any leading batch dims) to summaries."""
    stats = st.stats
    denom = jnp.maximum(stats.n_retired.astype(jnp.float32), 1.0)
    return StreamSweepSummary(
        n_retired=stats.n_retired,
        n_failed=stats.n_failed,
        makespan=stats.makespan,
        mean_response=stats.sum_response / denom,
        sum_len=stats.sum_len,
        peak_occupancy=st.peak_occupancy,
        max_backlog=st.max_backlog,
        energy_j=energy_total_j(final),
        transferred_mb=final.net_transferred_mb,
    )


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------
class SweepSummary(NamedTuple):
    """Per-scenario scalars over the trailing entity axes.

    Leaf shape = the batch shape of the reduced state: [B] after
    ``run_batch``, [P, B] after ``run_grid``.
    """
    n_done: jnp.ndarray          # i32[...]  completed cloudlets
    makespan: jnp.ndarray        # f32[...]  latest completion, s (0 if none)
    mean_response: jnp.ndarray   # f32[...]  mean finish - submit, s, over done
    total_cost: jnp.ndarray      # f32[...]  market bill, $
    energy_j: jnp.ndarray        # f32[...]  total joules over valid hosts
    n_migrations: jnp.ndarray    # i32[...]  live migrations performed
    mig_downtime: jnp.ndarray    # f32[...]  summed migration delays, VM-s
    transferred_mb: jnp.ndarray  # f32[...]  MB moved by completed transfers
    spot_cost: jnp.ndarray       # f32[...]  accrued spot spend, $
    n_scale_up: jnp.ndarray      # i32[...]  autoscaler VM creations
    n_scale_down: jnp.ndarray    # i32[...]  autoscaler VM destructions


def summarize_batch(final: DatacenterState) -> SweepSummary:
    """Reduce a batched final state (any leading batch dims) to summaries.

    Eager reductions, under the host span ``repro.summarize``; the
    caller's fetch of the summary lies outside it."""
    with _span("repro.summarize"):
        cl = final.cloudlets
        done = cl.state == CL_DONE
        n_done = jnp.sum(done.astype(jnp.int32), axis=-1)
        makespan = jnp.max(jnp.where(done, cl.finish_time, 0.0), axis=-1)
        resp = jnp.where(done, cl.finish_time - cl.submit_time, 0.0)
        denom = jnp.maximum(n_done.astype(jnp.float32), 1.0)
        return SweepSummary(
            n_done=n_done,
            makespan=makespan,
            mean_response=jnp.sum(resp, axis=-1) / denom,
            total_cost=final.acct.total,
            energy_j=energy_total_j(final),
            n_migrations=final.mig_count,
            mig_downtime=final.mig_downtime,
            transferred_mb=final.net_transferred_mb,
            spot_cost=final.scaler.spot_cost,
            n_scale_up=final.scaler.up_count,
            n_scale_down=final.scaler.down_count,
        )
