"""Federated multi-datacenter simulation over a device mesh (beyond-paper).

The paper's future work ("support for simulating federated network of
clouds") realized with JAX parallelism: every device in a mesh axis ``dc``
owns one datacenter shard and simulates it locally; the only cross-device
traffic is the CIS registry exchange (an ``all_gather`` of one descriptor
row per datacenter — exactly the register/query arrows of Figure 5) and the
broker's user->datacenter assignment, which every shard computes replicately
from the gathered table.

Because ``engine.step`` is pure and datacenters are independent between
CIS epochs, the federation scales linearly in devices: a (16,16) pod hosts
256 simulated datacenters (tens of millions of simulated hosts) in one
``shard_map`` call.  ``vmap_federation`` is the single-device reference
(identical math, used by tests to validate the sharded path).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import broker, cis
from repro.core import state as S
from repro.core.engine import run
from repro.core.provisioning import FIRST_FIT

__all__ = ["UserDemand", "assign_users", "cloudburst_assign",
           "federated_run", "vmap_federation"]


class UserDemand(NamedTuple):
    """Aggregate per-user fleet requirements the broker shops around.

    U = number of users.  ``experiments.fleet_demand`` builds this from
    per-user ``UserFleet`` specs.
    """
    pes: jnp.ndarray        # f32[U] total PEs wanted
    mips: jnp.ndarray       # f32[U] per-PE MIPS floor
    ram: jnp.ndarray        # f32[U] total RAM (MB)
    storage: jnp.ndarray    # f32[U] total storage (MB)


def assign_users(table: cis.CisEntry, demand: UserDemand, *,
                 latency: jnp.ndarray | None = None,
                 origin: jnp.ndarray | None = None,
                 latency_weight: float = 0.0) -> jnp.ndarray:
    """i32[U] — cheapest feasible datacenter per user, capacity-aware FCFS.

    Sequential greedy (earlier users consume capacity seen by later ones),
    replicated on every shard — the table is tiny (one row per DC).
    Users no datacenter can host get -1.

    Latency-aware routing (arXiv:0903.2525 §4.1's inter-entity latency
    matrix, lifted to the federation): ``latency`` is an optional
    f32[D, D] inter-datacenter latency matrix (seconds), ``origin`` the
    i32[U] home region (a row index) of each user (default: region 0),
    and ``latency_weight`` trades $ per second of WAN distance — user
    ``u`` is routed to the feasible datacenter minimizing::

        cost_per_cpu_sec[d] + latency_weight * latency[origin[u], d]

    ``latency=None`` (the default) is latency-blind routing, bit-identical
    to the pre-network broker.
    """
    if latency is not None:
        latency = jnp.asarray(latency, jnp.float32)
        n_users = demand.pes.shape[0]
        origin = (jnp.zeros((n_users,), jnp.int32) if origin is None
                  else jnp.asarray(origin, jnp.int32))
        weight = jnp.float32(latency_weight)

    def body(carry, u):
        free_pes, free_ram, free_sto = carry
        feas = ((free_pes >= demand.pes[u])
                & (table.max_mips_pe >= demand.mips[u])
                & (free_ram >= demand.ram[u])
                & (free_sto >= demand.storage[u]))
        score = table.cost_per_cpu_sec
        if latency is not None:
            nd = latency.shape[0]
            score = score + weight * latency[
                jnp.clip(origin[u], 0, nd - 1)]
        cost = jnp.where(feas, score, jnp.float32(1e30))
        pick = jnp.argmin(cost).astype(jnp.int32)
        ok = jnp.any(feas)
        d = jnp.where(ok, pick, -1)
        upd = lambda pool, amt: pool.at[pick].add(jnp.where(ok, -amt, 0.0))
        return ((upd(free_pes, demand.pes[u]),
                 upd(free_ram, demand.ram[u]),
                 upd(free_sto, demand.storage[u])), d)

    n_users = demand.pes.shape[0]
    init = (table.free_pes, table.free_ram, table.free_storage)
    _, dcs = jax.lax.scan(body, init, jnp.arange(n_users))
    return dcs


def cloudburst_assign(table: cis.CisEntry, demand: UserDemand,
                      spot, *, horizon: float,
                      latency: jnp.ndarray | None = None,
                      origin: jnp.ndarray | None = None,
                      latency_weight: float = 0.0) -> jnp.ndarray:
    """Spot-reactive cloudbursting: route marginal load by forecast price.

    The arXiv:0907.4878 burst scenario — when local capacity runs hot,
    overflow fleets shop the federation by *spot* economics rather than
    list price.  Each provider's score gains its time-averaged spot
    price over ``[0, horizon]`` (``market.mean_spot_price``), so the
    greedy FCFS broker (``assign_users``, including its latency-aware
    WAN penalty) sends each burst to the cheapest forecast provider
    with capacity.  ``spot`` is a ``market.SpotMarket`` whose provider
    rows align with the CIS table rows.
    """
    from repro.core import market as M
    bias = M.mean_spot_price(spot, horizon=horizon)
    biased = table._replace(cost_per_cpu_sec=table.cost_per_cpu_sec + bias)
    return assign_users(biased, demand, latency=latency, origin=origin,
                        latency_weight=latency_weight)


def _run_one(dc: S.DatacenterState, max_steps: int, policy: int):
    out = run(dc, max_steps=max_steps, provision_policy=policy)
    return out, broker.collect(out)


def federated_run(mesh: Mesh, dc_stack: S.DatacenterState, *,
                  axis: str = "dc", max_steps: int = 100_000,
                  provision_policy: int = FIRST_FIT):
    """Simulate D datacenters, one per device along ``axis``.

    ``dc_stack`` must have a leading axis equal to the mesh axis size D
    on every leaf (one datacenter per device — for many scenarios per
    device use ``sweep.run_sharded``, which blocks the lane axis).
    Returns ``(final stacked state [D, ...], stacked BrokerReport [D],
    gathered CIS table [D])`` — the table describes the *initial* states
    (free capacity before any placement; times in seconds, money in $).
    """
    spec = P(axis)

    @partial(
        jax.shard_map, mesh=mesh, in_specs=(spec,),
        out_specs=(spec, spec, P()), check_vma=False)
    def go(dc_block):
        dc = jax.tree.map(lambda x: x[0], dc_block)
        entry = cis.register(dc)
        table = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis), entry)
        out, rep = _run_one(dc, max_steps, provision_policy)
        lift = lambda t: jax.tree.map(lambda x: jnp.asarray(x)[None], t)
        return lift(out), lift(rep), table

    return go(dc_stack)


def vmap_federation(dc_stack: S.DatacenterState, *, max_steps: int = 100_000,
                    provision_policy: int = FIRST_FIT):
    """Single-device reference for ``federated_run`` (tests compare both).

    Same signature and [D]-leading result layout, minus the mesh.
    """
    out = jax.vmap(lambda d: run(d, max_steps=max_steps,
                                 provision_policy=provision_policy))(dc_stack)
    rep = jax.vmap(broker.collect)(out)
    table = jax.vmap(cis.register)(dc_stack)
    return out, rep, table
