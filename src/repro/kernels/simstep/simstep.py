"""Pallas TPU kernel for the DES advance hot loop.

CloudSim's ``updateVMsProcessing`` walks Java objects per VM per event; here
one fused kernel pass computes, for a [V, K] tile resident in VMEM, the
VM-level shares (both policies, branch-free select) and the per-VM earliest
completion time.  Rows are VMs (tiled 8/sublane), slots are cloudlets
(lane dim, padded to 128) — the layout maps the two-level scheduling
reductions (FCFS rank over K, min over K) onto the MXU and lane-wise VPU ops.

Grid: (V // TV,) — each step owns a [TV, K] tile; per-VM vectors travel as
[TV, 1] column blocks (Mosaic tiles only rank-2 blocks) and the policy
code sits in SMEM.  The FCFS rank is a 0/1 lower-triangular matmul in
place of ``cumsum``, which Mosaic cannot lower: every product is 0 or 1
and accumulates in f32, so ranks are exact up to 2^24 slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = jnp.float32(1e30)
SPACE_SHARED = 0


def _simstep_kernel(policy_ref, remaining_ref, runnable_ref, cap_ref,
                    pes_ref, rates_ref, dtmin_ref):
    remaining = remaining_ref[...]                       # [TV, K]
    runnable = runnable_ref[...] & (remaining > 0.0)
    cap = cap_ref[...]                                   # [TV, 1]
    pes = jnp.maximum(pes_ref[...], 1.0)
    policy = policy_ref[0]

    k = remaining.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    upper = (row <= col).astype(jnp.float32)             # [K, K] 0/1
    run_f = runnable.astype(jnp.float32)
    rank = jnp.dot(run_f, upper, preferred_element_type=jnp.float32) - 1.0

    per_pe = cap / pes
    space = jnp.where(rank < jnp.floor(pes), per_pe, 0.0)
    n_run = jnp.sum(run_f, axis=1, keepdims=True)
    time = cap / jnp.maximum(n_run, pes)

    rates = jnp.where(policy == SPACE_SHARED, space, time)
    rates = jnp.where(runnable, rates, 0.0)
    rates_ref[...] = rates

    dt = jnp.where(rates > 0.0, remaining / jnp.maximum(rates, 1e-30),
                   jnp.float32(1e30))
    dtmin_ref[...] = jnp.min(dt, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile_v", "interpret"))
def simstep_pallas(remaining: jnp.ndarray, runnable: jnp.ndarray,
                   vm_capacity: jnp.ndarray, req_pes: jnp.ndarray,
                   task_policy, *, interpret: bool, tile_v: int = 8):
    """Pallas version of simstep_ref (see ref.py for semantics).

    ``interpret`` has no default: ``False`` compiles the kernel for the
    TPU, ``True`` runs its body in the Pallas interpreter (any backend).
    """
    v, k = remaining.shape
    pad_v = (-v) % tile_v
    col = lambda a: jnp.pad(a, (0, pad_v)).reshape(-1, 1)
    remaining = jnp.pad(remaining, ((0, pad_v), (0, 0)))
    runnable = jnp.pad(runnable, ((0, pad_v), (0, 0)))
    vm_capacity = col(vm_capacity)
    req_pes = col(req_pes)
    vp = v + pad_v
    policy = jnp.asarray(task_policy, jnp.int32).reshape(1)

    row_spec = pl.BlockSpec((tile_v, k), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((tile_v, 1), lambda i: (i, 0))
    rates, dtmin = pl.pallas_call(
        _simstep_kernel,
        grid=(vp // tile_v,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # policy
            row_spec,                                          # remaining
            row_spec,                                          # runnable
            vec_spec,                                          # capacity
            vec_spec,                                          # req_pes
        ],
        out_specs=[row_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((vp, k), jnp.float32),
            jax.ShapeDtypeStruct((vp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(policy, remaining, runnable, vm_capacity, req_pes)
    return rates[:v], dtmin[:v, 0]
