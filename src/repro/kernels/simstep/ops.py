"""Dispatching wrapper for the simstep kernel: the compiled Pallas kernel
on TPU, the pure-jnp oracle on other backends (tests drive the kernel body
there with ``interpret=True``)."""
from __future__ import annotations

import jax

from repro.kernels.simstep.ref import simstep_ref
from repro.kernels.simstep.simstep import simstep_pallas

__all__ = ["simstep", "simstep_ref", "simstep_pallas"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def simstep(remaining, runnable, vm_capacity, req_pes, task_policy):
    """Fused VM-level share computation + earliest-completion reduction."""
    if _on_tpu():
        return simstep_pallas(remaining, runnable, vm_capacity, req_pes,
                              task_policy, interpret=False)
    return simstep_ref(remaining, runnable, vm_capacity, req_pes,
                       task_policy)


def to_dense(cl_vm, values, n_vms: int, slots_per_vm: int):
    """Flat grouped-by-VM cloudlet array -> dense [V, K] (uniform K)."""
    return values.reshape(n_vms, slots_per_vm)
