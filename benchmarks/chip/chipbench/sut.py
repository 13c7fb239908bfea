"""The system under test, entered only through its public API.

``state.make_*`` builds each scenario, ``sweep.stack_scenarios`` stacks
a grid's replicates, ``engine.run`` or ``sweep.run_grid`` runs a study,
and ``sweep.summarize_batch`` reduces it to the summary a researcher's
script fetches.  The scenario is built once, in set-up, on the host's
CPU backend where JAX has one, and put on the chip in one transfer; a
study only swaps in its policy pairs.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import numpy as np

from chipbench.traffic import Mix, Pair


class Outputs(NamedTuple):
    """What the comparison reads of a study's final state, [P, R, ...]
    for a grid and unbatched for a single run (device arrays)."""
    cl_state: object
    start_time: object
    finish_time: object
    vm_host: object
    energy_j: object
    time: object


class System:
    def __init__(self, mix: Mix, chips: int):
        import jax
        from repro import compat
        from repro.core import engine, state as S, sweep
        self.jax, self.engine, self.sweep = jax, engine, sweep
        self.mix = mix
        self.devices = jax.devices()[:chips]
        self.mesh = (compat.make_mesh("sweep", self.devices) if chips > 1
                     else None)
        if mix.runner == "engine.run" and chips > 1:
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
        self.template = jax.block_until_ready(
            jax.device_put(dc, self.devices[0]))
        self._pairs = {}

    def _policies(self, pairs: Tuple[Pair, ...]):
        """The study's policy arrays on the chip, put there once per
        order: (vm, task) scalars for a single run, i32[P] each for a
        grid."""
        if pairs not in self._pairs:
            put = lambda x: self.jax.device_put(np.asarray(x, np.int32),
                                                self.devices[0])
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
        dc, vm_p, task_p = inputs
        if self.mix.runner == "engine.run":
            return self.engine.run(dc, max_steps=self.mix.max_steps)
        if self.mesh is None:
            return self.sweep.run_grid(dc, vm_p, task_p,
                                       max_steps=self.mix.max_steps,
                                       sharded=False)
        return self.sweep.run_grid(dc, vm_p, task_p,
                                   max_steps=self.mix.max_steps,
                                   mesh=self.mesh)

    def summary(self, out):
        """The study's summary, on the host."""
        return self.jax.device_get(self.sweep.summarize_batch(out))

    @staticmethod
    def keep(out) -> Outputs:
        """The leaves the comparison reads; the rest of ``out`` is freed."""
        return Outputs(out.cloudlets.state, out.cloudlets.start_time,
                       out.cloudlets.finish_time, out.vms.host,
                       out.hosts.energy_j, out.time)

    def peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))
