"""The one generator: a deployment file and a traffic file make studies.

A deployment (``configs/<name>.json``) fixes the fleet (``hosts``), the
VM class (``vms``) and the wave workload (``cloudlets``: ``waves`` waves
of one ``length_mi`` cloudlet per VM, ``period_s`` apart).  A traffic mix
(``traffic/<name>.json``) says which runner a study goes through, which
(vm, task) policy pairs it runs and how many copies of the scenario a
grid study stacks.  The seed orders the policy pairs and nothing else,
so every seed runs the same work: the same seed gives the same studies,
in every run and on every machine.

Keys of a traffic file:

``runner``
    ``"engine.run"``: a study is the scenario under one policy pair,
    taken in seeded order from a shuffled cycle of ``policy_pairs``.
    ``"sweep.run_grid"``: a study is ``replicates`` copies of the
    scenario x all ``policy_pairs`` in an order drawn from the seed,
    fused into one call.
``policy_pairs``
    ``[[vm_policy, task_policy], ...]``; 0 is space-shared, 1 time-shared.
``replicates``
    scenarios a grid study stacks (1 for ``engine.run``).
``max_steps``
    the runner's event budget per lane.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

RUNNERS = ("engine.run", "sweep.run_grid")
Pair = Tuple[int, int]


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

    def lane(self, pair: Pair) -> Lane:
        """The plain inputs of a lane under ``pair``, for the reference."""
        return Lane(self.hosts, self.vms, self.cloudlets, pair[0], pair[1],
                    self.reserve_pes)
