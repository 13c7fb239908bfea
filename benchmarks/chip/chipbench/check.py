"""Decides ``correct``: the timed studies' outputs against the reference.

After the window has closed, the plain reference (``chipbench.reference``)
replays the scenario once under each policy pair, and every lane of every
study the window ran is compared with the replay of its pair, slot by
slot: VM placements and cloudlet states exactly, start/finish times and
the clock at quiescence, and per-host energy, by relative error.
Besides, every lane of every study must have completed every cloudlet.

Each compared number has its limit in the deployment file's ``checks``.
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence, Tuple

import numpy as np

from chipbench import reference
from chipbench.traffic import Mix, Pair

NAMES = ("placements_wrong", "states_wrong", "time_rel_err",
         "energy_rel_err", "lanes_short")


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.broadcast_to(np.asarray(want, np.float64), got.shape)
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.abs(want), 1e-30)))


def lane_readings(got: dict, ref: reference.Result) -> Dict[str, float]:
    """The compared numbers of the lanes in ``got`` (host arrays, with
    any number of leading lane axes), all run under ``ref``'s pair."""
    done = ref.cl_state == reference.CL_DONE
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


def compare(mix: Mix, studies: Sequence[Tuple[Pair, ...]],
            output: Callable[[int, int], dict]
            ) -> Tuple[Dict[str, float], int]:
    """Worst readings over every lane of ``studies``, and the number of
    lanes compared.  ``output(s, p)`` gives the lanes of study ``s`` under
    its ``p``-th pair as host arrays, as ``pair_of`` cuts them."""
    worst = {"placements_wrong": 0, "states_wrong": 0,
             "time_rel_err": 0.0, "energy_rel_err": 0.0}
    replays: Dict[Pair, reference.Result] = {}
    for pair in sorted({p for study in studies for p in study}):
        replays[pair] = reference.simulate(mix.lane(pair))
    for s, study in enumerate(studies):
        for p, pair in enumerate(study):
            for k, v in lane_readings(output(s, p), replays[pair]).items():
                worst[k] = max(worst[k], v)
    return worst, len(studies) * len(studies[0]) * mix.replicates


def study_outputs(mix: Mix, outputs: Sequence[dict]):
    """``output(s, p)`` over the studies' outputs as host arrays."""
    return lambda s, p: pair_of(outputs[s], mix, p)


def result_arrays(res: reference.Result) -> dict:
    """A reference result in the shape of one lane's outputs."""
    return {"cl_state": res.cl_state, "start_time": res.start_time,
            "finish_time": res.finish_time, "vm_host": res.vm_host,
            "energy_j": res.energy_j, "time": res.time}


def lanes_short(mix: Mix, n_done: np.ndarray) -> int:
    """Lanes of one study that did not complete every cloudlet."""
    return int(np.sum(np.asarray(n_done) != len(mix.cloudlets.vm)))


def verdict(readings: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, dict]]:
    """``correct`` and each number beside its limit."""
    table = {k: {"value": readings[k], "limit": limits[k]} for k in NAMES}
    return all(readings[k] <= limits[k] for k in NAMES), table
