"""Decides ``correct``: each compared number against its limit.

After the window has closed, the cell's deployment kind compares the
timed studies' outputs with its plain reference (``readings``, in
``deployments/<kind>.py``), and the harness adds ``lanes_short``, the
lanes that fell short of their work in the studies' summaries.  Each
number has its limit in the deployment file's ``checks``; the two sets of
names must be the same.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


class Mismatch(ValueError):
    """The readings and the deployment file's ``checks`` name different
    numbers: a number compared with no limit, or a limit with nothing to
    compare."""


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.broadcast_to(np.asarray(want, np.float64), got.shape)
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.abs(want), 1e-30)))


def verdict(readings: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, dict]]:
    """``correct`` and each number beside its limit.  Raises ``Mismatch``
    where the names of the readings and of the limits differ."""
    if set(readings) != set(limits):
        raise Mismatch(
            f"readings without a limit: {sorted(set(readings) - set(limits))}"
            f"; limits without a reading: "
            f"{sorted(set(limits) - set(readings))}")
    table = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    return all(readings[k] <= limits[k] for k in limits), table
