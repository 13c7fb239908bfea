"""Host power models + energy integration — the paper's energy axis.

The CloudSim paper puts "energy performance (power consumption, heat
dissipation)" on equal footing with scheduling performance, and the
power-aware provisioning studies around it (arXiv:0907.4878) model a
host's electrical draw as a function of CPU utilization.  This module
carries that model on the dense state:

  * every host owns ``idle_w``/``peak_w`` watts and a *normalized*
    utilization→power curve ``power_curve f32[H, K]`` (K = ``K_CURVE``
    control points at utilizations 0, 1/(K-1), ..., 1),
  * instantaneous power is ``idle_w + (peak_w - idle_w) *
    interp(curve, utilization)`` — the linear model is the identity
    curve, SPECpower-style models are measured piecewise-linear curves,
  * energy is the integral of power over the event timeline.  Execution
    rates — hence utilizations, hence power — are piecewise-constant
    between events (see ``core/engine.py``), so the trapezoidal rule
    over the timeline is *exact* and collapses to ``sum(P_i * dt_i)``:
    the engine accrues ``power * dt`` joules per host per event.

Units: power in watts (J/s), energy in joules, utilization in [0, 1]
(consumed MIPS / capacity MIPS).  All functions are pure and jit/vmap
safe; the NumPy oracle (``repro.oracle``) re-implements the same math
independently for differential testing (see ``docs/conformance.md``).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

__all__ = ["K_CURVE", "SPEC_G4_WATTS", "SPEC_G5_WATTS", "linear_curve",
           "normalize_watts", "make_power_model", "with_power_model",
           "host_power", "host_utilization", "step_power",
           "energy_total_j"]

# number of control points per curve: utilizations 0%, 10%, ..., 100%
# (the SPECpower_ssj2008 reporting grid).
K_CURVE = 11

# Published SPECpower-style measurement ladders (watts at 0..100%
# utilization in 10% steps) for two commodity servers — the same shape
# of data CloudSim's power package ships.  Used via ``normalize_watts``.
SPEC_G4_WATTS = (86.0, 89.4, 92.6, 96.0, 99.5, 102.0, 106.0, 108.0,
                 112.0, 114.0, 117.0)          # HP ProLiant ML110 G4
SPEC_G5_WATTS = (93.7, 97.0, 101.0, 105.0, 110.0, 116.0, 121.0, 125.0,
                 129.0, 133.0, 135.0)          # HP ProLiant ML110 G5


def linear_curve() -> jnp.ndarray:
    """f32[K] — the identity curve: power scales linearly idle→peak."""
    return jnp.linspace(0.0, 1.0, K_CURVE, dtype=jnp.float32)


def normalize_watts(watts) -> tuple[float, float, jnp.ndarray]:
    """(idle_w, peak_w, f32[K] normalized curve) from a watts ladder.

    ``watts`` is a length-``K_CURVE`` sequence of measured watts at
    utilizations 0, 0.1, ..., 1.0 (e.g. ``SPEC_G4_WATTS``).  The curve
    stores ``(w - w[0]) / (w[-1] - w[0])`` so the same ladder can be
    rescaled to any idle/peak pair.
    """
    w = np.asarray(watts, np.float64)
    if w.shape != (K_CURVE,):
        raise ValueError(f"watts ladder must have {K_CURVE} points, "
                         f"got shape {w.shape}")
    span = w[-1] - w[0]
    if span <= 0:
        raise ValueError("peak watts must exceed idle watts")
    curve = jnp.asarray((w - w[0]) / span, jnp.float32)
    return float(w[0]), float(w[-1]), curve


def make_power_model(n_hosts: int, idle_w, peak_w, curve=None
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(idle_w f32[H], peak_w f32[H], power_curve f32[H, K]) field triple.

    ``idle_w``/``peak_w`` broadcast from scalars or per-host sequences;
    ``curve`` is a normalized f32[K] (default ``linear_curve()``) or a
    per-host f32[H, K] block.
    """
    f = lambda x: jnp.broadcast_to(
        jnp.asarray(x, jnp.float32), (n_hosts,)).astype(jnp.float32)
    idle = f(idle_w)
    peak = f(peak_w)
    c = linear_curve() if curve is None else jnp.asarray(curve, jnp.float32)
    if c.ndim == 1:
        c = jnp.broadcast_to(c[None], (n_hosts, K_CURVE))
    if c.shape != (n_hosts, K_CURVE):
        raise ValueError(f"curve must be [K]={K_CURVE} or "
                         f"[H={n_hosts}, {K_CURVE}]; got {c.shape}")
    return idle, peak, c


def with_power_model(hosts, idle_w, peak_w, curve=None):
    """A copy of a ``HostState`` with the power-model fields attached.

    Example — a fleet of SPECpower-curve hosts::

        idle, peak, curve = energy.normalize_watts(energy.SPEC_G4_WATTS)
        hosts = energy.with_power_model(S.make_uniform_hosts(64),
                                        idle, peak, curve)
    """
    n = hosts.num_pes.shape[0]
    idle, peak, c = make_power_model(n, idle_w, peak_w, curve)
    return dataclasses.replace(hosts, idle_w=idle, peak_w=peak,
                               power_curve=c)


def host_power(hosts, util: jnp.ndarray) -> jnp.ndarray:
    """f32[H] instantaneous watts at per-host utilization ``util``.

    Piecewise-linear interpolation of each host's normalized curve at
    ``util`` (clamped to [0, 1]), scaled into [idle_w, peak_w].  Invalid
    (padded) hosts draw exactly 0 W, which keeps scenario padding and
    inert sweep lanes energy-neutral.

    The knot lookup is a select over the ``K_CURVE`` knots summed along
    the knot axis, not a per-host gather: a TPU gathers one element at a
    time, while the select fuses into elementwise work.  Each sum adds one
    knot to exact zeros, so it is bit-exact with the gather.
    """
    u = jnp.clip(util, 0.0, 1.0) * (K_CURVE - 1)
    lo = jnp.clip(u.astype(jnp.int32), 0, K_CURVE - 2)    # i32[H]
    frac = u - lo.astype(jnp.float32)
    k = jnp.arange(K_CURVE, dtype=jnp.int32)
    knot = lambda i: jnp.sum(jnp.where(k == i[:, None], hosts.power_curve,
                                       0.0), axis=1)
    c_lo = knot(lo)
    c_hi = knot(lo + 1)
    c = c_lo + (c_hi - c_lo) * frac
    watts = hosts.idle_w + (hosts.peak_w - hosts.idle_w) * c
    return jnp.where(hosts.valid, watts, 0.0)


def host_utilization(dc, rates: jnp.ndarray) -> jnp.ndarray:
    """f32[H] consumed MIPS / capacity MIPS per host, given cloudlet rates.

    ``rates f32[C]`` is the ``scheduling.cloudlet_rates`` output; a
    cloudlet's rate lands on its VM's host.  Rates are zero for
    non-runnable cloudlets, so clipped gather targets never contribute.
    """
    import jax

    nh = dc.hosts.num_pes.shape[0]
    nv = dc.vms.req_pes.shape[0]
    host_of_cl = dc.vms.host[jnp.clip(dc.cloudlets.vm, 0, nv - 1)]
    consumed = jax.ops.segment_sum(
        rates, jnp.clip(host_of_cl, 0, nh - 1), num_segments=nh)
    cap = dc.hosts.capacity_mips
    return jnp.where(cap > 0.0, consumed / jnp.maximum(cap, 1e-30), 0.0)


def step_power(dc, rates: jnp.ndarray) -> jnp.ndarray:
    """f32[H] watts drawn by each host while ``rates`` hold (one event)."""
    return host_power(dc.hosts, host_utilization(dc, rates))


def energy_total_j(dc) -> jnp.ndarray:
    """f32[...] total joules accrued across real hosts (any batch dims).

    Filters on ``num_pes > 0`` (real vs padding slot), not ``valid`` —
    ``valid`` is dynamic since host-failure events exist, and a host
    that failed mid-run must keep its pre-failure joules in the fleet
    total (padding slots accrue exactly 0, so they drop out either way).
    """
    return jnp.sum(jnp.where(dc.hosts.num_pes > 0, dc.hosts.energy_j, 0.0),
                   axis=-1)
