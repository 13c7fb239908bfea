"""``energy.host_power``: the knot lookup as a select over the curve's
knots returns the same bits as a per-host gather, on every curve shape,
utilization edge and padded host, alone and under ``vmap`` over lanes as
``engine.batched_run`` calls it; and it lowers with no ``gather``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import energy
from repro.core import state as S

K = energy.K_CURVE
LANES = 4


def gather_host_power(hosts, util):
    """The per-host gather formulation ``host_power`` must match bit for
    bit."""
    u = jnp.clip(util, 0.0, 1.0) * (K - 1)
    lo = jnp.clip(u.astype(jnp.int32), 0, K - 2)
    frac = u - lo.astype(jnp.float32)
    c_lo = jnp.take_along_axis(hosts.power_curve, lo[:, None], axis=1)[:, 0]
    c_hi = jnp.take_along_axis(hosts.power_curve, (lo + 1)[:, None],
                               axis=1)[:, 0]
    c = c_lo + (c_hi - c_lo) * frac
    watts = hosts.idle_w + (hosts.peak_w - hosts.idle_w) * c
    return jnp.where(hosts.valid, watts, 0.0)


def edge_utilizations() -> np.ndarray:
    """Every knot, one ulp either side of it, 0 and 1, and outside [0, 1]."""
    knots = np.arange(K, dtype=np.float32) / np.float32(K - 1)
    return np.concatenate([
        knots,
        np.nextafter(knots, np.float32(-np.inf)),
        np.nextafter(knots, np.float32(np.inf)),
        np.float32([0.0, 1.0, -0.2, -1e-7, 1.0 + 1e-6, 1.2, 7.0]),
    ]).astype(np.float32)


CURVES = {
    "linear": lambda h, rng: energy.linear_curve(),
    "spec_g4": lambda h, rng: energy.normalize_watts(energy.SPEC_G4_WATTS)[2],
    "spec_g5": lambda h, rng: energy.normalize_watts(energy.SPEC_G5_WATTS)[2],
    "concave": lambda h, rng: jnp.linspace(0.0, 1.0, K,
                                           dtype=jnp.float32) ** 0.25,
    "random_per_host": lambda h, rng: np.sort(
        rng.random((h, K), dtype=np.float32), axis=1),
}


def fleet(curve_name: str, util: np.ndarray, seed: int):
    """A host block of ``len(util)`` hosts with per-host idle/peak watts,
    the named curve, and every third host padding (``valid`` False)."""
    rng = np.random.default_rng(seed)
    h = util.shape[0]
    idle = rng.uniform(50.0, 150.0, h).astype(np.float32)
    peak = idle + rng.uniform(10.0, 100.0, h).astype(np.float32)
    hosts = S.make_uniform_hosts(h, idle_w=idle, peak_w=peak,
                                 power_curve=CURVES[curve_name](h, rng))
    return dataclasses.replace(hosts,
                               valid=jnp.asarray(np.arange(h) % 3 != 2))


def lanes(curve_name: str):
    """``LANES`` host blocks stacked on a leading lane axis, each with its
    own utilizations (the edges and random draws in [-0.2, 1.2],
    shuffled) and watts."""
    edges = edge_utilizations()
    blocks, utils = [], []
    for lane in range(LANES):
        rng = np.random.default_rng(100 + lane)
        util = np.concatenate([
            edges, rng.uniform(-0.2, 1.2, 64).astype(np.float32)])
        util = rng.permutation(util)
        blocks.append(fleet(curve_name, util, seed=lane))
        utils.append(util)
    stack = lambda *xs: jnp.stack(xs)
    return jax.tree.map(stack, *blocks), jnp.asarray(np.stack(utils))


def bits(x) -> np.ndarray:
    return np.asarray(x, np.float32).view(np.int32)


def case(curve_name: str, batched: bool):
    """``(transform, hosts, util)``: ``jax.vmap`` over the stacked lanes,
    or the identity over the first lane alone."""
    hosts, util = lanes(curve_name)
    if batched:
        return jax.vmap, hosts, util
    return (lambda f: f), jax.tree.map(lambda x: x[0], hosts), util[0]


BATCHED = pytest.mark.parametrize("batched", [False, True],
                                  ids=["unbatched", "vmap_lanes"])


@BATCHED
@pytest.mark.parametrize("curve_name", sorted(CURVES))
def test_host_power_bitwise_equals_gather(curve_name, batched):
    over, hosts, util = case(curve_name, batched)
    got = jax.jit(over(energy.host_power))(hosts, util)
    want = jax.jit(over(gather_host_power))(hosts, util)
    assert got.shape == util.shape
    np.testing.assert_array_equal(bits(got), bits(want))
    valid = np.asarray(hosts.valid)
    assert np.all(bits(got)[~valid] == 0)          # padding draws +0 W
    assert np.all(np.asarray(got)[valid] > 0.0)


@BATCHED
def test_host_power_lowers_without_gather(batched):
    over, hosts, util = case("random_per_host", batched)
    lowered = lambda f: jax.jit(over(f)).lower(hosts, util).as_text()
    assert "gather" not in lowered(energy.host_power)
    # the control: the gather formulation does lower to a gather
    assert "gather" in lowered(gather_host_power)
