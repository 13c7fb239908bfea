"""The main path compiles for a TPU v5e chip that is described, not attached.

Each test hands the TPU compiler a program at its real shapes and checks
what only that compiler can refuse: Mosaic lowering of the Pallas kernel,
and the device memory of the engine and sweep programs.  Nothing runs, so
these say nothing about results or speed.

The topology is described inside a fixture: only one process at a time
may load the TPU library, so describing it while a module is imported
would make every other test worker fail to collect.  Code that asks
``jax.default_backend()`` still sees the CPU here, so the engine's static
flags are passed explicitly rather than auto-detected.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import broker as B, engine, state as S, sweep
from repro.core.provisioning import FIRST_FIT
from repro.kernels.simstep import simstep_pallas

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler installed / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("runnable_dtype", [jnp.bool_, jnp.int32])
def test_simstep_kernel_compiles_for_v5e(one_chip, runnable_dtype):
    v, k = 4096, 128
    args = _shapes((jnp.zeros((v, k), jnp.float32),
                    jnp.zeros((v, k), runnable_dtype),
                    jnp.zeros((v,), jnp.float32),
                    jnp.zeros((v,), jnp.float32),
                    jnp.int32(0)), one_chip)
    compiled = simstep_pallas.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_run_fig8_compiles_for_v5e(one_chip):
    """Paper Fig 8/9 at full scale: 10,000 hosts, 50 VMs, 500 cloudlets."""
    hosts = S.make_uniform_hosts(10_000)
    vms = B.build_fleet([B.VmSpec(count=50, pes=1, mips=1000.0, ram=512.0,
                                  bw=10.0, size=1000.0)])
    cl = B.build_waves(50, B.WaveSpec(waves=10, length_mi=1_200_000.0,
                                      period=600.0))
    dc = S.make_datacenter(hosts, vms, cl, task_policy=S.TIME_SHARED,
                           reserve_pes=True)
    run = jax.jit(partial(engine.run, max_steps=8192, dynamic=False,
                          networked=False, elastic=False, probed=False))
    compiled = run.lower(_shapes(dc, one_chip)).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES


def test_grid_runner_compiles_for_v5e(one_chip):
    """The one-chip fused policy grid (``sweep.run_grid``'s program)."""
    def scenario(seed):
        rng = np.random.default_rng(seed)
        hosts = S.make_uniform_hosts(64, pes=2, ram=2048.0)
        vms = B.build_fleet([B.VmSpec(count=16, pes=1, mips=1000.0,
                                      ram=512.0, bw=10.0, size=1000.0)])
        cl = B.build_waves(16, B.WaveSpec(
            waves=1 + seed % 4,
            length_mi=float(rng.integers(600, 1200) * 1000), period=300.0))
        return S.make_datacenter(hosts, vms, cl, reserve_pes=True)

    batch = sweep.stack_scenarios([scenario(s) for s in range(4)])
    vm_p, task_p = sweep.policy_grid()
    runner = sweep._grid_runner(None, 4096, FIRST_FIT, "gspmd", "vmap",
                                False, False, False, False)
    compiled = runner.lower(*_shapes((batch, vm_p, task_p),
                                     one_chip)).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES
