"""HLO collective parser + roofline term math (incl. the cost_analysis
per-device calibration referenced from launch/hlo_analysis.py)."""
import numpy as np
import pytest

from repro.launch.hlo_analysis import (
    HW,
    collective_bytes,
    roofline_terms,
)

HLO_SAMPLE = """
HloModule jit_f

ENTRY %main {
  %p0 = f32[4096]{0} parameter(0)
  ROOT %all-reduce = f32[4096]{0} all-reduce(%p0), channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add
}
"""

HLO_MIXED = """
  %ag = bf16[1024,512]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %rs = f32[256]{0} reduce-scatter(%y), replica_groups=[2,4]<=[8], dimensions={0}
  %a2a = f32[64,64]{1,0} all-to-all(%z), replica_groups={{0,1}}
  %cp = f32[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %ar-start = f32[32]{0} all-reduce-start(%v), replica_groups={{0,1,2,3}}
  %ar-done = f32[32]{0} all-reduce-done(%ar-start)
"""


def test_all_reduce_ring_cost():
    st = collective_bytes(HLO_SAMPLE)
    assert st.counts["all-reduce"] == 1
    size = 4096 * 4
    np.testing.assert_allclose(st.by_kind["all-reduce"],
                               2 * size * 7 / 8, rtol=1e-6)


def test_mixed_collectives():
    st = collective_bytes(HLO_MIXED)
    assert st.counts == {"all-reduce": 1, "all-gather": 1,
                         "reduce-scatter": 1, "all-to-all": 1,
                         "collective-permute": 1}
    np.testing.assert_allclose(st.by_kind["all-gather"],
                               1024 * 512 * 2 * 3 / 4, rtol=1e-6)
    np.testing.assert_allclose(st.by_kind["reduce-scatter"],
                               256 * 4 * 3, rtol=1e-6)
    np.testing.assert_allclose(st.by_kind["collective-permute"],
                               128 * 4, rtol=1e-6)
    # async start counted once, done skipped
    np.testing.assert_allclose(st.by_kind["all-reduce"],
                               2 * 32 * 4 * 3 / 4, rtol=1e-6)


def test_roofline_terms_math():
    r = roofline_terms(hlo_flops=197e12 * 0.1,       # 100ms of compute
                       hlo_bytes=819e9 * 0.05,       # 50ms of HBM
                       collective_wire_bytes=150e9 * 0.2,  # 200ms of ICI
                       chips=256,
                       model_flops=197e12 * 0.08 * 256)   # 80ms useful
    np.testing.assert_allclose(r["compute_s"], 0.1, rtol=1e-6)
    np.testing.assert_allclose(r["memory_s"], 0.05, rtol=1e-6)
    np.testing.assert_allclose(r["collective_s"], 0.2, rtol=1e-6)
    assert r["dominant"] == "collective_s"
    np.testing.assert_allclose(r["useful_flops_ratio"], 0.8, rtol=1e-6)
    np.testing.assert_allclose(r["roofline_fraction"], 0.08 / 0.2,
                               rtol=1e-6)


@pytest.mark.slow
@pytest.mark.subprocess
def test_cost_analysis_is_per_device():
    """Calibration: an SPMD-partitioned module reports PER-DEVICE flops.

    Runs in a subprocess so the fake devices never leak into this
    process's jax runtime.  2 forced devices (not 8): the per-device
    division is the property under test, and 8 single-core XLA device
    instances made this time out on slow 2-core hosts; if even that
    can't compile in time (loaded CI box), skip rather than fail —
    the calibration is environment-bound, not a code property."""
    import subprocess
    import sys
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
os.environ.pop('JAX_PLATFORMS', None)
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
mesh = jax.make_mesh((2,), ('x',))
A = jax.ShapeDtypeStruct((1024, 512), jnp.float32)
B = jax.ShapeDtypeStruct((512, 256), jnp.float32)
f = jax.jit(lambda a, b: a @ b,
            in_shardings=(NamedSharding(mesh, P('x', None)),
                          NamedSharding(mesh, P())),
            out_shardings=NamedSharding(mesh, P('x', None)))
ca = f.lower(A, B).compile().cost_analysis()
total = 2 * 1024 * 512 * 256
assert abs(ca['flops'] - total / 2) / total < 0.01, ca['flops']
print('OK')
"""
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=300)
    except subprocess.TimeoutExpired:
        pytest.skip("forced-2-device XLA compile exceeded 300 s "
                    "(slow/loaded host)")
    assert "OK" in out.stdout, out.stderr[-2000:]
