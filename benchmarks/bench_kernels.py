"""Kernel-path microbenches.

Times the jitted pure-jnp oracle paths on the default backend and reports
each kernel's analytic VMEM working set per grid step, the quantity the
BlockSpecs were chosen against (v5e: ~128MB VMEM/core).  Off TPU the
Pallas kernels run only in the interpreter, whose wall time says nothing
about the chip, so they are not timed here."""
from __future__ import annotations

import time

import numpy as np


def _timeit(f, *args, reps=5):
    import jax
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.selective_scan.ref import selective_scan_ref
    from repro.kernels.simstep.ref import simstep_ref

    print(f"# kernel oracle paths ({jax.default_backend()}) + VMEM working "
          "sets (TPU design)")
    print("name,us_per_call,derived")

    # simstep: 4096 VMs x 64 slots
    rng = np.random.default_rng(0)
    v, k = 4096, 64
    rem = jnp.asarray(rng.uniform(0, 1e5, (v, k)).astype(np.float32))
    run = jnp.asarray(rng.random((v, k)) < 0.5)
    cap = jnp.asarray(rng.uniform(100, 4000, v).astype(np.float32))
    pes = jnp.ones((v,), jnp.float32)
    f = jax.jit(lambda *a: simstep_ref(*a, 1))
    dt = _timeit(f, rem, run, cap, pes)
    vmem = (8 * k * 4 * 3 + 8 * 4 * 2) / 1e3
    print(f"simstep_{v}x{k},{dt*1e6:.0f},vmem_kb_per_tile={vmem:.1f}")

    # flash attention: 1x1024x8 heads x 64
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, 1024, 8, 64))
    kk = jax.random.normal(keys[1], (1, 1024, 2, 64))
    vv = jax.random.normal(keys[2], (1, 1024, 2, 64))
    f = jax.jit(lambda a, b, c: attention_ref(a, b, c, causal=True))
    dt = _timeit(f, q, kk, vv)
    vmem = (128 * 64 * 3 * 4 + 128 * 128 * 4 + 128 * 64 * 4) / 1e3
    print(f"flash_attn_1k_gqa,{dt*1e6:.0f},vmem_kb_per_tile={vmem:.1f}")

    # selective scan: 2x512x256, N=16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    b, s, di, n = 2, 512, 256, 16
    dts = jax.nn.softplus(jax.random.normal(ks[0], (b, s, di)))
    x = jax.random.normal(ks[1], (b, s, di))
    bs = jax.random.normal(ks[2], (b, s, n))
    cs = jax.random.normal(ks[3], (b, s, n))
    a = -jnp.exp(jax.random.normal(ks[4], (di, n)))
    d = jnp.ones((di,))
    f = jax.jit(selective_scan_ref)
    dt = _timeit(f, dts, x, bs, cs, a, d)
    vmem = (256 * 256 * 4 * 2 + 256 * 16 * 4 * 3) / 1e3
    print(f"selective_scan_2x512x256,{dt*1e6:.0f},"
          f"vmem_kb_per_tile={vmem:.1f}")


if __name__ == "__main__":
    main()
