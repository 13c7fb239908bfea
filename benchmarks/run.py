"""Benchmark harness entry point — one section per paper table/figure plus
the beyond-paper engine/kernel benches.  Prints ``name,us_per_call,derived``
CSV throughout (PYTHONPATH=src python -m benchmarks.run).  Exits non-zero
when a bench phase failed."""
from __future__ import annotations

import sys


def main() -> int:
    from benchmarks import bench_policies

    # First, while this process is still off JAX: its phases run in
    # children, and a device belongs to one process at a time.
    rc = bench_policies.main()       # paper Fig 8 & 9
    print()

    from benchmarks import bench_engine, bench_instantiation, bench_kernels
    from repro import compat

    compat.use_compile_cache()
    bench_instantiation.main()       # paper Fig 6 & 7
    print()
    bench_engine.main()              # beyond paper: DES throughput
    print()
    bench_kernels.main()             # kernel paths

    # roofline table if dry-run artifacts exist
    import os
    if os.path.isdir("artifacts/dryrun"):
        print("\n# roofline (from dry-run artifacts; see EXPERIMENTS.md)")
        from benchmarks import roofline
        rows = roofline.load("artifacts/dryrun")
        if rows:
            print(f"# {len(rows)} cells analyzed — table in EXPERIMENTS.md")
    return rc


if __name__ == "__main__":
    sys.exit(main())
