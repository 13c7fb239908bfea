"""Run one cell of the chip benchmark once, from the root of a checkout.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Refuses (exit 3, no result) where JAX finds no TPU or fewer chips than
the cell asks for.  The last line on standard output is the result.
"""
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
