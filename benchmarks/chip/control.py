"""The control: the reference in bfloat16, put in the program's place.

    python3 benchmarks/chip/control.py --workload <cell> --studies <n> \
        --seeds <a> <b> <c> ...

For each seed it makes the studies a run of the cell would make (the
first ``--studies`` of them), takes as the "program's" outputs of every
study those of the cell's deployment kind's plain reference computed in
bfloat16 (the nearest precision below the engine's float32), and runs
the same comparison a run makes.  A sound comparison refuses it:
``correct`` has to come out false.  The benchmark's own runs never run
this; it needs no chip.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import check, spec  # noqa: E402


def control(cell, seed: int, n_studies: int):
    """(correct, each number beside its limit) of the control."""
    kind = cell.kind
    mix = kind.make_mix(cell.config, cell.traffic, seed)
    studies = [mix.study(i) for i in range(n_studies)]
    outputs = kind.reference_outputs(mix, studies, "bfloat16")
    readings = kind.readings(mix, studies, outputs)
    readings["lanes_short"] = 0
    return check.verdict(readings, cell.config["checks"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--studies", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(spec.BENCH_DIR))
    cell = spec.load_cell(root, args.workload)
    for seed in args.seeds:
        correct, table = control(cell, seed % 2**64, args.studies)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": correct, "checks": table}), flush=True)


if __name__ == "__main__":
    main()
