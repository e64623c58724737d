#!/usr/bin/env python3
"""Readings for the limits of ``correct``: many seeds of one cell in one
process, with the program as it is or with its control path switched on.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds 10
    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds 10 \\
        --control hash32

Each seed runs the cell's whole run (set-up, window at the cell's own load,
comparison with the reference) and prints one JSON line with every number
compared.  ``--control hash32`` runs the program with the 32-bit hash, the
precision below the 64-bit hash the configurations state, while the
reference keeps 64 bits: a sound limit fails it.  The benchmark's own runs
never run a control.  Off TPU it refuses, as a run does, unless
``CHIPBENCH_REHEARSAL=1``.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench.bench import harness  # noqa: E402

CONTROLS = {"hash32": {"hash_bits": 32}}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", choices=sorted(CONTROLS))
    args = ap.parse_args(argv)
    rehearsal = os.environ.get(harness.REHEARSAL_ENV) == "1"
    cell = harness.load_cell(args.workload, rehearsal)
    peaks, why = harness.device_check(cell, rehearsal)
    if why:
        return harness.refuse(why)
    control = CONTROLS.get(args.control)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result = harness.execute(
            cell, seed, args.seconds, False, t0, peaks, control,
            say=lambda line: print(line, file=sys.stderr),
        )
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": args.control,
            "correct": result["correct"], "checks": result["checks"],
            "metrics": result["metrics"], "seconds": time.perf_counter() - t0,
        }), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
