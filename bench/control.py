"""Readings that set the limit of a cell's compared number.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 5

For each seed, in one process (set-up compiles once): one run of the cell
at its own size and load, checked as every run is, with the control read
beside the program: the reference's walk with its GEMM inputs rounded to
float8, drawing its own outcome at every checked position from the same
uniform.  Prints one JSON line per seed: the program's widest gap (the
lower reading is their largest), the control's (the upper reading is their
smallest), and whether the run was correct under the current limit.
Needs the chips the cell asks for; the benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    cell = harness.open_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=time.time(), control=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program_gap": out["checks"]["widest_gap"]["value"],
            "control_gap": out["control_gap"],
            "limit": out["checks"]["widest_gap"]["limit"],
            "correct": out["correct"], "batches": out["attempted"],
            "rate": out["metrics"]["site_samples_per_s"]["value"],
            "reference_s": out["reference_s"], "plan": out["plan"]}),
            flush=True)
