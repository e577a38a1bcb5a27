"""Record a small trace of the benchmark's window for the reduction's test.

    python3 bench/record_trace.py --workload <name> --out <file.xplane.pb>

Runs the cell's own path and spans at a reduced width (χ = 1024, 2048
samples per batch) for a short traced window, and copies the profiler's
``.xplane.pb`` to ``--out``; ``tests/bench/test_bench_trace.py`` reduces
the copy checked in under ``bench/testdata``.  Needs the cell's chips.
"""
import argparse
import dataclasses
import os
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cell = harness.open_cell(args.workload)
    small = dataclasses.replace(
        cell, config=dict(cell.config, chi=1024),
        traffic=dict(cell.traffic, samples_per_batch=2048,
                     rows_checked_per_batch=256))
    out = harness.run_cell(small, 1, 0.5, True, t_start=time.time(),
                           keep_trace=args.out)
    harness.report(out)
