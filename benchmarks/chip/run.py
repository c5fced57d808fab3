"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout. The cell is an entry of ``workloads`` in
``BENCHMARK.json``; ``harness.py`` says how its files are found. With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. The last line of standard output is the result, a JSON object;
the last lines of standard error give each number the outputs check
compared, beside its limit.

Exits 2, before any work and with no result, when JAX finds no TPU, a
device kind that ``peaks.json`` lacks, or fewer chips than the cell asks
for.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    opened = harness.open_cell(args.workload, "run.py")
    if opened is None:
        return 2
    cell, devices, peaks = opened
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t0=T0, devices=devices,
                              peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
