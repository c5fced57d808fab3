"""Readings that set a cell's check limits: the program's numbers on many
seeds (the lower reading) and the control's (the upper reading), in one
process so that set-up compiles once.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <n> ...]

Each seed runs the cell's set-up, a window of ``--seconds`` at the cell's
own load and the outputs check, as a run does (``harness.start``,
``finish`` and ``judge``). Seeds in ``--control-seeds`` then judge the
control too: the plain reference computed in the precision below the one
the configuration states, put in the program's place, through the same
check, whose ``correct`` has to read false. One JSON line per seed goes to
standard output. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    opened = harness.open_cell(args.workload, "control.py")
    if opened is None:
        return 2
    cell, devices, peaks = opened

    def log(s):
        print(s, file=sys.stderr, flush=True)

    def numbers(checks):
        return {c.name: c.value for c in checks}

    for seed in args.seeds:
        t0 = time.perf_counter()
        driver, st = harness.start(cell, seed, args.seconds, devices,
                                   log=log)
        win = driver.window(st, args.seconds)
        harness.finish(driver, st, win)
        checks, correct = harness.judge(driver, st, win, log=log)
        row = {"seed": seed, "program": numbers(checks), "correct": correct,
               "metrics": win.metrics}
        if seed in args.control_seeds:
            checks, correct = harness.judge(driver, st, win, log=log,
                                            control=True)
            row["control"] = numbers(checks)
            row["control_correct"] = correct
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del st
    return 0


if __name__ == "__main__":
    sys.exit(main())
