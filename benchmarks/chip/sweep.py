"""Find the highest rate an open-loop serving cell sustains (the knee):
serve the cell's mix at each of several rates, one after the other in one
process, and report whether the queue grew across the window.

    python3 benchmarks/chip/sweep.py --workload <name> --seconds <s> \
        --seed <n> --rates <r> [<r> ...] [--warm <s>]

``--warm`` replaces the mix's warm-up, so that the slots fill before the
window even where requests live longer than the cell's warm-up.

The knee is found once, when a cell is defined, and its traffic file then
fixes the rate; the benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--warm", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    opened = harness.open_cell(args.workload, "sweep.py")
    if opened is None:
        return 2
    cell, devices, peaks = opened
    for rate in args.rates:
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        if args.warm is not None:
            cell.traffic["warm_s"] = args.warm
        driver, st = harness.start(cell, args.seed, args.seconds, devices,
                                   log=lambda s: print(s, file=sys.stderr))
        win = driver.window(st, args.seconds)
        done = sum(1 for tr in st.tracked if tr.req.done_step >= 0
                   and tr.times[-1] >= st.window_lo)
        print(json.dumps({"rate_per_s": rate, "metrics": win.metrics,
                          "due": win.attempted,
                          "finished_in_window": done,
                          "queued": win.context["queued"],
                          "active": len(st.engine.scheduler.active),
                          "steps": win.context["steps"],
                          "step_ms": 1e3 * win.seconds
                          / max(win.context["steps"], 1),
                          "tokens_per_s": win.context["tokens"]
                          / win.seconds}), flush=True)
        driver.free_program_state(st)
        del st
    return 0


if __name__ == "__main__":
    sys.exit(main())
