"""Readings that set a cell's check limits: the program's and the control's.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

Runs the cell once per seed in this one process (set-up is paid once for
the compiles), each a full run with its window, and after each prints one
JSON line: the seed, the program's compared numbers (the lower readings)
and the control's (the reference at bf16x3 in the program's place: the
upper readings).  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchlib import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             t_process=t0, control=True, log=log)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "program": {k: v["value"]
                                      for k, v in r["checks"].items()},
                          "control": r["control"],
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()},
                          "wall_s": time.perf_counter() - t0}), flush=True)
        del r
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
