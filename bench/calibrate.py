"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8 [--out file]

For each seed, one run of the cell (set-up, a short window at the cell's
own load, the captures); then the numbers of the comparison for the
program, for the control (the reference at bfloat16 put in the program's
place) and for each planted fault (the float32 reference with the fault,
put in the program's place). All in one process, one seed after another.
Prints one JSON line per seed. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import runpy
import sys

FAULTS = ("control", "unchanged", "half_batch", "stale_node", "uniform_sampling",
          "altered_row")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    run = runpy.run_path(str(pathlib.Path(__file__).with_name("run.py")))
    for seed in (int(s) for s in args.seeds.split(",")):
        result, lines = run["run_cell"](args.workload, seed, args.seconds, False,
                                        calibrate=FAULTS)
        row = {"workload": args.workload, "seed": seed, "correct": result["correct"],
               "metrics": result["metrics"], "device": result["device"],
               "calibration": result["calibration"], "run": lines[0]}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
