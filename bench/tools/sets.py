"""Run one cell several times, each run a process of its own, and report
the spread of every metric: the two six-run sets that bounds are set from.

    python3 bench/tools/sets.py --workload <cell> --seeds 1,2,3,4,5,6 \
        --sets 2 --seconds 30 [--trace-seeds 7,8,9] [--out runs.jsonl]

Each set runs every seed once, in order, with ``--trace 0``; then each
trace seed runs once with ``--trace 1``. The parent never imports JAX, so
each child has the chip to itself. Prints one JSON line per run and, per
set and metric, the median and the spread (distance between the first and
third quartile of ``statistics.quantiles(values, n=4)``, over the median).
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time


def run_once(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    out = {"workload": workload, "seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.time() - t0, "stderr_tail": p.stderr[-3000:]}
    try:
        out["result"] = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        out["result"] = None
    return out


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return {"median": statistics.median(values), "iqr_over_median": (q3 - q1) / statistics.median(values),
            "values": values}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = []
    for k in range(args.sets):
        for seed in seeds:
            r = run_once(args.workload, seed, args.seconds, 0)
            r["set"] = k
            runs.append(r)
            print(json.dumps({key: v for key, v in r.items() if key != "stderr_tail"}), flush=True)
    for seed in [int(s) for s in args.trace_seeds.split(",") if s]:
        r = run_once(args.workload, seed, args.seconds, 1)
        r["set"] = "trace"
        runs.append(r)
        print(json.dumps({key: v for key, v in r.items() if key != "stderr_tail"}), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            for r in runs:
                f.write(json.dumps(r) + "\n")
    summary = {}
    for k in range(args.sets):
        ok = [r["result"] for r in runs if r["set"] == k and r["result"]]
        names = sorted({m for res in ok for m in res["metrics"]})
        summary[f"set{k}"] = {m: spread([res["metrics"][m]["value"] for res in ok])
                              for m in names if len(ok) >= 2}
        summary[f"set{k}"]["correct"] = sum(res["correct"] for res in ok)
        summary[f"set{k}"]["runs"] = len(ok)
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
