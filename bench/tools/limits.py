"""Limits of a cell's comparison, from the readings ``bench/calibrate.py``
wrote (and, optionally, the program's readings in ordinary result lines).

    python3 bench/tools/limits.py --workload <cell> cal.jsonl [runs.jsonl ...]

Rule, for each number:
* lower L: the largest reading of the program over every seed given,
  floored at FLOOR (about eight float32 ulps of a relative gap: a reading
  below it is rounding, not a difference);
* upper U: the smallest reading, over the seeds, of the control where that
  is at least 3 L, and of each planted fault where that is at least 10 L;
  the least of these;
* limit: L^0.4 U^0.6 (three fifths of the way from L to U on a log scale,
  so more room above the lower than below the upper), rounded down to one
  significant digit.
A number with no upper reading gets no limit and is listed as such.
"""

from __future__ import annotations

import argparse
import json
import math
import sys

FLOOR = 1e-6


def _round_down(x: float) -> float:
    e = math.floor(math.log10(x))
    return math.floor(x / 10 ** e) * 10 ** e


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("files", nargs="+")
    args = ap.parse_args(argv)
    program, others = {}, {}
    for path in args.files:
        for line in open(path):
            row = json.loads(line)
            if row.get("workload") != args.workload:
                continue
            if "calibration" in row:
                for mode, nums in row["calibration"].items():
                    dst = program if mode == "program" else others.setdefault(mode, {})
                    for k, v in nums.items():
                        dst.setdefault(k, []).append(v)
            res = row.get("result")
            if res and "checks" in res:
                for k, c in res["checks"].items():
                    if c["value"] is not None:
                        program.setdefault(k, []).append(c["value"])
    out = {}
    for k, vals in program.items():
        lower = max(max(vals), FLOOR)
        cands = []
        for mode, nums in others.items():
            if k not in nums:
                continue
            low = min(nums[k])
            if low >= (3 if mode == "control" else 10) * lower:
                cands.append((low, mode))
        if not cands:
            out[k] = {"lower": max(vals), "seeds": len(vals), "upper": None, "limit": None}
            continue
        upper, by = min(cands)
        limit = _round_down(lower ** 0.4 * upper ** 0.6)
        out[k] = {"lower": max(vals), "seeds": len(vals), "upper": upper, "upper_from": by,
                  "limit": limit}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
