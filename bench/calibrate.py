"""Readings for the limit of a cell's `correct` check, on the chip at
the cell's own size. One process runs the cell on many seeds; each run
compares its served tokens with the float32 reference and, on the same
prompts and tokens, the float8 control:

    python3 bench/calibrate.py --workload qwen2-1.5b.chat --seconds 30 \\
        --seeds 101 102 103 ...

It prints one line per seed: the program's widest gap (the lower
reading is the largest over the seeds) and the control's (the upper
reading is the smallest), and whether each passes the cell's `correct`
predicate at the cell's limit. The control is put in the program's
place and judged by that same predicate; it has to come out not
correct on every seed, and the tool exits non-zero where it does not.
PERF.md keeps the readings and the limit set between them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from bench import harness

    cell = harness.load_cell(args.workload)
    prog, ctl, ctl_passed = [], [], 0
    for seed in args.seeds:
        r = harness.run_cell(cell, seed, args.seconds, False,
                             t_process=time.perf_counter(),
                             log=lambda m: None, control=True)
        prog.append(r["checks"]["max_logit_gap"]["value"])
        ctl.append(r["control_checks"]["max_logit_gap"]["value"])
        ctl_passed += r["control_correct"]
        print(json.dumps({"seed": seed, "program_gap": prog[-1],
                          "control_gap": ctl[-1],
                          "correct": r["correct"],
                          "control_correct": r["control_correct"],
                          "tokens": r["tokens_compared"],
                          "failed": r["failed"], **{
                              k: v["value"] for k, v in r["metrics"].items()
                          }}), flush=True)
    print(json.dumps({"lower_reading": max(prog),
                      "upper_reading": min(ctl),
                      "control_correct_runs": ctl_passed}), flush=True)
    return 1 if ctl_passed else 0


if __name__ == "__main__":
    sys.exit(main())
