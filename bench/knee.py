"""Finds the knee of an open-loop cell: the highest arrival rate at
which the backlog does not grow over the window. One process, one rate
after another, on the chip:

    python3 bench/knee.py --workload qwen2-1.5b.chat --seconds 51 \\
        --rates 0.4 0.6 0.8 1.0 1.2

For each rate it prints the requests due in the window, those still
queued at its close, TTFT and TPOT and the output tokens per second.
The cell's mix then runs steadily at about 0.8 of the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


#: read beside the cell's own end-to-end metrics
EXTRA = [("ttft_mean_ms", "ms"), ("ttft_p50_ms.serve", "ms"),
         ("ttft_p90_ms", "ms"), ("tpot_p50_ms", "ms"),
         ("output_tok_s", "tokens/s")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    from bench import harness

    for rate in args.rates:
        cell = harness.load_cell(args.workload)
        arr = dict(cell.mix["arrivals"],
                   phases=[{"seconds": 10, "rate_per_s": rate}])
        cell.mix = dict(cell.mix, arrivals=arr)
        have = {m["name"] for m in cell.end_to_end}
        cell.end_to_end += [{"name": n, "unit": u} for n, u in EXTRA
                            if n not in have]
        r = harness.run_cell(cell, args.seed, args.seconds, False,
                             t_process=time.perf_counter(),
                             log=lambda m: None)
        m = {k: v["value"] for k, v in r["metrics"].items()}
        print(json.dumps({"rate_per_s": rate, "due": r["attempted"],
                          "queued_at_close": r["queued_at_close"],
                          "correct": r["correct"], **m}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
