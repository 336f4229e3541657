"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chip this process is started on
and prints, as the last line of standard output, one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and, traced,
`breakdown`), and last `checks`, each number compared beside its limit.
The same comparisons end standard error. With `--trace 0` the metrics
are the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics. Exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell needs (3), or when a program compiled inside
the measured window (4).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS,
                                  log=log)
    except harness.NoChip as e:
        log(f"bench: {e}")
        return 3
    except harness.WindowCompiled as e:
        log(f"bench: {e}")
        return 4
    for name, r in result["checks"].items():
        log(f"check {name}: {r['value']} (limit {r['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
