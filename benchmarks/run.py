"""Benchmark harness (deliverable d): one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Sections:
  table1/*    Table I    relative clock frequencies
  fig1/*      Fig. 1     ideal scaling vs RIMA
  table4/*    Table IV   reduction latency models
  table5/*    Table V    PiCaSO-IM block utilization deltas
  fig5/*      Fig. 5     100%-BRAM scalability across devices
  table8/*    Table VIII system comparison / gold scores
  fig7/*      Fig. 7     GEMV cycle latency + execution time
  fig7sim/*   Fig. 7     cycle-accurate simulator validation
  table9/*    Table IX   curve-fitted (a, b, c) + interpretations
  kernel/*    TPU adaptation: bit-plane GEMV bandwidth amplification,
              paged-attention gather parity + streamed-bytes accounting,
              length-bucketed dispatch raggedness sweep + serve smoke
  reduction/* collective schedule byte models
  roofline/*  per-cell roofline terms from the dry-run artifacts
  serve/*     continuous-batching throughput, dense vs paged KV cache,
              TTFT/TPOT percentiles + streamed-byte telemetry, and the
              metrics-on/off overhead + bit-exactness guard
  prefix/*    shared-prefix serving, prefix-indexed vs unshared paged
"""

from __future__ import annotations

import sys


def main() -> None:
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    from .kernel_bench import (
        bucketed_serve_smoke,
        kernel_bench,
        paged_attention_bench,
        reduction_schedule_bench,
    )
    from .paper_tables import (
        fig1_scaling,
        fig5_scalability,
        fig7_gemv,
        fig7_simulator_validation,
        table1_frequency,
        table4_reduction,
        table5_utilization,
        table8_systems,
        table9_curvefit,
    )
    from .prefix_bench import prefix_bench, windowed_prefix_bench
    from .roofline_bench import roofline_bench
    from .serve_bench import metrics_overhead_bench, serve_bench

    sections = [
        table1_frequency, fig1_scaling, table4_reduction, table5_utilization,
        fig5_scalability, table8_systems, fig7_gemv,
        fig7_simulator_validation, table9_curvefit, kernel_bench,
        paged_attention_bench, bucketed_serve_smoke,
        reduction_schedule_bench, roofline_bench,
        serve_bench, prefix_bench, windowed_prefix_bench,
        metrics_overhead_bench,
    ]
    print("name,us_per_call,derived")
    failures = 0
    for fn in sections:
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # keep the harness running; report at exit
            failures += 1
            print(f"{fn.__name__}/ERROR,0.0,{type(e).__name__}:{e}", file=sys.stdout)
    # append this run's normalized headline record to the bench history
    # (DESIGN.md §14) — failed sections are recorded too, so the history
    # never silently skips a bad run
    from repro.obs import regress

    record = regress.make_record("results", extra={"failures": failures})
    regress.append_history("results/history.jsonl", record)
    print(f"history,0.0,appended={record['config_hash']};"
          f"sha={record['git_sha']};failures={failures}")
    if failures:
        raise SystemExit(f"{failures} benchmark sections failed")


if __name__ == "__main__":
    main()
