"""Predicted-vs-measured performance attribution (DESIGN.md §14).

The paper's evaluation method is *measured next to modeled*: IMAGine's
cycle counts are validated against the analytic latency models before
any scaling claim is made. This module is the serving stack's version
of that discipline. Every paged kernel launch records, beside the bytes
the dispatch layer actually accounts (`ServeTelemetry.on_launch`),
three analytic predictions derived from pool geometry alone:

  full      the single-launch full-depth walk — `n_rows *
            max_blocks_per_slot` table entries per layer group;
  bucketed  what the §11-§12 pow2 plan built from the same live needs
            will stream (`kernels.ops.make_bucket_plan` re-derived per
            group, `plan_streamed_pages` summed) — the autotuner's
            candidate-scoring quantity;
  live      the floor: exactly the live walk entries
            (`PagedKVCache.bucket_needs`), no pow2 padding.

The *applicable* prediction (bucketed when the dispatch builds plans,
full when it cannot — oracle impl or strategy "none") is compared to
the measured accounting per launch and per layer group; the relative
error lands in `perf_model_error{phase[,group]}` histograms. Because
both sides are structural the error must be exactly 0 — the histograms
exist to catch DRIFT: any future change that makes the dispatch stream
something the model does not predict (or vice versa) shows up as a
nonzero bucket, and `benchmarks/check_regress.py` gates on it. A
predictor nobody validates cannot drive the ROADMAP's roofline
autotuner; this one is validated on every instrumented launch.

Each prediction also carries a roofline time estimate —
`bytes / ChipSpec.hbm_bandwidth` (`core.tpu_gold.TPU_V5E` by default),
the §10 argument that the paged decode walk is HBM-bound — so the
summary attributes per-phase (prefill vs decode) fractions of the
predicted HBM time, machine-independently.

`CompileWatcher` is the compile-cache half (DESIGN.md §14): the jit
factories in `serve/compiled.py` report every trace/compile of a serve
step, which increments `serve_recompiles_total{step, plans}`, observes
the compile walltime histogram, and captures `cost_analysis`
FLOP/byte numbers from the compiled executable once per compile (the
`launch.roofline.analyze_compiled` idiom, scoped to serve steps).
PR 4's bounded-recompile-set property claim becomes a live runtime
metric: tests assert the observed count equals the pow2 plan
structure's prediction on a geometric trace.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.tpu_gold import TPU_V5E, ChipSpec
from ..kernels.ops import (
    is_bucket_plan,
    make_bucket_plan,
    plan_streamed_pages,
    resolve_bucket_strategy,
    resolve_impl,
)
from .metrics import MetricsRegistry

#: relative-error buckets for the model-error histograms: the first
#: bucket (<= 0.1%) is where every in-contract launch must land (the
#: prediction is structural, so the error is exactly 0); the rest
#: exist to measure drift when a future change breaks the model
MODEL_ERROR_BUCKETS: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
)

#: compile walltimes: 1 ms .. ~65 s, x2 per step
COMPILE_WALLTIME_BUCKETS: Tuple[float, ...] = tuple(
    1e-3 * 2.0 ** i for i in range(17)
)


def plans_enabled(strategy: str, kernel_impl: str) -> bool:
    """Whether the serving dispatch will build bucket plans at all —
    mirrors the `ops.bucket_args*` gate: strategy `"none"` and the
    oracle impl (`ref`, incl. `auto` off-TPU) never build plans, so
    their launches walk the full table depth."""
    return (
        resolve_bucket_strategy(strategy) != "none"
        and resolve_impl(kernel_impl) != "ref"
    )


def plan_signature(plans) -> str:
    """Compact stable label for a plan combination (the static half of
    the jit cache key): `"single"` for the everywhere-full-depth walk,
    `"<bound>x<count>[+...]"` per launch bucket, `|`-joined per layer
    group with `-` for a group that degenerated to the single launch."""
    if plans is None:
        return "single"
    if is_bucket_plan(plans):
        return "+".join(f"{b}x{c}" for b, c in plans)
    return "|".join(
        "-" if p is None else "+".join(f"{b}x{c}" for b, c in p)
        for p in plans
    )


def predict_streamed_pages(
    needs, n_rows: int, table_width: int, bucketed: bool = True
) -> int:
    """Pages ONE group's launch walks, predicted from its live
    walk-entry counts alone: re-derive the pow2 plan the dispatch
    would build (`bucketed=True`) or charge the full-depth walk. The
    single-group form `benchmarks/kernel_bench.py` validates against
    its measured sweep."""
    if not bucketed:
        return n_rows * table_width
    plan, _ = make_bucket_plan(None, 0, table_width, needs=needs)
    return plan_streamed_pages(plan, n_rows, table_width)


@dataclasses.dataclass
class LaunchPrediction:
    """Analytic streamed-byte model of one paged dispatch."""

    phase: str
    n_rows: int
    #: per-layer-group predicted pages under the APPLICABLE policy
    pages_by_group: List[int]
    #: per-group bytes (layer-count- and page-byte-weighted)
    bytes_by_group: List[int]
    #: the three model grades, summed over groups (bytes)
    full_bytes: int
    bucketed_bytes: int
    live_bytes: int

    @property
    def bytes_total(self) -> int:
        return sum(self.bytes_by_group)

    def roofline_s(self, chip: ChipSpec = TPU_V5E) -> float:
        """Predicted HBM-bound launch time at the device spec."""
        return self.bytes_total / chip.hbm_bandwidth


def predict_launch(
    pcache,
    eff_lengths,
    slots,
    n_rows: int,
    *,
    strategy: str = "pow2",
    kernel_impl: str = "auto",
    phase: str = "",
) -> LaunchPrediction:
    """Full analytic model of one dispatch from pool geometry: per
    layer group, the live walk-entry counts (`bucket_needs` — window
    retirement already folded in via each pool's first live block),
    the pow2 plan re-derived from them, and the three byte grades. The
    applicable grade of a `"decode"` launch is its block walk's copied
    pages (`PagedKVCache.decode_walk_pages` under the plan the dispatch
    builds), which stops at each slot's last live column."""
    needs = pcache.bucket_needs(eff_lengths, slots)
    mb = pcache.max_blocks_per_slot
    plb = pcache.page_layer_bytes
    bucketed = plans_enabled(strategy, kernel_impl)
    walk_pages = None
    if phase == "decode":
        plans = tuple(
            make_bucket_plan(None, 0, mb, needs=need)[0] for need in needs
        ) if bucketed else None
        walk_pages = pcache.decode_walk_pages(eff_lengths, plans)
    full_b = bucketed_b = live_b = 0
    pages_by_group: List[int] = []
    bytes_by_group: List[int] = []
    for g, (pool, need) in enumerate(zip(pcache.pools, needs)):
        layers = len(pool.layers)
        full_pg = n_rows * mb
        buck_pg = predict_streamed_pages(need, n_rows, mb, bucketed=True)
        live_pg = int(np.asarray(need).sum())
        full_b += layers * full_pg * plb
        bucketed_b += layers * buck_pg * plb
        live_b += layers * live_pg * plb
        pg = buck_pg if bucketed else full_pg
        if walk_pages is not None:
            pg = walk_pages[g]
        pages_by_group.append(pg)
        bytes_by_group.append(layers * pg * plb)
    return LaunchPrediction(
        phase=phase, n_rows=n_rows, pages_by_group=pages_by_group,
        bytes_by_group=bytes_by_group, full_bytes=full_b,
        bucketed_bytes=bucketed_b, live_bytes=live_b,
    )


def _rel_err(predicted: float, measured: float) -> float:
    if measured == 0:
        return 0.0 if predicted == 0 else 1.0
    return abs(predicted - measured) / measured


class PerfModel:
    """Per-launch predicted-vs-measured accounting + phase attribution.

    One instance per `ServeTelemetry`; `record_launch` runs only on the
    instrumented path (the metrics-off contract is enforced by the
    callers, exactly like the rest of the telemetry)."""

    def __init__(self, registry: MetricsRegistry,
                 chip: ChipSpec = TPU_V5E):
        self.registry = registry
        self.chip = chip
        #: per-phase accumulators (exact, host ints) for `summary()`
        self.phases: Dict[str, Dict[str, float]] = {}
        #: every instrumented launch: (phase, plans, n_rows,
        #: eff_lengths tuple) — the §11 recompile-set ground truth the
        #: compile-watcher tests replay
        self.launch_log: List[Tuple[str, object, int, Tuple[int, ...]]] = []

    def _phase(self, phase: str) -> Dict[str, float]:
        st = self.phases.get(phase)
        if st is None:
            st = self.phases[phase] = {
                "launches": 0, "predicted_bytes": 0, "measured_bytes": 0,
                "live_bytes": 0, "full_walk_bytes": 0,
                "bucketed_bytes": 0, "model_error_max": 0.0,
            }
        return st

    def record_launch(
        self,
        phase: str,
        pcache,
        plans,
        n_rows: int,
        eff_lengths,
        slots,
        strategy: str,
        kernel_impl: str,
        measured_pages_by_group: Sequence[int],
        measured_bytes_by_group: Sequence[int],
    ) -> LaunchPrediction:
        """Predict this launch from geometry, compare to the measured
        per-group accounting, and record the model error."""
        pred = predict_launch(
            pcache, eff_lengths, slots, n_rows,
            strategy=strategy, kernel_impl=kernel_impl, phase=phase,
        )
        r = self.registry
        measured_total = int(sum(measured_bytes_by_group))
        err = _rel_err(pred.bytes_total, measured_total)
        r.histogram(
            "perf_model_error", {"phase": phase},
            bounds=MODEL_ERROR_BUCKETS,
        ).observe(err)
        for pool, pb, mb_ in zip(
            pcache.pools, pred.bytes_by_group, measured_bytes_by_group
        ):
            r.histogram(
                "perf_model_error", {"phase": phase, "group": pool.gid},
                bounds=MODEL_ERROR_BUCKETS,
            ).observe(_rel_err(pb, mb_))
        r.counter("perf_predicted_bytes_total", {"phase": phase}).inc(
            pred.bytes_total
        )
        r.counter("perf_live_bytes_total", {"phase": phase}).inc(
            pred.live_bytes
        )
        st = self._phase(phase)
        st["launches"] += 1
        st["predicted_bytes"] += pred.bytes_total
        st["measured_bytes"] += measured_total
        st["live_bytes"] += pred.live_bytes
        st["full_walk_bytes"] += pred.full_bytes
        st["bucketed_bytes"] += pred.bucketed_bytes
        st["model_error_max"] = max(st["model_error_max"], err)
        self.launch_log.append(
            (phase, plans, n_rows,
             tuple(int(x) for x in np.asarray(eff_lengths).reshape(-1)))
        )
        return pred

    def summary(self) -> Dict[str, object]:
        """Per-phase attribution: predicted/measured/live/full bytes,
        exact max model error, roofline seconds at the device spec, and
        each phase's fraction of the total predicted HBM time. Every
        time here is `bytes / hbm_bandwidth` — a prediction, labelled as
        such under `roofline_basis`; none is a device measurement."""
        bw = self.chip.hbm_bandwidth
        total_s = sum(
            st["measured_bytes"] / bw for st in self.phases.values()
        )
        out: Dict[str, object] = {
            "chip": self.chip.name, "roofline_basis": "predicted",
            "phases": {},
        }
        for phase, st in sorted(self.phases.items()):
            meas = st["measured_bytes"]
            roofline_s = meas / bw
            out["phases"][phase] = {
                "launches": int(st["launches"]),
                "predicted_bytes": int(st["predicted_bytes"]),
                "measured_bytes": int(meas),
                "live_bytes": int(st["live_bytes"]),
                "full_walk_bytes": int(st["full_walk_bytes"]),
                "model_error_max": st["model_error_max"],
                "roofline_s": roofline_s,
                "roofline_fraction": (
                    roofline_s / total_s if total_s > 0 else 0.0
                ),
                # how much of what streams is live data (vs pow2 pad)
                "walk_efficiency": (
                    st["live_bytes"] / meas if meas > 0 else 1.0
                ),
                # what bucketing saved over the full-depth walk
                "bucketing_savings": (
                    1.0 - meas / st["full_walk_bytes"]
                    if st["full_walk_bytes"] > 0 else 0.0
                ),
            }
        out["model_error_max"] = max(
            (st["model_error_max"] for st in self.phases.values()),
            default=0.0,
        )
        out["roofline_total_s"] = total_s
        return out


def _cost_analysis(compiled) -> Tuple[float, float]:
    """(flops, bytes accessed) from a compiled executable — tolerant of
    the list-wrapped older API and of backends that report nothing."""
    try:
        cost = compiled.cost_analysis() or {}
    except Exception:
        return 0.0, 0.0
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
    )


class CompileWatcher:
    """Live compile-cache introspection for the serve steps.

    `serve/compiled.py`'s introspected wrappers call `on_compile` once
    per actual XLA compile (their AOT signature cache IS the compile
    cache). Everything lands in the registry —
    `serve_recompiles_total{step, plans}`,
    `serve_compile_walltime_s{step}` — plus a host-side record list
    with the per-executable `cost_analysis` capture and its roofline
    terms at the device spec."""

    def __init__(self, registry: MetricsRegistry,
                 chip: ChipSpec = TPU_V5E):
        self.registry = registry
        self.chip = chip
        self.compiles: List[Dict[str, object]] = []

    def clock(self) -> float:
        """The registry's injected clock, in seconds. Compile timing in
        `serve/compiled.py` reads time through here — never the wall
        clock directly (analysis rule RL204) — so a `ManualClock`
        registry makes compile walltimes deterministic in tests."""
        return float(self.registry.clock())

    def on_compile(self, step: str, plans, walltime_s: float,
                   compiled) -> None:
        sig = plan_signature(plans)
        r = self.registry
        r.counter(
            "serve_recompiles_total", {"step": step, "plans": sig}
        ).inc()
        r.histogram(
            "serve_compile_walltime_s", {"step": step},
            bounds=COMPILE_WALLTIME_BUCKETS,
        ).observe(walltime_s)
        flops, nbytes = _cost_analysis(compiled)
        lab = {"step": step, "plans": sig}
        r.gauge("serve_compiled_hlo_flops", lab).set(flops)
        r.gauge("serve_compiled_hlo_bytes", lab).set(nbytes)
        self.compiles.append({
            "step": step,
            "plans": sig,
            "raw_plans": plans,
            "walltime_s": walltime_s,
            "hlo_flops": flops,
            "hlo_bytes": nbytes,
            "compute_s": flops / self.chip.peak_flops_bf16,
            "memory_s": nbytes / self.chip.hbm_bandwidth,
        })

    @property
    def total(self) -> int:
        return len(self.compiles)

    def by_step(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for rec in self.compiles:
            out[rec["step"]] = out.get(rec["step"], 0) + 1
        return out

    def summary(self) -> Dict[str, object]:
        return {
            "total": self.total,
            "by_step": self.by_step(),
            "distinct_plan_signatures": sorted(
                {(r["step"], r["plans"]) for r in self.compiles}
            ),
            "compiles": [
                {k: v for k, v in rec.items() if k != "raw_plans"}
                for rec in self.compiles
            ],
        }
