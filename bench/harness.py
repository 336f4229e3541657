"""One run of one cell: set-up, the measured window, the check.

`run_cell` builds the program's normal serving path
(`ContinuousBatcher(paged=True)`) from the cell's files, drives it
through `submit()` and `step()` on the schedule the traffic mix sets,
measures for `seconds`, and then compares what it served with the plain
reference. It returns the result line as a dict.

Files, found by name (`root` is the directory holding BENCHMARK.json):
`bench/configs/<config>.json`, `bench/traffic/<mix>.json`,
`bench/cells/<workload>.json` (the program's server settings, passed to
`ContinuousBatcher` as they stand, and the check's limit) and
`bench/metrics/<metric>.py` (one reader per metric, end-to-end or
per-layer). Set-up and the window follow from what the mix holds
(sessions, arrivals), never from its name.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import tempfile
import time
from typing import List, Optional

import numpy as np

from . import model, traffic

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: JAX's events for a program compiled, or read from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


class WindowCompiled(RuntimeError):
    """Something compiled inside the measured window: set-up missed a
    program the window calls, and the run measures the compiler."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: str
    hf: dict
    mix: dict
    server: dict
    check: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    #: where its files are: readers load from `<bench_dir>/metrics/`
    bench_dir: str = BENCH_DIR


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    bdir = os.path.join(root, "bench")
    with open(os.path.join(bdir, "cells", f"{name}.json")) as f:
        cell = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=w["config"],
        hf=model.load_config(w["config"], bdir),
        mix=traffic.load_mix(w["traffic"], bdir),
        server=cell["server"], check=cell["check"],
        end_to_end=[m for m in bench["end_to_end"] if _listed(m, name)],
        per_layer=[m for m in bench["per_layer"] if _listed(m, name)],
        bench_dir=bdir,
    )


def load_reader(metric: str, bdir: str = BENCH_DIR):
    """The `read(ctx)` function of `bench/metrics/<metric>.py`: the
    metric's value from a `Context`, or None where it finds nothing to
    read."""
    path = os.path.join(bdir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at `$JAX_COMPILATION_CACHE_DIR`
    or the fixed `<checkout>/.jax_cache`; every program is cached,
    however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCount:
    """Counts programs compiled or read from the persistent compile
    cache while on."""

    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event in COMPILE_EVENTS:
            self.n += 1


class Recorder:
    """The program's telemetry hooks, kept as plain lists while `on`:
    paged kernel launches with the lengths they cover, decode batch
    sizes and page-pool gauges. Attached only in traced runs."""

    profile = False

    def __init__(self):
        self.on = False
        self.decode_eff: List[np.ndarray] = []
        self.prefill: List[tuple] = []          # (start, end) per launch
        self.decode_batch: List[int] = []
        self.reserved_unused: List[float] = []
        self._pad: Optional[int] = None

    def compile_watcher(self):
        return None

    def on_prefill(self, uid, pad):
        self._pad = int(pad)

    def account_paged_launch(self, kind, plans, n_rows, pc, eff_lengths,
                             slots=None, strategy=None, kernel_impl=None):
        if not self.on:
            return
        if kind == "decode":
            self.decode_eff.append(np.array(eff_lengths, np.int64))
        else:
            end = int(np.asarray(eff_lengths).reshape(-1)[0])
            bs = pc.block_size
            start = -(-end // bs) * bs - self._pad
            self.prefill.append((start, end))

    def on_decode(self, uids):
        if self.on:
            self.decode_batch.append(len(uids))

    def end_tick(self, queued, active, pool_gauges=None, **_):
        if self.on and pool_gauges:
            g = pool_gauges[0]
            pool = g["free_pages"] + g["allocated_pages"]
            unused = g["free_pages"] - g["unreserved_pages"]
            self.reserved_unused.append(100.0 * unused / max(pool, 1))

    def __getattr__(self, name):      # every other hook: nothing to keep
        if name.startswith("on_"):
            return lambda *a, **k: None
        raise AttributeError(name)


@dataclasses.dataclass
class Track:
    spec: traffic.Spec
    req: object
    submit_s: Optional[float] = None
    seen: int = 0
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Context:
    """What a metric reader may read. `tracks` hold every request with
    its token times (seconds from the window's opening, host clock);
    `red` and `rec`, the trace reduction and the program's telemetry,
    exist in a traced run only."""

    hf: dict
    server: dict
    seconds: float
    setup_s: float
    tracks: List[Track]
    lags_s: List[float]
    peaks: Optional[dict] = None
    red: object = None             # trace_reduce.Reduced
    rec: Optional[Recorder] = None


def _span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def _program_hook(batcher, name: str):
    """A name of the program that set-up needs and that is no public
    interface yet; its absence stops the run, never a silent fallback."""
    if not hasattr(batcher, name):
        raise RuntimeError(
            f"ContinuousBatcher has no {name!r}: the benchmark warms the "
            "decode plans of its sessions through it; the program needs a "
            "public warm-up hook")
    return getattr(batcher, name)


def _warm_decode_plans(batcher, lengths, steps: int) -> int:
    """Compile, before the window, every decode program that `steps`
    more ticks from `lengths` can call: replay the program's bucket rule
    over those ticks and run each distinct plan once on all-scratch
    tables (their writes land in the scratch page). Should the program's
    rule differ from this replay, a plan compiles in the window and the
    run stops (`WindowCompiled`)."""
    import jax.numpy as jnp

    from repro.kernels.ops import bucket_args_grouped

    decode = _program_hook(batcher, "_decode_paged")
    impl = _program_hook(batcher, "_kernel_impl")
    pc = batcher.pcache
    seen = set()
    for k in range(steps):
        eff = np.asarray(lengths) + 1 + k
        plans, perms = bucket_args_grouped(
            batcher.bucket_strategy, impl, pc.bucket_needs(eff),
            pc.max_blocks_per_slot)
        if plans in seen:
            continue
        seen.add(plans)
        bt = jnp.zeros_like(pc.device_block_tables())
        _, pc.k_pages, pc.v_pages = decode(
            batcher.params, batcher.tokens, pc.k_pages, pc.v_pages, bt,
            pc.device_block_starts(), pc.device_positions(), perms,
            plans=plans)
    return len(seen)


def passes(readings: dict) -> bool:
    """The predicate of `correct`: every number within its limit."""
    return all(r["value"] <= r["limit"] for r in readings.values())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             kernel_impl: Optional[str] = None, log=print,
             sabotage=None, control: bool = False) -> dict:
    """One run. `kernel_impl` overrides the cell's (tests on the CPU);
    `sabotage(batcher)`, if given, may break the program after set-up
    (tests that the check catches it). With `control` the float8
    control is also put in the program's place on the same sequences
    and judged by the same predicate and limits: `control_checks` and
    `control_correct` (setting the limits; the benchmark's own runs
    never do)."""
    import jax
    import jax.numpy as jnp
    from repro.serve import ContinuousBatcher, Request

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"needs {cell.chips} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform} device(s)")
    dev = devs[0]
    cache = enable_compile_cache()
    compiles = CompileCount()

    hf, srv, mix = cell.hf, cell.server, cell.mix
    cfg = model.program_config(hf, cell.config)
    weights = model.make_weights(hf, model.seed_key(seed))
    params = model.program_params(hf, weights)
    jax.block_until_ready(weights)
    specs = traffic.generate(mix, seed, seconds, hf["vocab_size"])
    rec = Recorder() if trace else None
    opts = {k: v for k, v in srv.items() if k not in ("n_slots", "max_len")}
    if kernel_impl:
        opts["kernel_impl"] = kernel_impl
    batcher = ContinuousBatcher(cfg, params, srv["n_slots"], srv["max_len"],
                                paged=True, telemetry=rec, **opts)
    tracks = [Track(s, Request(s.uid, jnp.asarray(s.prompt),
                               s.max_new_tokens)) for s in specs]
    log(f"cell {cell.name}: {len(specs)} requests, compile cache {cache}")

    # ---- set-up: every program the window calls, and the sessions -----
    # each warm prompt (every prefill width and chunk offset the arrivals
    # can send), served in every slot and decoded once
    for k, prompts in enumerate(traffic.warm_prompts(mix, seed,
                                                     hf["vocab_size"])):
        for i in range(max(srv["n_slots"], len(prompts))):
            batcher.submit(Request(-1 - i - 100000 * k,
                                   jnp.asarray(prompts[i % len(prompts)]), 2))
        batcher.run_until_drained()
        batcher.finished.clear()
    sessions = [tr for tr in tracks if tr.spec.due_s is None]
    for tr in sessions:
        batcher.submit(tr.req)
    while any(not tr.req.generated for tr in sessions):
        batcher.step()
    for tr in sessions:
        tr.seen = len(tr.req.generated)
    if sessions and batcher.bucket_strategy != "none":
        n = _warm_decode_plans(
            batcher, batcher.pcache.lengths.copy(),
            max(tr.spec.max_new_tokens for tr in sessions))
        log(f"{n} decode plans warmed for the sessions")
    jax.block_until_ready((batcher.pcache.k_pages, batcher.tokens))
    if sabotage is not None:
        sabotage(batcher)
    # what set-up left behind is kept out of the window's collections
    gc.collect()
    gc.freeze()
    log(f"set-up done at {time.perf_counter() - t_process:.3f} s")

    # ---- the window ---------------------------------------------------
    pending = collections.deque(
        sorted((tr for tr in tracks if tr.spec.due_s is not None),
               key=lambda tr: tr.spec.due_s))
    live = list(sessions)
    ramp = -pending[0].spec.due_s if pending and \
        pending[0].spec.due_s < 0 else 0.0
    clock = time.perf_counter
    t0 = clock() + ramp
    setup_end = None
    trace_dir, window_span = None, None
    while True:
        now = clock() - t0
        if setup_end is None and now >= 0:
            if trace:
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(trace_dir)
                window_span = jax.profiler.TraceAnnotation("bench/window")
                window_span.__enter__()
                rec.on = True
            compiles.on = True
            setup_end = clock()
            t0 = setup_end
            now = 0.0
        if now >= seconds:
            break
        with _span(trace, "bench/submit"):
            while pending and pending[0].spec.due_s <= now:
                tr = pending.popleft()
                batcher.submit(tr.req)
                tr.submit_s = clock() - t0
                live.append(tr)
        if batcher.queue or any(s is not None for s in batcher.slots):
            with _span(trace, "bench/step"):
                batcher.step()
            t_end = clock() - t0
            with _span(trace, "bench/scan"):
                keep = []
                for tr in live:
                    n = len(tr.req.generated)
                    if n > tr.seen:
                        tr.times.extend([t_end] * (n - tr.seen))
                        tr.seen = n
                    if n < tr.spec.max_new_tokens:
                        keep.append(tr)
                live = keep
        else:
            nxt = pending[0].spec.due_s if pending else seconds
            with _span(trace, "bench/wait"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
    compiles.on = False
    if trace:
        rec.on = False
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    gc.unfreeze()
    if compiles.n:
        raise WindowCompiled(
            f"{compiles.n} program(s) compiled inside the window; set-up "
            "has to warm every program the window calls")
    setup_s = setup_end - t_process
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    # ---- numbers --------------------------------------------------------
    in_window = [tr for tr in tracks
                 if tr.spec.due_s is None or tr.spec.due_s >= 0]
    failed = sum(1 for tr in tracks if tr.req.uid in batcher.finished
                 and len(tr.req.generated) != tr.spec.max_new_tokens)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": len(in_window),
              "failed": failed, "queued_at_close": len(batcher.queue)}
    ctx = Context(hf=hf, server=srv, seconds=seconds, setup_s=setup_s,
                  tracks=tracks,
                  lags_s=[tr.submit_s - tr.spec.due_s for tr in in_window
                          if tr.submit_s is not None])
    if trace:
        from . import peaks, trace_reduce

        ctx.red = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.rec, ctx.peaks = rec, peaks.peaks(dev.device_kind)
        device["busy_s"] = ctx.red.busy_s
        device["window_s"] = ctx.red.window_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top(ctx.red.op_seconds),
            "idle_gaps": trace_reduce.top(ctx.red.idle_by_span),
        }
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        v = load_reader(m["name"], cell.bench_dir)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device

    # ---- the check, with the program's state freed ---------------------
    served = [(tr.spec.prompt, list(tr.req.generated)) for tr in tracks
              if tr.req.uid in batcher.finished
              or (tr.spec.due_s is None and tr.req.generated)]
    batcher.pcache.k_pages = batcher.pcache.v_pages = None
    del batcher, params, ctx
    gc.collect()
    from . import check

    t_check = time.perf_counter()
    readings, ctl, n_tokens = check.compare(
        weights, hf, served, seed, cell.check, pad_to=srv["max_len"],
        control=control)
    log(f"check: {n_tokens} served tokens compared in "
        f"{time.perf_counter() - t_check:.1f} s")
    readings["failed_requests"] = {"value": failed, "limit": 0}
    result["tokens_compared"] = n_tokens
    result["correct"] = passes(readings)
    if control:
        result["control_checks"] = ctl
        result["control_correct"] = passes(ctl)
    result["checks"] = readings
    return result
