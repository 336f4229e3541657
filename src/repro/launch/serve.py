"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the continuous-batching scheduler over the architecture's published
config with random weights (``--smoke`` swaps in the tiny preset the CPU
tests use), optionally with PIM bit-plane quantized weights (the paper's
technique): --quantize converts every projection to packed digit planes
first. The summary names the device it ran on.

--paged switches to the block-paged KV cache (DESIGN.md §8): prompt
lengths are drawn ragged per request (no shared padded length), slots
refill at any tick, and finished requests' pages recycle through the
free list. Without --paged the dense cache requires one shared
--prompt-len.

--prefix (requires --paged) enables the shared-prefix radix index
(DESIGN.md §9): every request's prompt opens with a common
--shared-prefix-len system prompt, whose KV pages are stored and
prefilled once and mapped refcounted into every later request.

Paged caches are layer-major (DESIGN.md §12): layers sharing an
attention pattern form a group with its own page pool/tables, and
sliding-window groups retire pages that fall behind the window
(--no-window-retirement keeps the lockstep-residency baseline; try
``--arch gemma3-27b --paged`` for a mixed global/window stack).

--warmup serves the same requests once before the timed drain, so the
reported serving seconds exclude compilation. --trace-dir PATH records
a `jax.profiler` trace of the timed drain there (one `.xplane.pb` under
`PATH/plugins/profile/<run>/`): the scheduler's always-on `serve/*`
spans (`obs.tracing.SPANS`) on the same clock as the device's ops.
`run()` is the whole launch as a function; `chip_smoke.py` drives the
chip through it.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..configs.base import ModelConfig
from ..models import init_lm
from ..models.common import compute_dtype
from ..obs import ServeTelemetry
from ..quant.bitplane import PimQuantConfig
from ..serve import ContinuousBatcher, Request, ServeConfig, ServeEngine
from ..serve.compiled import trace_count
from .compile_cache import enable_compilation_cache


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="the architecture's tiny preset (CPU tests and "
                         "demos) instead of its published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--group", type=int, default=1)
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache: ragged prompts, slot "
                         "refill at any tick, page recycling")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV page size in tokens (--paged)")
    ap.add_argument("--prefix", action="store_true",
                    help="shared-prefix radix index: dedup + skip prefill "
                         "of the common prompt prefix (requires --paged)")
    ap.add_argument("--shared-prefix-len", type=int, default=32,
                    help="tokens of common system prompt prepended to "
                         "every request (--prefix demo trace)")
    ap.add_argument("--eos", type=int, default=-1,
                    help="EOS token id: a slot emitting it stops early and "
                         "frees its pages that tick (-1 = never)")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "int8"],
                    help="paged KV page-pool storage (DESIGN.md §16): "
                         "'int8' stores per-page-scaled quantized pages "
                         "— ~2x less resident/streamed KV, dequantized "
                         "inside the kernels' page fold (requires "
                         "--paged)")
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "pallas", "pallas_interpret", "ref"],
                    help="paged-attention kernel path; explicit values are "
                         "strict ('pallas' raises off-TPU)")
    ap.add_argument("--bucket-strategy", default="pow2",
                    choices=["pow2", "none"],
                    help="length-bucketed paged dispatch (DESIGN.md §11): "
                         "'pow2' bounds each kernel launch at its bucket's "
                         "page occupancy, 'none' keeps the single "
                         "full-depth launch")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill (DESIGN.md §17): split each "
                         "prompt into block-multiple chunks of at most "
                         "this many tokens, prefilled one chunk per tick "
                         "interleaved with decode — no head-of-line "
                         "stall behind a long prompt, and a windowed "
                         "group transiently holds only window + chunk "
                         "tokens (0 = single-shot; requires --paged)")
    ap.add_argument("--group-pool-slack", type=int, default=None,
                    help="retirement-aware admission slack (§17): a "
                         "retiring windowed group reserves "
                         "ceil(window/bs) + slack draws instead of the "
                         "full ceil(total/bs) (default: derived from "
                         "--prefill-chunk, the exact worst case)")
    ap.add_argument("--group-pool", default="uniform",
                    choices=["uniform", "auto"],
                    help="per-group pool sizing (§17): 'auto' sizes each "
                         "retiring windowed group's pool at n_slots * "
                         "(ceil(window/bs) + slack) — the HBM-budget "
                         "win on mixed global/window stacks (requires "
                         "--prefill-chunk > 0)")
    ap.add_argument("--no-window-retirement", action="store_true",
                    help="disable sliding-window page retirement "
                         "(DESIGN.md §12) — the lockstep-residency "
                         "baseline; tokens are identical either way")
    ap.add_argument("--metrics", action="store_true",
                    help="attach the serving telemetry (DESIGN.md §13): "
                         "request-lifecycle traces, per-tick pool/kernel "
                         "gauges, TTFT/TPOT percentiles; prints the run "
                         "summary and a Prometheus-style snapshot")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="stream the structured JSON-lines event log to "
                         "PATH (implies --metrics)")
    ap.add_argument("--trace-dir", default=None, metavar="PATH",
                    help="record a jax.profiler trace of the timed drain "
                         "under PATH: the scheduler's serve/* tick-phase "
                         "spans beside the device's ops")
    ap.add_argument("--warmup", action="store_true",
                    help="serve the same requests once, untimed, before "
                         "the timed drain: every step compiles there")
    args = ap.parse_args(argv)
    if args.prefix and not args.paged:
        ap.error("--prefix requires --paged (the prefix index shares "
                 "pages of the block-paged KV cache)")
    if args.kv_dtype != "bf16" and not args.paged:
        ap.error("--kv-dtype int8 requires --paged (quantized pages "
                 "live in the block-paged pools)")
    if (args.prefill_chunk or args.group_pool_slack is not None
            or args.group_pool != "uniform") and not args.paged:
        ap.error("--prefill-chunk / --group-pool-slack / --group-pool "
                 "require --paged (they shape the block-paged pools)")
    if args.group_pool == "auto" and not args.prefill_chunk:
        ap.error("--group-pool auto requires --prefill-chunk > 0: the "
                 "live-need bound that sizes each group only holds when "
                 "prefill appends are chunk-bounded (DESIGN.md §17)")
    return args


@dataclasses.dataclass
class ServeRun:
    """What one launch did: the batcher it drained, the tokens each
    timed request got, and where the time went."""

    cfg: ModelConfig
    batcher: ContinuousBatcher
    results: Dict[int, List[int]]
    param_bytes: int
    #: config, weights, batcher and (with --warmup) the warm-up drain
    setup_s: float
    #: the timed drain, ended by `block_until_ready` on the pools
    served_s: float
    #: serve-step traces (each one an XLA compile) in set-up / timed drain
    compiles_setup: int
    compiles_served: int
    telemetry: Optional[ServeTelemetry]

    @property
    def tokens(self) -> int:
        return sum(len(v) for v in self.results.values())


def init_params(cfg: ModelConfig, seed: int = 0):
    """Random weights from `seed`, stored at the compute dtype. One jitted
    init writes the stacked layers straight into their final buffers, so
    a published-width model never holds a second float32 copy."""

    def init(key):
        params = init_lm(key, cfg)
        dt = compute_dtype(cfg.dtype)
        return jax.tree.map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, params,
        )

    return jax.jit(init)(jax.random.PRNGKey(seed))


def _prompt_len(key, uid: int, max_len: int, block_size: int) -> int:
    """Ragged paged prompt length: a power-of-two page count (one of the
    four largest up to ceil(max_len / block_size)) less a random part of
    its last page. Prefill pads each prompt to whole pages, so the
    lengths stay ragged while prefill compiles at most four widths."""
    top = max(-(-max_len // block_size), 1).bit_length() - 1
    exp = top - int(jax.random.randint(
        jax.random.fold_in(key, 1000 + uid), (), 0, min(top, 3) + 1))
    short = int(jax.random.randint(
        jax.random.fold_in(key, 2000 + uid), (), 0, block_size))
    return max((1 << exp) * block_size - short, 1)


def make_requests(args, cfg: ModelConfig, uid0: int = 0) -> List[Request]:
    """The launch's request set, the same for every call: prompts from a
    fixed key, uids from `uid0`."""
    key = jax.random.PRNGKey(1)
    shared_len = args.shared_prefix_len if args.prefix else 0
    shared = jax.random.randint(
        jax.random.fold_in(key, 9999), (shared_len,), 0, cfg.vocab_size
    ).astype(jnp.int32)
    reqs = []
    for uid in range(args.requests):
        if args.paged:
            t = _prompt_len(key, uid, args.prompt_len, args.block_size)
        else:
            t = args.prompt_len
        prompt = jax.random.randint(
            jax.random.fold_in(key, uid), (t,), 0, cfg.vocab_size
        ).astype(jnp.int32)
        if args.prefix:  # every request opens with the shared system prompt
            prompt = jnp.concatenate([shared, prompt])
        reqs.append(Request(uid=uid0 + uid, prompt=prompt,
                            max_new_tokens=args.new_tokens))
    return reqs


def run(args: argparse.Namespace) -> ServeRun:
    """Build the model and batcher, optionally warm up, and drain the
    request set once under the clock."""
    t0 = time.perf_counter()
    traces0 = trace_count()
    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_params(cfg)
    if args.quantize:
        eng = ServeEngine(cfg, params, ServeConfig())
        frac = eng.quantize(PimQuantConfig(n_bits=args.bits, group=args.group,
                                           min_features=1))
        params = eng.params
        print(f"PIM-quantized: {frac:.1%} of param bytes packed "
              f"({args.bits}-bit, group={args.group})")
    param_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))

    telemetry = None
    if args.metrics or args.events_out:
        telemetry = ServeTelemetry(events_path=args.events_out)

    shared_len = args.shared_prefix_len if args.prefix else 0
    cache_len = shared_len + args.prompt_len + args.new_tokens + 8
    batcher = ContinuousBatcher(
        cfg, params, n_slots=args.slots, cache_len=cache_len,
        prompt_len=None if args.paged else args.prompt_len,
        paged=args.paged, block_size=args.block_size, prefix=args.prefix,
        eos_token=args.eos, kernel_impl=args.kernel_impl,
        bucket_strategy=args.bucket_strategy,
        window_retirement=not args.no_window_retirement,
        kv_dtype=args.kv_dtype,
        prefill_chunk=args.prefill_chunk,
        group_pool_slack=args.group_pool_slack,
        group_blocks="auto" if args.group_pool == "auto" else None,
        telemetry=telemetry,
    )
    if args.warmup:  # same prompts under other uids: every step compiles
        for req in make_requests(args, cfg, uid0=args.requests):
            batcher.submit(req)
        batcher.run_until_drained()
    jax.block_until_ready(params)
    setup_s = time.perf_counter() - t0
    traces1 = trace_count()

    for req in make_requests(args, cfg):
        batcher.submit(req)
    if args.trace_dir:
        jax.profiler.start_trace(args.trace_dir)
    t1 = time.perf_counter()
    try:
        batcher.run_until_drained()
        jax.block_until_ready(
            (batcher.tokens, batcher.cache if batcher.pcache is None
             else (batcher.pcache.k_pages, batcher.pcache.v_pages))
        )
        served_s = time.perf_counter() - t1
    finally:
        if args.trace_dir:
            jax.profiler.stop_trace()
    results = {u: batcher.finished[u] for u in range(args.requests)}
    return ServeRun(
        cfg=cfg, batcher=batcher, results=results, param_bytes=param_bytes,
        setup_s=setup_s, served_s=served_s,
        compiles_setup=traces1 - traces0,
        compiles_served=trace_count() - traces1, telemetry=telemetry,
    )


def main(argv: Optional[List[str]] = None):
    args = parse_args(argv)
    enable_compilation_cache()
    out = run(args)
    batcher, telemetry = out.batcher, out.telemetry
    dev = jax.devices()[0]
    mode = "paged" if args.paged else "dense"
    print(f"served {len(out.results)} requests, {out.tokens} tokens "
          f"in {out.served_s:.2f}s ({out.tokens / out.served_s:.1f} tok/s, "
          f"{mode} cache, {out.cfg.name}{' smoke' if args.smoke else ''}, "
          f"platform={dev.platform}, device_kind={dev.device_kind})")
    print(f"  set-up {out.setup_s:.2f}s, param bytes {out.param_bytes}, "
          f"step compiles: {out.compiles_setup} in set-up, "
          f"{out.compiles_served} while serving")
    if args.paged:
        pc = batcher.pcache
        print(f"  prefill tokens processed: {batcher.prefill_tokens}, "
              f"pages allocated: {pc.pages_allocated}, COW: {pc.cow_events}, "
              f"window-retired: {pc.pages_retired}")
        print(f"  kv pool dtype: {pc.kv_dtype}, "
              f"page-layer bytes: {pc.page_layer_bytes} "
              f"(true itemsize, scales included)")
        if len(pc.pools) > 1:  # layer-major groups (DESIGN.md §12)
            for p in pc.pools:
                kind = "global" if p.window is None else f"window={p.window}"
                bound = ("" if p.live_bound is None
                         else f", live-bound {p.live_bound} blocks/slot")
                print(f"  group {p.gid} ({kind}, {len(p.layers)} layers, "
                      f"pool {p.n_blocks - 1} pages{bound}): "
                      f"{p.pages_allocated} pages drawn, "
                      f"{p.pages_retired} retired, {p.cow_events} COW")
            print(f"  provisioned page bytes: "
                  f"{pc.provisioned_page_bytes()} "
                  f"(per-group sizing, DESIGN.md §17)")
    if args.prefix:
        ix = batcher.prefix
        print(f"  prefix index: {ix.hits}/{ix.lookups} hits, "
              f"{ix.cached_tokens_served} prompt tokens served from cache, "
              f"{len(ix)} pages indexed")
    for uid in sorted(out.results)[:3]:
        print(f"  req {uid}: {out.results[uid]}")
    if args.trace_dir:
        print(f"  profiler trace of the timed drain under {args.trace_dir}")
    if telemetry is not None:
        lat = telemetry.latency_summary()
        print("  telemetry (DESIGN.md §13):")
        for k in ("ttft_s", "tpot_s", "queue_delay_s"):
            s = lat[k]
            if s["n"]:
                print(f"    {k}: p50={s['p50']:.4f} p90={s['p90']:.4f} "
                      f"p99={s['p99']:.4f} (n={s['n']})")
        sb = telemetry.streamed_bytes_total
        print(f"    kernel streamed bytes: {sb} "
              f"({len(telemetry.tick_streamed_bytes)} ticks sampled), "
              f"{len(telemetry.events)} events")
        if telemetry.perf.phases:
            perf = telemetry.perf.summary()
            print("  perf attribution (DESIGN.md §14, "
                  f"{perf['roofline_basis']} at chip={perf['chip']} "
                  "peaks, not measured):")
            for phase, st in sorted(perf["phases"].items()):
                print(f"    {phase}: {st['launches']} launches, "
                      f"predicted={st['predicted_bytes']}B "
                      f"measured={st['measured_bytes']}B "
                      f"err_max={st['model_error_max']:g} "
                      f"roofline_frac={st['roofline_fraction']:.2f} "
                      f"bucketing_savings={st['bucketing_savings']:.2f}")
        if telemetry._compile_watcher is not None \
                and telemetry._compile_watcher.total:
            w = telemetry._compile_watcher
            steps = ";".join(f"{k}={v}"
                             for k, v in sorted(w.by_step().items()))
            print(f"    recompiles: {w.total} total ({steps})")
        print("  --- prometheus snapshot ---")
        print("  " + telemetry.registry.prometheus().rstrip()
              .replace("\n", "\n  "))
        telemetry.close()
        if args.events_out:
            print(f"  events written to {args.events_out}")


if __name__ == "__main__":
    main()
