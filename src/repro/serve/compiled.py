"""Shared jitted closures over the paged model entry points.

`ServeEngine` and `ContinuousBatcher` drive the same two compiled
functions (suffix prefill into the page pools, one-token paged decode);
building them here keeps the `models.prefill_paged` /
`models.decode_step_paged` call signatures in exactly one place.

`impl` selects the paged-attention kernel path (`ops.resolve_impl`
semantics) and is closed over statically, so one engine can pin the
native kernel (`"pallas"`, strict — raises off-TPU at trace time), the
interpreter (`"pallas_interpret"`, the CPU correctness tool) or the
oracle, while `"auto"` keeps the silent backend dispatch.

Layer-major dispatch (DESIGN.md §12): both factories take per-layer
block tables `[L, B, mb]` and first-live-block vectors `[L, B]`, and the
bucket PLANS as a **static** per-group tuple
(`static_argnames=("plans",)`) with the matching permutations as a
dynamic tuple — jax's jit cache IS the per-plan-combination compile
cache, and the power-of-two rounding in `kernels.ops.make_bucket_plan`
bounds how many combinations can ever exist. `plans=None` (the default)
is the everywhere-single-launch path and compiles exactly the PR-3
program.

Compile-cache introspection (DESIGN.md §14): passing a `watcher` (an
`obs.perf.CompileWatcher`) switches the factory to an ahead-of-time
execution path. `_IntrospectedStep` keeps its own signature cache —
static plans plus the argument pytree's (structure, shape, dtype)
signature, i.e. exactly what jax's jit cache keys on — and on a miss
runs `jitted.lower(...).compile()` explicitly, timing the compile and
reporting it to the watcher before caching the executable. On a hit it
calls the cached `Compiled` directly. Every XLA compile is therefore
observed exactly once (`serve_recompiles_total{step, plans}` in the
registry), with walltime and `cost_analysis` FLOP/byte capture, and
PR 4's "bounded recompile set" claim becomes a runtime metric.

The module-level trace log is the neutral referee for the overhead
bench: `fn` bodies append to it at *trace time* whether they were
traced by plain jit dispatch (watcher off) or by `lower()` (watcher
on). `trace_count()` deltas therefore count XLA traces identically on
both paths — plain Python list appends, zero registry calls, so the
metrics-off contract (`obs.metrics.mutation_count()` flat) still
holds — and `metrics_overhead_bench` asserts the counts are identical:
observability must not perturb the compile cache.
"""

from __future__ import annotations

from typing import List, Tuple

import jax

from ..configs.base import ModelConfig
from ..models import decode_step, decode_step_paged, prefill, prefill_paged

#: (step kind, static plans) appended once per XLA trace of a serve
#: step — trace-time side effect, see module docstring
_TRACE_LOG: List[Tuple[str, object]] = []


def _note_trace(kind: str, plans) -> None:
    _TRACE_LOG.append((kind, plans))


def trace_count(kind: str = None) -> int:
    """Total serve-step traces this process, optionally per step kind."""
    if kind is None:
        return len(_TRACE_LOG)
    return sum(1 for k, _ in _TRACE_LOG if k == kind)


def _leaf_sig(leaf) -> tuple:
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return (tuple(leaf.shape), str(leaf.dtype),
                bool(getattr(leaf, "weak_type", False)))
    return ("py", type(leaf).__name__, leaf)


def _call_signature(args, kwargs) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (str(treedef), tuple(_leaf_sig(l) for l in leaves))


class _IntrospectedStep:
    """AOT wrapper around one jitted serve step: own signature cache,
    explicit timed `lower().compile()` on miss, watcher report per
    compile. The cached executable is called with the exact dynamic
    argument structure it was lowered with (static `plans` is baked
    into it and must not be re-passed)."""

    def __init__(self, kind: str, jitted, watcher):
        self.kind = kind
        self._jitted = jitted
        self._watcher = watcher
        self._cache = {}

    def __call__(self, *args, **kwargs):
        # stay signature-transparent: callers pass `perms` positionally
        # or by keyword; only the static `plans` kwarg is peeled off
        # (it is baked into the executable and must not be re-passed)
        plans = kwargs.pop("plans", None)
        key = (plans, _call_signature(args, kwargs))
        compiled = self._cache.get(key)
        if compiled is None:
            # compile timing reads the watcher's injected clock, never
            # the wall clock directly (RL204 — ManualClock in tests
            # makes compile walltimes deterministic)
            t0 = self._watcher.clock()
            compiled = self._jitted.lower(
                *args, plans=plans, **kwargs
            ).compile()
            walltime = self._watcher.clock() - t0
            self._cache[key] = compiled
            self._watcher.on_compile(self.kind, plans, walltime, compiled)
        return compiled(*args, **kwargs)

    def cache_size(self) -> int:
        return len(self._cache)


def _finish(kind: str, jitted, watcher):
    if watcher is not None:
        return _IntrospectedStep(kind, jitted, watcher)
    return jitted


def jit_paged_prefill(cfg: ModelConfig, impl: str = "auto", watcher=None,
                      kv_dtype: str = "bf16"):
    """(params, toks, k_pages, v_pages, block_tables, block_starts,
    start, total, last_pos[, perms], plans=...) ->
    (logits, k_pages, v_pages). Retraces once per (padded suffix-length
    bucket, plan combination) pair.

    `kv_dtype="int8"` (DESIGN.md §16) builds the quantized-pool variant
    instead: the scale stacks ride as two extra positional args after
    the pools — (params, toks, k_pages, v_pages, k_scales, v_scales,
    bt, st, start, total, last_pos[, perms], plans=...) -> (logits,
    k_pages, v_pages, k_scales, v_scales). The bf16 factory output is
    untouched (same fn, same call signature, same jit cache keys), so
    the float path's recompile accounting stays exactly PR 8.

    Chunked prefill (DESIGN.md §17) reuses this factory unchanged: each
    chunk is one call with an advancing `start`/`total`. Mid chunks are
    exactly `prefill_chunk` tokens wide (a block multiple) and only the
    tail chunk is ragged, so the retrace set stays bounded by the §11
    pow2 plan classes times at most two suffix widths — the scheduler
    asserts the compile-cache size through `_cache_size()` as before."""

    if kv_dtype == "int8":
        def qfn(p, toks, kp, vp, ks, vs, bt, st, strt, tot, lp,
                perms=None, plans=None):
            _note_trace("prefill", plans)
            return prefill_paged(
                p, toks, kp, vp, bt, strt, tot, cfg, last_pos=lp,
                impl=impl, bucket_plan=plans, bucket_perm=perms,
                block_start=st, k_scales=ks, v_scales=vs,
            )

        jitted = jax.jit(qfn, static_argnames=("plans",))
        return _finish("prefill", jitted, watcher)

    def fn(p, toks, kp, vp, bt, st, strt, tot, lp, perms=None, plans=None):
        _note_trace("prefill", plans)
        return prefill_paged(
            p, toks, kp, vp, bt, strt, tot, cfg, last_pos=lp, impl=impl,
            bucket_plan=plans, bucket_perm=perms, block_start=st,
        )

    jitted = jax.jit(fn, static_argnames=("plans",))
    return _finish("prefill", jitted, watcher)


def jit_paged_decode(cfg: ModelConfig, impl: str = "auto", watcher=None,
                     kv_dtype: str = "bf16"):
    """(params, token, k_pages, v_pages, block_tables, block_starts,
    positions[, perms], plans=...) -> (logits, k_pages, v_pages).
    Retraces once per plan combination.

    `kv_dtype="int8"` (DESIGN.md §16): quantized variant with the scale
    stacks after the pools — (params, token, k_pages, v_pages,
    k_scales, v_scales, bt, st, positions[, perms], plans=...) ->
    (logits, k_pages, v_pages, k_scales, v_scales); the bf16 factory
    output is byte-for-byte PR 8."""

    if kv_dtype == "int8":
        def qfn(p, t, kp, vp, ks, vs, bt, st, pos, perms=None, plans=None):
            _note_trace("decode", plans)
            return decode_step_paged(
                p, t, kp, vp, bt, pos, cfg, impl=impl,
                bucket_plan=plans, bucket_perm=perms, block_start=st,
                k_scales=ks, v_scales=vs,
            )

        jitted = jax.jit(qfn, static_argnames=("plans",))
        return _finish("decode", jitted, watcher)

    def fn(p, t, kp, vp, bt, st, pos, perms=None, plans=None):
        _note_trace("decode", plans)
        return decode_step_paged(
            p, t, kp, vp, bt, pos, cfg, impl=impl,
            bucket_plan=plans, bucket_perm=perms, block_start=st,
        )

    jitted = jax.jit(fn, static_argnames=("plans",))
    return _finish("decode", jitted, watcher)


def jit_dense_prefill(cfg: ModelConfig, cache_len: int, watcher=None):
    """(params, toks) -> (logits, cache): the dense pre-allocated-cache
    prefill. Lives here (not inline in the engines) so dense serve-step
    compiles share the paged path's introspection plumbing —
    `jax.jit` of a serve step outside this module is a lint violation
    (analysis rule RL201)."""

    def fn(p, toks, plans=None):
        _note_trace("dense_prefill", plans)
        return prefill(p, toks, cfg, cache_len=cache_len)

    jitted = jax.jit(fn, static_argnames=("plans",))
    return _finish("dense_prefill", jitted, watcher)


def jit_dense_decode(cfg: ModelConfig, watcher=None):
    """(params, token, cache) -> (logits, cache): one dense decode
    step. Same single-home rule as `jit_dense_prefill`."""

    def fn(p, t, cache, plans=None):
        _note_trace("dense_decode", plans)
        return decode_step(p, t, cache, cfg)

    jitted = jax.jit(fn, static_argnames=("plans",))
    return _finish("dense_decode", jitted, watcher)
