"""Layer 1: lint the traced serve steps' jaxprs (DESIGN.md §15).

The decode/prefill programs the engines actually run are built here
exactly the way `serve/compiled.py` builds them — same jit factories,
same layer-major block tables, same static per-group bucket plans —
then traced with `jax.make_jaxpr` and walked recursively. Rules:

  JX001  host callback / transfer primitives in the hot path (any
         `*_callback`, `outside_call`, infeed/outfeed): each one is a
         host round-trip per step, the §13 failure mode telemetry was
         explicitly designed to avoid.
  JX002  float64/complex128 values anywhere in the program: f64 creep
         doubles page bytes and silently de-optimizes TPU lowering.
  JX003  whole-pool materialization: a pallas operand the size of a KV
         pool must be mapped with `memory_space=ANY` (stays in HBM and
         is DMA'd page-by-page); any pool-sized *elementwise/copy*
         output outside a kernel means a full-pool copy per step.
  JX004  every `lax.switch`/`cond` whose branches contain a
         `pallas_call` is the per-layer group dispatch of
         `models.attention._select_bucket_plan`; its branch count must
         equal `len(models.layer_attn_groups(cfg, capacity))`.
  JX005  weak-typed top-level inputs: a weak-type scalar promotes per
         call site, splitting jit cache keys and defeating the §11
         bounded-recompile-set guarantee.

`lint_jaxpr` is reusable on any ClosedJaxpr (the fixture tests trace
tiny deliberately-broken functions); `lint_serve_steps` applies it to
the real decode + prefill steps on a two-layer-group probe config.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr

from .findings import Finding

#: primitives that round-trip to the host when hit inside a step
_HOST_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
})

#: primitives that MATERIALIZE a new buffer the size of their output —
#: pool-sized outputs from these mean a whole-pool copy per step.
#: In-place page writes (scatter/dynamic_update_slice), control flow and
#: pallas_call itself legitimately carry pool-sized outputs and are NOT
#: listed.
_MATERIALIZING_PRIMS = frozenset({
    "convert_element_type", "broadcast_in_dim", "gather", "concatenate",
    "copy", "iota", "reshape", "transpose", "rev",
    "add", "sub", "mul", "div", "max", "min", "pow", "exp", "log",
    "select_n", "dot_general",
})

_WIDE_DTYPES = ("float64", "complex128")


def _sub_jaxprs(eqn):
    """Inner jaxprs of one equation (cond branches, scan/while bodies,
    pjit calls, pallas_call kernel bodies ...)."""
    out = []

    def visit(v):
        if isinstance(v, ClosedJaxpr):
            out.append(v.jaxpr)
        elif hasattr(v, "eqns") and hasattr(v, "invars"):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            for item in v:
                visit(item)

    for v in eqn.params.values():
        visit(v)
    return out


def _walk_eqns(jaxpr, in_kernel=False):
    """Yield (eqn, in_kernel) over the whole program, depth-first.
    `in_kernel` marks equations inside a pallas_call body, where
    pool-sized refs are the POINT and JX003(b) must not fire."""
    for eqn in jaxpr.eqns:
        yield eqn, in_kernel
        inner = in_kernel or eqn.primitive.name == "pallas_call"
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub, inner)


def _contains_pallas(jaxpr) -> bool:
    return any(
        eqn.primitive.name == "pallas_call" for eqn, _ in _walk_eqns(jaxpr)
    )


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def _check_pallas_operands(eqn, where, pool_nbytes, findings):
    """JX003(a): pool-sized pallas operands must be memory_space=ANY."""
    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return
    for i, bm in enumerate(getattr(gm, "block_mappings", ())):
        asd = getattr(bm, "array_aval", None)
        if asd is None:
            continue
        nbytes = math.prod(asd.shape) * jnp.dtype(asd.dtype).itemsize
        if nbytes < pool_nbytes:
            continue
        space = getattr(bm.block_aval, "memory_space", None)
        if "any" not in str(space).lower():
            findings.append(Finding(
                "JX003", where, 0, "error",
                f"pallas operand {i} is pool-sized ({nbytes} bytes, "
                f"shape {tuple(asd.shape)}) but mapped into "
                f"memory_space={space!r} instead of ANY — the whole pool "
                "would be staged into VMEM-sized blocks",
            ))


def lint_jaxpr(
    closed: "ClosedJaxpr",
    where: str,
    pool_nbytes: Optional[int] = None,
    expected_switch_branches: Optional[int] = None,
) -> List[Finding]:
    """Apply rules JX001-JX005 to one traced program."""
    findings: List[Finding] = []
    jaxpr = closed.jaxpr

    for i, v in enumerate(jaxpr.invars):
        aval = v.aval
        if getattr(aval, "weak_type", False):
            findings.append(Finding(
                "JX005", where, 0, "warning",
                f"step input {i} is weak-typed "
                f"({getattr(aval, 'dtype', '?')}) — weak scalars promote "
                "per call site and split the jit cache key (§11 bounded "
                "recompile set)",
            ))
        if str(getattr(aval, "dtype", "")) in _WIDE_DTYPES:
            findings.append(Finding(
                "JX002", where, 0, "error",
                f"step input {i} is {aval.dtype} — 64-bit values in the "
                "serve step double page bytes",
            ))

    seen_f64_prims = set()
    for eqn, in_kernel in _walk_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _HOST_PRIMS:
            findings.append(Finding(
                "JX001", where, 0, "error",
                f"host transfer primitive `{name}` in the hot path — "
                "one host round-trip per serve step",
            ))
        for ov in eqn.outvars:
            dtype = str(getattr(ov.aval, "dtype", ""))
            if dtype in _WIDE_DTYPES and (name, dtype) not in seen_f64_prims:
                seen_f64_prims.add((name, dtype))
                findings.append(Finding(
                    "JX002", where, 0, "error",
                    f"`{name}` produces {dtype} — float64 creep in the "
                    "step program",
                ))
        if name == "pallas_call" and pool_nbytes:
            _check_pallas_operands(eqn, where, pool_nbytes, findings)
        if (
            pool_nbytes
            and not in_kernel
            and name in _MATERIALIZING_PRIMS
        ):
            for ov in eqn.outvars:
                nbytes = _aval_bytes(ov.aval)
                if nbytes >= pool_nbytes:
                    findings.append(Finding(
                        "JX003", where, 0, "error",
                        f"`{name}` materializes a pool-sized buffer "
                        f"({nbytes} bytes) outside any kernel — a "
                        "whole-pool copy per step",
                    ))
        if name == "cond" and expected_switch_branches:
            branches = eqn.params.get("branches", ())
            if len(branches) > 1 and any(
                _contains_pallas(
                    b.jaxpr if isinstance(b, ClosedJaxpr) else b
                )
                for b in branches
            ):
                if len(branches) != expected_switch_branches:
                    findings.append(Finding(
                        "JX004", where, 0, "error",
                        f"kernel dispatch switch has {len(branches)} "
                        f"branches but layer_attn_groups gives "
                        f"{expected_switch_branches} groups — plan "
                        "tuple and group partition disagree",
                    ))
    return findings


# ---------------------------------------------------------------------------
# the serve-step probe
# ---------------------------------------------------------------------------

def probe_config():
    """Smallest config with TWO layer groups (layer 0 sliding-window(4),
    layer 1 global) — exercises the per-group plan tuple, the group
    dispatch switch, and window-aware bucketing in one trace."""
    from ..configs.base import ModelConfig

    return ModelConfig(
        name="analysis-probe", family="dense", n_layers=2, d_model=8,
        n_heads=2, n_kv_heads=1, d_ff=16, vocab_size=32, dtype="float32",
        local_global_ratio=1, sliding_window=4,
    )


def _traced_steps(cfg, impl: str, strategy: str):
    """(name, ClosedJaxpr, pool_nbytes, n_groups) for the decode and
    prefill steps, built exactly as `ContinuousBatcher` builds them."""
    from ..kernels.ops import bucket_args_grouped
    from ..models.transformer import init_lm, layer_attn_groups
    from ..serve.compiled import jit_paged_decode, jit_paged_prefill
    from ..serve.paged_cache import PagedKVCache

    params = init_lm(jax.random.PRNGKey(0), cfg)
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4)
    pc.alloc_slot(0, 9)
    pc.lengths[0] = 9
    pc.alloc_slot(1, 3)
    pc.lengths[1] = 3
    capacity = pc.max_blocks_per_slot * pc.block_size
    n_groups = len(layer_attn_groups(cfg, capacity))
    pool_nbytes = int(pc.k_pages.nbytes)
    out = []

    # decode: ragged lengths -> >1 bucket per group, so the dispatch
    # switch is live in the traced program
    plans, perms = bucket_args_grouped(
        strategy, impl, pc.bucket_needs(pc.lengths + 1),
        pc.max_blocks_per_slot,
    )
    jitted = jit_paged_decode(cfg, impl=impl)
    fn = getattr(jitted, "__wrapped__", jitted)
    tok = jnp.zeros((pc.n_slots, 1), jnp.int32)
    closed = jax.make_jaxpr(functools.partial(fn, plans=plans))(
        params, tok, pc.k_pages, pc.v_pages,
        pc.device_block_tables(), pc.device_block_starts(),
        pc.device_positions(), perms,
    )
    out.append(("decode", closed, pool_nbytes, n_groups))

    # prefill: one-slot suffix launch, block-padded tokens, same slicing
    # as ContinuousBatcher._prefill_into_paged
    t, n_cached = 9, 0
    ns = t - n_cached
    pad = -(-ns // pc.block_size) * pc.block_size
    toks = jnp.zeros((1, pad), jnp.int32)
    plans, perms = bucket_args_grouped(
        strategy, impl, pc.bucket_needs([t], slots=[0]),
        pc.max_blocks_per_slot,
    )
    bt, st = pc.device_block_tables(), pc.device_block_starts()
    if bt.ndim == 2:
        bt, st = bt[0:1], st[0:1]
    else:
        bt, st = bt[:, 0:1], st[:, 0:1]
    jitted = jit_paged_prefill(cfg, impl=impl)
    fn = getattr(jitted, "__wrapped__", jitted)
    closed = jax.make_jaxpr(functools.partial(fn, plans=plans))(
        params, toks, pc.k_pages, pc.v_pages, bt, st,
        jnp.asarray([n_cached], jnp.int32), jnp.asarray([t], jnp.int32),
        jnp.asarray(ns - 1, jnp.int32), perms,
    )
    out.append(("prefill", closed, pool_nbytes, n_groups))
    return out


def lint_serve_steps(
    cfg=None, impl: str = "pallas_interpret", strategy: str = "pow2"
) -> List[Finding]:
    """Trace the real decode + prefill steps on the probe config and
    lint both jaxprs. `impl="pallas_interpret"` keeps the trace faithful
    to the TPU program (same pallas_call structure) while staying
    traceable on CPU."""
    if cfg is None:
        cfg = probe_config()
    findings: List[Finding] = []
    for name, closed, pool_nbytes, n_groups in _traced_steps(
        cfg, impl, strategy
    ):
        findings.extend(lint_jaxpr(
            closed, f"<jaxpr:{name}>", pool_nbytes=pool_nbytes,
            expected_switch_branches=n_groups,
        ))
    return findings
