"""Public jit'd wrappers around the bit-plane kernels.

`bitplane_matmul` is the op the model stack calls (quant.PimLinear): it
dispatches between the Pallas kernels (TPU, or interpret=True on CPU for
validation) and the pure-jnp reference (CPU dry-run lowering), applies the
unsigned-offset correction and the per-channel dequantization scale.
"""

from __future__ import annotations

import functools
from typing import Literal, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .bitplane_gemm import bitplane_gemm
from .bitplane_gemv import bitplane_gemv
from .pack import pack_bitplanes

Impl = Literal["auto", "pallas", "pallas_interpret", "ref"]

#: a bucket plan: ((walk_depth, padded_slot_count), ...) — hashable, so it
#: can cross a jit boundary as a static argument
BucketPlan = Tuple[Tuple[int, int], ...]
BucketStrategy = Literal["none", "pow2"]

#: B threshold below which the GEMV (untiled-B) kernel is used
_GEMV_MAX_B = 512


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> Literal["ref", "interpret", "native"]:
    """Single home of the impl-dispatch rule, shared by the bit-plane ops
    and the paged-attention kernels.

    `auto` is the silent-dispatch path: the jnp oracle off-TPU (dry-run
    lowering), the native kernel on TPU. Explicit values are **strict**:
    `pallas` raises off-TPU instead of silently running the interpreter
    (a benchmark that asks for the native kernel must never measure the
    interpreter), `pallas_interpret` always runs the kernel body through
    the interpreter, `ref` always runs the oracle.
    """
    if impl == "ref":
        return "ref"
    if impl == "auto":
        return "native" if _on_tpu() else "ref"
    if impl == "pallas_interpret":
        return "interpret"
    if impl == "pallas":
        if not _on_tpu():
            raise RuntimeError(
                "impl='pallas' requests the native TPU kernel but the "
                f"default backend is '{jax.default_backend()}'; use "
                "impl='pallas_interpret' to run the kernel body on CPU or "
                "impl='auto' for silent backend dispatch"
            )
        return "native"
    raise ValueError(
        f"unknown impl {impl!r}; expected one of "
        "'auto', 'pallas', 'pallas_interpret', 'ref'"
    )


# ---------------------------------------------------------------------------
# length-bucketed paged dispatch (DESIGN.md §11)
# ---------------------------------------------------------------------------

def resolve_bucket_strategy(strategy: str) -> BucketStrategy:
    """Single home of the bucket-strategy knob shared by the serving
    layer: `"none"` keeps the PR-3 single-launch walk (every slot folds
    its full table depth), `"pow2"` groups slots into power-of-two
    occupancy buckets so the (slot × kv-block) grid never visits a page
    beyond the bucket bound."""
    if strategy in ("none", "pow2"):
        return strategy
    raise ValueError(
        f"unknown bucket_strategy {strategy!r}; expected 'none' or 'pow2'"
    )


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def make_bucket_plan(
    lengths,
    block_size: int,
    table_width: int,
    strategy: str = "pow2",
    needs=None,
) -> Tuple[Optional[BucketPlan], Optional[np.ndarray]]:
    """Host-side slot→bucket packing for one paged-kernel dispatch.

    `lengths` are the effective kv lengths each slot's launch must cover
    (host ints — decode passes `position + 1`, prefill passes `total`).
    Each slot needs `ceil(len / block_size)` table entries walked; slots
    are grouped by that need rounded up to a power of two (clipped to
    `table_width`), and each group's slot count is also padded to a power
    of two — both roundings exist to bound the recompile set: every
    launch shape is drawn from the O(log(max_blocks) * log(n_slots))
    grid of (bound, count) pairs, not from the raggedness of the tick.

    `needs` overrides the per-slot walk-entry count directly (same
    shape as `lengths`, which is then ignored): a sliding-window layer's
    walk covers only its LIVE trailing blocks
    (`ceil(len/bs) - first_live_block`, the kernels' `block_start`
    offset skipping the retired head), so windowed layers bucket by live
    pages, not total length (DESIGN.md §12).

    Returns `(plan, perm)`:
      plan  ((bound, padded_count), ...) sorted by bound — hashable, the
            static half (jit cache key);
      perm  int32 [sum(padded_count)] — the dynamic half: slot ids
            grouped by bucket, padding entries equal to `n_slots` (they
            gather a dummy scratch row whose output is discarded).

    `(None, None)` means single launch: strategy `"none"`, or a plan
    whose launches (count padding included) would walk at least as many
    table entries as the single full-depth launch — bucketing must only
    ever shrink the streamed bytes, never add launch overhead for equal
    or more traffic.
    """
    if resolve_bucket_strategy(strategy) == "none":
        return None, None
    if needs is None:
        lens = np.asarray(lengths).reshape(-1)
        need = -(-np.maximum(lens.astype(np.int64), 1) // block_size)
    else:
        need = np.maximum(np.asarray(needs).reshape(-1).astype(np.int64), 1)
    n = int(need.shape[0])
    if n == 0:
        return None, None
    buckets: dict = {}
    for slot, nd in enumerate(need):
        bound = min(_next_pow2(int(nd)), table_width)
        buckets.setdefault(bound, []).append(slot)
    plan, perm = [], []
    for bound in sorted(buckets):
        slots = buckets[bound]
        count = _next_pow2(len(slots))
        plan.append((bound, count))
        perm.extend(slots)
        perm.extend([n] * (count - len(slots)))
    if sum(bound * count for bound, count in plan) >= n * table_width:
        return None, None
    return tuple(plan), np.asarray(perm, np.int32)


def plan_streamed_pages(
    plan: Optional[BucketPlan], n_slots: int, table_width: int
) -> int:
    """Table entries (pages per pool) one dispatch's grid spans: the
    structural data-movement quantity `benchmarks/kernel_bench.py`
    sweeps, and what the one-page-a-step prefill walk streams. `None`
    (single launch) spans every slot's full table. The decode walk
    copies only its slots' live pages inside this extent
    (`decode_walk_pages`)."""
    if plan is None:
        return n_slots * table_width
    return sum(bound * count for bound, count in plan)


def decode_walk_pages(
    eff_lengths, first_blocks, block_size: int, pages_per_step: int,
    plan: Optional[BucketPlan] = None,
) -> int:
    """Pages per pool one decode launch over one layer group copies
    (DESIGN.md §10): each slot's walk runs from the P-column block
    holding its first live column (`first_blocks`, the kernels'
    `block_start`) through its last live column — at least one page, as
    an idle slot folds one masked page — and each count-padding row of
    a bucketed `plan` (a zero-length row) copies one page more."""
    eff = np.maximum(np.asarray(eff_lengths).reshape(-1), 1)
    n_cols = -(-eff // block_size)
    first = np.minimum(
        np.asarray(first_blocks).reshape(-1) // pages_per_step,
        (n_cols - 1) // pages_per_step,
    )
    pages = int((n_cols - first * pages_per_step).sum())
    if plan is not None:
        pages += sum(count for _, count in plan) - eff.shape[0]
    return pages


def per_group_plans(plans, n_groups: int):
    """The per-group form of the static half `bucket_args_grouped`
    returns: a tuple of per-group plans (entries may be None), a single
    plan shared by every group, or None for the everywhere-single-launch
    path."""
    if plans is None:
        return (None,) * n_groups
    if is_bucket_plan(plans):
        return (plans,) * n_groups
    return tuple(plans)


def grouped_streamed_pages(
    plans, n_slots: int, table_width: int, n_groups: int = 1
):
    """Per-group `plan_streamed_pages` for one layer-major dispatch —
    the extent of each group's grid, from the same plans
    `bucket_args_grouped` returned (see `per_group_plans`)."""
    return [
        plan_streamed_pages(p, n_slots, table_width)
        for p in per_group_plans(plans, n_groups)
    ]


def bucket_args(
    strategy: str,
    kernel_impl: str,
    eff_lengths,
    block_size: int,
    table_width: int,
):
    """The serving layer's slot→bucket packing for one launch — the one
    policy `ServeEngine` and `ContinuousBatcher` both apply: `(plan,
    perm-as-device-array)` from `make_bucket_plan`, or `(None, None)`
    for the single-launch path when the strategy is `"none"` OR the impl
    resolves to the oracle (which ignores plans — building them would
    only retrace the jitted step per plan for zero streamed-byte
    benefit; `auto` on CPU therefore keeps its single compile)."""
    if (
        resolve_bucket_strategy(strategy) == "none"
        or resolve_impl(kernel_impl) == "ref"
    ):
        return None, None
    plan, perm = make_bucket_plan(eff_lengths, block_size, table_width)
    return plan, None if perm is None else jnp.asarray(perm)


def is_bucket_plan(plan) -> bool:
    """True for a SINGLE BucketPlan `((bound, count), ...)` as opposed to
    a per-group tuple of plans `(plan_or_None, ...)` — the two shapes the
    paged model entry points accept for their `bucket_plan` argument."""
    return (
        isinstance(plan, tuple)
        and len(plan) > 0
        and isinstance(plan[0], tuple)
        and len(plan[0]) == 2
        and isinstance(plan[0][0], (int, np.integer))
    )


def bucket_args_grouped(
    strategy: str,
    kernel_impl: str,
    needs_by_group,
    table_width: int,
):
    """Per-group slot→bucket packing for one layer-major launch
    (DESIGN.md §12): `needs_by_group` is one live-walk-entry array per
    layer group (global groups pass `ceil(len/bs)`, windowed groups pass
    live trailing blocks only). Returns `(plans, perms)` — a tuple of
    per-group plans (static jit half; entries may be None when that
    group degenerates to the single launch) and the matching tuple of
    device permutation arrays — or `(None, None)` when no group's plan
    exists (or the strategy/impl rules out bucketing entirely), which is
    the everywhere-single-launch path."""
    if (
        resolve_bucket_strategy(strategy) == "none"
        or resolve_impl(kernel_impl) == "ref"
    ):
        return None, None
    plans, perms = [], []
    for needs in needs_by_group:
        plan, perm = make_bucket_plan(None, 0, table_width, needs=needs)
        plans.append(plan)
        perms.append(None if perm is None else jnp.asarray(perm))
    if all(p is None for p in plans):
        return None, None
    return tuple(plans), tuple(perms)


def quantize_and_pack(
    w: jnp.ndarray, n_bits: int, group: int = 1, impl: Impl = "auto"
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """W [K, M] float -> (planes [n_d, K*g//8, M] u8, scale [M] f32)."""
    w_q, scale = ref.quantize_ref(w, n_bits)
    u = (w_q + 2 ** (n_bits - 1)).astype(jnp.uint8)
    dpb = 8 // group
    k, m = u.shape
    u_r = u.reshape(k // dpb, dpb, m).transpose(1, 0, 2)  # [dpb, K8, M]
    mode = resolve_impl(impl)
    if mode == "ref":
        planes = ref.pack_ref(w_q, n_bits, group)
    else:
        planes = pack_bitplanes(
            u_r, n_bits=n_bits, group=group, interpret=(mode == "interpret")
        )
    return planes, scale


def bitplane_matmul(
    x: jnp.ndarray,        # [B, K] or [..., K]
    planes: jnp.ndarray,   # [n_digits, K*g//8, M] uint8
    scale: jnp.ndarray,    # [M] f32
    *,
    n_bits: int,
    group: int = 1,
    impl: Impl = "auto",
    block_m: int = 256,
    block_k8: int = 128,
) -> jnp.ndarray:
    """y = x @ dequant(planes, scale); batch dims flattened internally."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    xf = x.reshape(-1, k)
    b = xf.shape[0]
    m = planes.shape[-1]

    mode = resolve_impl(impl)
    if mode == "ref":
        y = ref.bitplane_matmul_ref(xf, planes, scale, n_bits, group)
        return y.reshape(*lead, m)

    interpret = mode == "interpret"
    x_r = ref.prepare_x_ref(xf, group)
    kern = bitplane_gemv if b <= _GEMV_MAX_B else bitplane_gemm
    raw = kern(
        x_r,
        planes,
        n_bits=n_bits,
        group=group,
        block_m=block_m,
        block_k8=block_k8,
        interpret=interpret,
    )
    off = float(2 ** (n_bits - 1))
    sum_x = jnp.sum(xf.astype(jnp.float32), axis=-1, keepdims=True)
    y = (raw - off * sum_x) * scale[None, :]
    return y.astype(x.dtype).reshape(*lead, m)


def packed_bytes(k: int, m: int, n_bits: int, group: int = 1) -> int:
    """HBM bytes of the packed representation — the bandwidth-amplification
    accounting used by the roofline (paper: '100% of BRAM bandwidth')."""
    nd = -(-n_bits // group)
    return nd * (k * group // 8) * m + 4 * m  # planes + f32 scale
