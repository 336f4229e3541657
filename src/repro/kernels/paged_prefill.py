"""Pallas paged-prefill attention: native-TPU suffix queries over
block-table KV.

The compute half of prefix sharing (DESIGN.md §9, §10). When a request's
leading tokens hit the prefix index, only the *uncached suffix* runs
through prefill — but its queries must still attend to the cached-prefix
pages. This kernel folds every table page into an online softmax for
**all suffix queries at once**, with an offset causal mask — suffix row
`t` sits at logical position `start + t`, so page row `kv_pos`
participates iff

    kv_pos <= start + t          (causality, offset by the cached prefix)
    kv_pos <  total              (ragged: suffix padding rows are garbage)
    kv_pos >  start + t - window (sliding window, logical positions)

A cache hit therefore skips the prefix's prefill compute entirely — the
prefix contributes only page reads — while a miss (start = 0) degenerates
to ordinary causal paged prefill over the whole prompt.

Like the paged-decode kernel (see its module docstring for the full
data-movement story) this is a native-lowerable scalar-prefetch kernel:
block table / start / total / window ride in via
`PrefetchScalarGridSpec`, the KV pools stay in ANY/HBM memory space, the
grid is (slot, kv-block), and each step double-buffer-DMAs one page per
pool into VMEM scratch ahead of the fold. The per-query online-softmax
state (m, l, acc) is carried in VMEM scratch across a slot's kv-block
steps; the last step normalizes and writes the slot's [T, H, hd] output.

Layouts:
    q            [B, T, H, hd]              suffix queries, T padded to a
                                            block multiple (RoPE applied
                                            at start + t by the caller)
    k/v_pages    [n_blocks, KV, bs, hd]     shared pool (ANY/HBM) of
                                            head-major pages, suffix KV
                                            already scattered in
    block_table  [B, max_blocks] int32      page id of slot b's j-th page
    start        [B] int32                  cached-prefix length per slot
    total        [B] int32                  full valid length per slot
    window       [1] int32                  sliding window (cache capacity
                                            = full attention)

Every step folds with the same masked math as `ref.paged_prefill_ref`,
so interpret mode on CPU is bit-comparable to the oracle (parity tests)
and the identical body lowers natively on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .ops import resolve_impl
from .paged_common import (
    VMEM_LIMIT_BYTES,
    bucketed_page_dispatch,
    check_quantized_operands as _check_quantized,
    double_buffered_page_walk,
    effective_walk_start,
    finalize_online_softmax,
    fold_kv_page,
    page_scale_rows,
    reset_online_softmax,
)


def _paged_prefill_kernel(
    # scalar prefetch (SMEM)
    bt_ref,       # [B, max_blocks] int32
    blk_ref,      # [B] int32 — first live block per slot (walk start)
    start_ref,    # [B] int32
    total_ref,    # [B] int32
    win_ref,      # [1] int32
    # blocked / ANY operands, then outputs, then scratch — the exact
    # tuple depends on `quantized` (int8 pools add the slot's two scale
    # row blocks after the pools)
    *refs,
    # float path refs:
    #   q_ref [1, KV, g*T, hd] VMEM | kp_hbm, vp_hbm [n_blocks, KV, bs,
    #   hd] ANY/HBM | out_ref [1, KV, g*T, hd] f32 VMEM | k_buf, v_buf
    #   [2, KV, bs, hd] | m_s, l_s [KV, g*T, 1] f32 | acc_s [KV, g*T, hd]
    #   f32 | sem [2, 2]
    # quantized path inserts ks_ref/vs_ref [1, max_blocks, KV] f32 VMEM
    # (this slot's per-page scale rows) after the pools
    n_groups: int,  # g query heads per KV head (rows = g * T)
    block_size: int,
    depth: int,   # walk depth of THIS launch (<= table width)
    quantized: bool,
):
    if quantized:
        (q_ref, kp_hbm, vp_hbm, ks_ref, vs_ref, out_ref,
         k_buf, v_buf, m_s, l_s, acc_s, sem) = refs
    else:
        (q_ref, kp_hbm, vp_hbm, out_ref,
         k_buf, v_buf, m_s, l_s, acc_s, sem) = refs
        ks_ref = vs_ref = None
    i = pl.program_id(0)               # slot
    j = pl.program_id(1)               # kv block within the slot's table
    n_steps = pl.num_programs(0) * depth
    step = i * depth + j
    mb = bt_ref.shape[1]
    g = n_groups
    t = q_ref.shape[2] // g

    # double-buffered DMA: warm up step 0, prefetch step+1, wait step.
    # The walk covers table columns [start, start + depth): a windowed
    # slot's retired head columns are never visited (DESIGN.md §12)
    cur = double_buffered_page_walk(
        step, n_steps, bt_ref, depth, kp_hbm, vp_hbm, k_buf, v_buf, sem,
        start_ref=blk_ref,
    )

    # -- online-softmax fold (identical math to the ref oracle) -----------
    @pl.when(j == 0)
    def _():
        reset_online_softmax(m_s, l_s, acc_s)

    start = start_ref[i]
    total = total_ref[i]
    window = win_ref[0]
    # query row r = (group head, token) in g-major order: token t sits at
    # logical position start + t
    q_pos = start + jax.lax.broadcasted_iota(
        jnp.int32, (g, t, block_size), 1
    ).reshape(g * t, block_size)                         # [g*T, bs]
    col = effective_walk_start(blk_ref, i, depth, mb) + j
    kv_pos = col * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1
    )                                                    # [1, bs] (2D: TPU)
    ok = (
        (kv_pos <= q_pos)
        & (kv_pos < total)
        & (kv_pos > q_pos - window)
    )                                                    # [g*T, bs]
    fold_kv_page(
        q_ref, k_buf, v_buf, cur, ok, m_s, l_s, acc_s, ks_ref, vs_ref, col
    )

    @pl.when(j == depth - 1)
    def _():
        finalize_online_softmax(l_s, acc_s, out_ref)


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def paged_prefill_attention(
    q: jnp.ndarray,            # [B, T, H, hd]
    k_pages: jnp.ndarray,      # [n_blocks, KV, bs, hd]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_blocks] int32
    start: jnp.ndarray,        # [B] int32
    total: jnp.ndarray,        # [B] int32
    window: jnp.ndarray,       # scalar / [1] int32
    *,
    k_scales: jnp.ndarray | None = None,     # [n_blocks, KV] f32 per-page
    v_scales: jnp.ndarray | None = None,     # scales (int8 pools only)
    block_start: jnp.ndarray | None = None,  # [B] int32 first live block
    depth: int | None = None,  # walk depth; None = full table width
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas entry point; returns f32 [B, T, H, hd] attention outputs.

    `depth` bounds the block walk: the grid becomes (B, depth) and table
    columns outside [start, start + depth) are never DMA'd or folded.
    The bucketed dispatch passes the bucket bound here; every slot in
    the launch must hold its live blocks inside that window or its tail
    KV is silently skipped. `block_start` (default zeros) is the first
    live block per slot (DESIGN.md §12) — retired head columns point at
    scratch and are window-masked, so any start <= the true first live
    block is bit-exact.

    The queries enter the kernel head-major, `[B, KV, g*T, hd]` (an XLA
    transpose outside the kernel), so each KV head's rows are one 2-D
    operand and the fold never reshapes inside a tile.

    `k_scales`/`v_scales` are required iff the pools are int8
    (DESIGN.md §16): each slot's scale rows then ride beside its block
    table and the fold dequantizes in-register — same kernel body, no
    second code path."""
    b, t, h, hd = q.shape
    n_blocks, n_kv, bs, hd2 = k_pages.shape
    assert hd2 == hd, (hd2, hd)
    assert h % n_kv == 0, (h, n_kv)
    quantized = _check_quantized(k_pages, k_scales, v_scales)
    mb = block_table.shape[1]
    depth = mb if depth is None else depth
    assert 1 <= depth <= mb, (depth, mb)
    g = h // n_kv
    rows = g * t
    win = jnp.asarray(window, jnp.int32).reshape(1)
    if block_start is None:
        block_start = jnp.zeros((b,), jnp.int32)
    kernel = functools.partial(
        _paged_prefill_kernel, n_groups=g, block_size=bs, depth=depth,
        quantized=quantized,
    )
    pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2 + (
        [pl.BlockSpec((1, mb, n_kv), lambda i, j, *_: (i, 0, 0))] * 2
        if quantized else []
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,   # table, block_start, start, total, window
        grid=(b, depth),
        in_specs=[
            pl.BlockSpec((1, n_kv, rows, hd), lambda i, j, *_: (i, 0, 0, 0)),
            *pool_specs,         # K/V pools stay in HBM (+ scale rows)
        ],
        out_specs=pl.BlockSpec(
            (1, n_kv, rows, hd), lambda i, j, *_: (i, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, n_kv, bs, hd), k_pages.dtype),
            pltpu.VMEM((2, n_kv, bs, hd), v_pages.dtype),
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),
            pltpu.VMEM((n_kv, rows, hd), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    pools = (
        (k_pages, v_pages, page_scale_rows(k_scales, block_table),
         page_scale_rows(v_scales, block_table)) if quantized
        else (k_pages, v_pages)
    )
    q_heads = (
        q.reshape(b, t, n_kv, g, hd).transpose(0, 2, 3, 1, 4)
        .reshape(b, n_kv, rows, hd)
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rows, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_table.astype(jnp.int32), block_start.astype(jnp.int32),
      jnp.asarray(start, jnp.int32), jnp.asarray(total, jnp.int32), win,
      q_heads, *pools)
    return (
        out.reshape(b, n_kv, g, t, hd).transpose(0, 3, 1, 2, 4)
        .reshape(b, t, h, hd)
    )


def paged_prefill_attention_bucketed(
    q: jnp.ndarray,            # [B, T, H, hd]
    k_pages: jnp.ndarray,      # [n_blocks, KV, bs, hd]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_blocks] int32
    start: jnp.ndarray,        # [B] int32
    total: jnp.ndarray,        # [B] int32
    window: jnp.ndarray,
    plan,                      # ops.BucketPlan (static)
    perm,                      # int32 [sum counts] (dynamic)
    *,
    k_scales: jnp.ndarray | None = None,     # [n_blocks, KV] f32
    v_scales: jnp.ndarray | None = None,     # (int8 pools only)
    block_start: jnp.ndarray | None = None,  # [B] int32 first live block
    interpret: bool = False,
) -> jnp.ndarray:
    """Bucketed dispatch (DESIGN.md §11): one `paged_prefill_attention`
    launch per occupancy bucket (slots grouped by ceil(total / bs), or
    by live trailing blocks when `block_start` rides along — DESIGN.md
    §12), each bounded at the bucket's walk depth. Bit-identical to the
    single launch on every valid query row (start + t < total). Scale
    arrays (int8 pools) pass through whole, like the pools."""
    if block_start is None:
        block_start = jnp.zeros(start.shape, jnp.int32)

    def launch(bound, bt_rows, q_rows, start_rows, total_rows, blk_rows):
        return paged_prefill_attention(
            q_rows, k_pages, v_pages, bt_rows, start_rows, total_rows,
            window, k_scales=k_scales, v_scales=v_scales,
            block_start=blk_rows, depth=bound, interpret=interpret,
        )

    return bucketed_page_dispatch(
        launch, plan, perm, block_table,
        [q, start.astype(jnp.int32), total.astype(jnp.int32),
         block_start.astype(jnp.int32)],
    )


def paged_prefill(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    start: jnp.ndarray,
    total: jnp.ndarray,
    window: jnp.ndarray,
    *,
    impl: str = "auto",
    k_scales=None,
    v_scales=None,
    plan=None,
    perm=None,
    block_start=None,
) -> jnp.ndarray:
    """Impl dispatch, sharing `ops.resolve_impl`: `auto` silently uses the
    jnp oracle on CPU (dry-run lowering) and the native kernel on TPU;
    explicit `pallas` is strict (raises off-TPU); `pallas_interpret`
    forces the kernel body through the interpreter; `ref` is the oracle.

    `plan`/`perm` (from `ops.make_bucket_plan` over the per-slot totals)
    select the bucketed dispatch on the kernel paths; the oracle is a
    dense gather with no page walk to bound, so `ref` mode ignores them
    (and `block_start` — retired columns are masked either way).
    `plan=None` is the single-launch path. `k_scales`/`v_scales`
    (required iff the pools are int8, DESIGN.md §16) follow the pools
    down every arm."""
    _check_quantized(k_pages, k_scales, v_scales)
    mode = resolve_impl(impl)
    if mode == "ref":
        return ref.paged_prefill_ref(
            q, k_pages, v_pages, block_table, start, total, window,
            k_scales=k_scales, v_scales=v_scales,
        )
    if plan is not None:
        return paged_prefill_attention_bucketed(
            q, k_pages, v_pages, block_table, start, total, window,
            plan, perm, k_scales=k_scales, v_scales=v_scales,
            block_start=block_start, interpret=(mode == "interpret"),
        )
    return paged_prefill_attention(
        q, k_pages, v_pages, block_table, start, total, window,
        k_scales=k_scales, v_scales=v_scales,
        block_start=block_start, interpret=(mode == "interpret"),
    )
