"""Pallas paged-decode attention: native-TPU block-table gather.

The serving-side companion of the bit-plane GEMV (DESIGN.md §8, §10):
decode attention where each slot's KV lives in non-contiguous fixed-size
pages of a shared pool, addressed through a per-slot block table — so a
batch of requests with completely different prompt lengths decodes in one
fused call, no padding to a common length.

The data-movement path is the one real hardware executes ("the BRAM is
the limit" applied to TPU serving): the KV pools stay in ANY/HBM memory
space and are never mapped whole into a grid step. Instead the block
table, walk starts, lengths and window ride in as **scalar-prefetch
operands** (`PrefetchScalarGridSpec`), and the grid is (slot, block):
each step covers a block of P table columns (`decode_pages_per_step`,
from the page bytes and the table width), aligned to multiples of P.
Only a slot's live blocks are walked — from the block holding its first
live column through the block holding its last — and within a block
only the pages up to the last live column are DMA'd, into a
double-buffered VMEM scratch; the next live block's copies are issued
before this block's fold, so the walk overlaps compute. Steps past a
slot's last live block do nothing. The fold scores a whole block at
once per KV head; the online-softmax state (m, l, acc) is carried
across a slot's steps in VMEM scratch, and the last step normalizes and
writes the slot's output block (DESIGN.md §10).

Layouts:
    q            [B, H, hd]                 one query token per slot
    k/v_pages    [n_blocks, KV, bs, hd]     the shared page pool (ANY/HBM),
                                            head-major pages
    block_table  [B, max_blocks] int32      page id of slot b's j-th page
    lengths      [B] int32                  valid kv count (ragged)
    window       [1] int32                  sliding window (cache capacity
                                            = full attention)

Every block folds with the same masked math as the
`ref.paged_attention_ref` oracle — including columns past a slot's
length and a windowed slot's retired head, whose contributions cancel
through the online-softmax rescale — so interpret mode on CPU matches
the oracle on every row with length >= 1 across ragged lengths,
windows, and COW-fragmented tables (the parity tests), and the
identical body lowers natively on TPU.
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
    bucketed_page_dispatch,
    check_quantized_operands as _check_quantized,
    decode_pages_per_step,
    double_buffered_block_walk,
    finalize_online_softmax,
    fold_kv_block,
    page_scale_rows,
    reset_online_softmax,
)


def _paged_decode_kernel(
    # scalar prefetch (SMEM)
    bt_ref,       # [B, max_blocks] int32
    st_ref,       # [B] int32 — first live table column per slot
    len_ref,      # [B] int32
    win_ref,      # [1] int32
    # blocked / ANY operands, then outputs, then scratch — the exact
    # tuple depends on `quantized` (int8 pools add the slot's two scale
    # row blocks after the pools)
    *refs,
    # float path refs:
    #   q_ref [1, KV, g, hd] VMEM | kp_hbm, vp_hbm [n_blocks, KV, bs, hd]
    #   ANY/HBM | out_ref [1, KV, g, hd] f32 VMEM | k_buf, v_buf
    #   [2, P, KV, bs, hd] | m_s, l_s [KV, g, 1] f32 | acc_s [KV, g, hd]
    #   f32 | sem [2, 2] | buf_ref [1] int32 SMEM
    # quantized path inserts ks_ref/vs_ref [1, ceil(max_blocks / P) * P,
    # KV] f32 VMEM (this slot's per-page scale rows) after the pools
    block_size: int,
    quantized: bool,
):
    if quantized:
        (q_ref, kp_hbm, vp_hbm, ks_ref, vs_ref, out_ref,
         k_buf, v_buf, m_s, l_s, acc_s, sem, buf_ref) = refs
    else:
        (q_ref, kp_hbm, vp_hbm, out_ref,
         k_buf, v_buf, m_s, l_s, acc_s, sem, buf_ref) = refs
        ks_ref = vs_ref = None
    i = pl.program_id(0)               # slot
    j = pl.program_id(1)               # block of the slot's walk
    n_steps = pl.num_programs(1)
    pages = k_buf.shape[1]

    # double-buffered DMA over live blocks only: the next live block is
    # started before this one is waited on; blocks past the slot's
    # length (and a windowed slot's retired head, DESIGN.md §12) are
    # never visited
    live, cur, first = double_buffered_block_walk(
        i, j, pl.num_programs(0), n_steps, bt_ref, st_ref, len_ref,
        block_size, kp_hbm, vp_hbm, k_buf, v_buf, sem, buf_ref,
    )

    # -- online-softmax fold (identical math to the ref oracle) -----------
    @pl.when(j == 0)
    def _():
        reset_online_softmax(m_s, l_s, acc_s)

    @pl.when(live)
    def _():
        length = len_ref[i]
        window = win_ref[0]
        q_pos = length - 1
        col = (first + j) * pages
        kv_pos = col * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, pages * block_size), 1
        )                                                # [1, P*bs] (2D)
        ok = (kv_pos < length) & (kv_pos > q_pos - window)
        fold_kv_block(
            q_ref, k_buf, v_buf, cur, ok, m_s, l_s, acc_s, ks_ref, vs_ref,
            col,
        )

    @pl.when(j == n_steps - 1)
    def _():
        finalize_online_softmax(l_s, acc_s, out_ref)


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,            # [B, H, hd]
    k_pages: jnp.ndarray,      # [n_blocks, KV, bs, hd]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_blocks] int32
    lengths: jnp.ndarray,      # [B] int32
    window: jnp.ndarray,       # scalar / [1] int32
    *,
    k_scales: jnp.ndarray | None = None,     # [n_blocks, KV] f32 per-page
    v_scales: jnp.ndarray | None = None,     # scales (int8 pools only)
    block_start: jnp.ndarray | None = None,  # [B] int32 first live block
    depth: int | None = None,  # walk depth; None = full table width
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas entry point; returns f32 [B, H, hd] attention outputs.

    The walk folds blocks of P = `decode_pages_per_step` table columns,
    aligned to multiples of P, from the block holding `block_start`
    through the block holding the slot's last live position; blocks
    past that are neither DMA'd nor folded. `depth` bounds the walk: the
    grid becomes (B, blocks covering `depth` columns) and a slot whose
    live columns run past `block_start + depth` silently loses its tail
    KV — the bucketed dispatch passes the bucket bound here, which
    covers every slot in its bucket. `block_start` (default zeros) is
    the per-slot first live block: a sliding-window layer retires its
    leading blocks (DESIGN.md §12), and the walk starts at the block
    holding the first live one — retired columns point at scratch and
    are fully window-masked, so any start <= the true first live block
    is bit-exact (start 0 = the full walk).

    Length-0 rows are don't-care: they fold one fully masked block and
    come out finite. Every launch over one table folds the same aligned
    blocks of every row with length >= 1, so a bucketed launch is
    bit-identical to the single one there.

    `k_scales`/`v_scales` are required iff the pools are int8
    (DESIGN.md §16): each slot's scale rows then ride beside its block
    table and the fold dequantizes in-register — same kernel body, no
    second code path."""
    b, h, hd = q.shape
    n_blocks, n_kv, bs, hd2 = k_pages.shape
    assert hd2 == hd, (hd2, hd)
    assert h % n_kv == 0, (h, n_kv)
    quantized = _check_quantized(k_pages, k_scales, v_scales)
    mb = block_table.shape[1]
    depth = mb if depth is None else depth
    assert 1 <= depth <= mb, (depth, mb)
    g = h // n_kv
    pages = decode_pages_per_step(
        n_kv * bs * hd * k_pages.dtype.itemsize, mb
    )
    table_blocks = pl.cdiv(mb, pages)
    # a walk that starts at an unaligned `block_start` spans one block
    # more than its depth does
    steps = min(
        pl.cdiv(depth, pages) + (block_start is not None and depth < mb),
        table_blocks,
    )
    win = jnp.asarray(window, jnp.int32).reshape(1)
    if block_start is None:
        block_start = jnp.zeros((b,), jnp.int32)
    bt = block_table.astype(jnp.int32)
    kernel = functools.partial(
        _paged_decode_kernel, block_size=bs, quantized=quantized,
    )
    pools = (k_pages, v_pages)
    if quantized:
        # each slot's scale rows, padded to whole blocks: the fold reads
        # a block's P rows (the padding rows scale scratch page 0)
        bt_blocks = jnp.pad(bt, ((0, 0), (0, table_blocks * pages - mb)))
        pools += (page_scale_rows(k_scales, bt_blocks),
                  page_scale_rows(v_scales, bt_blocks))
    pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2 + (
        [pl.BlockSpec((1, table_blocks * pages, n_kv),
                      lambda i, j, *_: (i, 0, 0))] * 2
        if quantized else []
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,   # block_table, block_start, lengths, window
        grid=(b, steps),
        in_specs=[
            pl.BlockSpec((1, n_kv, g, hd), lambda i, j, *_: (i, 0, 0, 0)),
            *pool_specs,         # K/V pools stay in HBM (+ scale rows)
        ],
        out_specs=pl.BlockSpec(
            (1, n_kv, g, hd), lambda i, j, *_: (i, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, pages, n_kv, bs, hd), k_pages.dtype),
            pltpu.VMEM((2, pages, n_kv, bs, hd), v_pages.dtype),
            pltpu.VMEM((n_kv, g, 1), jnp.float32),
            pltpu.VMEM((n_kv, g, 1), jnp.float32),
            pltpu.VMEM((n_kv, g, hd), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, hd), jnp.float32),
        interpret=interpret,
        name="paged_decode_attention",
    )(bt, block_start.astype(jnp.int32), lengths.astype(jnp.int32), win,
      q.reshape(b, n_kv, g, hd), *pools)
    return out.reshape(b, h, hd)


def paged_decode_attention_bucketed(
    q: jnp.ndarray,            # [B, H, hd]
    k_pages: jnp.ndarray,      # [n_blocks, KV, bs, hd]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_blocks] int32
    lengths: jnp.ndarray,      # [B] int32
    window: jnp.ndarray,
    plan,                      # ops.BucketPlan (static)
    perm,                      # int32 [sum counts] (dynamic)
    *,
    k_scales: jnp.ndarray | None = None,     # [n_blocks, KV] f32
    v_scales: jnp.ndarray | None = None,     # (int8 pools only)
    block_start: jnp.ndarray | None = None,  # [B] int32 first live block
    interpret: bool = False,
) -> jnp.ndarray:
    """Bucketed dispatch (DESIGN.md §11): one `paged_decode_attention`
    launch per occupancy bucket, each bounded at the bucket's walk
    depth, rows gathered/scattered through the bucket permutation. Bit-
    identical to the single launch on every slot with length >= 1.
    With `block_start` (DESIGN.md §12) the plan may bucket windowed
    slots by LIVE trailing blocks — each launch walks
    [start, start + bound) of the gathered rows. Scale arrays (int8
    pools) pass through whole, like the pools — only per-slot rows are
    bucket-gathered."""
    if block_start is None:
        block_start = jnp.zeros(lengths.shape, jnp.int32)

    def launch(bound, bt_rows, q_rows, len_rows, start_rows):
        return paged_decode_attention(
            q_rows, k_pages, v_pages, bt_rows, len_rows, window,
            k_scales=k_scales, v_scales=v_scales,
            block_start=start_rows, depth=bound, interpret=interpret,
        )

    return bucketed_page_dispatch(
        launch, plan, perm, block_table,
        [q, lengths.astype(jnp.int32), block_start.astype(jnp.int32)],
    )


def paged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
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

    `plan`/`perm` (from `ops.make_bucket_plan`) select the bucketed
    dispatch on the kernel paths; the oracle is a dense gather with no
    page walk to bound, so `ref` mode ignores them (and `block_start` —
    retired columns are window-masked either way). `plan=None` is the
    single-launch path. `k_scales`/`v_scales` (required iff the pools
    are int8, DESIGN.md §16) follow the pools down every arm."""
    _check_quantized(k_pages, k_scales, v_scales)
    mode = resolve_impl(impl)
    if mode == "ref":
        return ref.paged_attention_ref(
            q, k_pages, v_pages, block_table, lengths, window,
            k_scales=k_scales, v_scales=v_scales,
        )
    if plan is not None:
        return paged_decode_attention_bucketed(
            q, k_pages, v_pages, block_table, lengths, window, plan, perm,
            k_scales=k_scales, v_scales=v_scales,
            block_start=block_start, interpret=(mode == "interpret"),
        )
    return paged_decode_attention(
        q, k_pages, v_pages, block_table, lengths, window,
        k_scales=k_scales, v_scales=v_scales,
        block_start=block_start, interpret=(mode == "interpret"),
    )
