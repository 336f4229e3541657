"""Shared kernel pieces of the two paged-attention kernels.

Both paged kernels (decode and prefill) walk a per-slot block table over
a (slot, kv-block) grid, consume one K page + one V page per step from
ANY/HBM-resident pools, and fold each page into a carried online
softmax. The DMA pipeline and the fold math are identical in both —
decode is simply the T=1 case of the fold — so both halves live here:
a fix to the semaphore layout, the prefetch guard, or the softmax
numerics (NEG_INF sentinel, alpha rescale, max(l, eps) epilogue) lands
in exactly one place and cannot diverge between the kernels
(DESIGN.md §10). The bucketed dispatch scaffold (DESIGN.md §11) lives
here too: one launch per occupancy bucket, each walking only the bucket
bound instead of the full table depth.

Quantized KV pages (DESIGN.md §16) are also anchored here: the per-page
int8 code <-> float conversion (`quantize_pages` / `dequantize_pages` /
`requantize_page_update`) and the in-register dequant on the kernel path
(`load_kv_page`, fed by the scale rows the page walk double-buffers next
to each K/V page). This is the ONLY module where quantized page codes
turn back into floats — the models/serve layers call
`requantize_page_update` for appends and otherwise move codes around
opaquely (analysis rule RL206 pins this).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: scoped-VMEM limit of the paged kernels. A prefill launch holds its
#: slot's whole query block, output block and fold state in VMEM (about
#: 3 KiB per query row and KV head), which passes the 16 MiB default at
#: a few thousand rows; a v5e core has 128 MiB.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024

#: symmetric int8 quantization ceiling — codes live in [-128, 127], the
#: per-(page, head) absmax maps to ±127 (same convention as the weight
#: path's `ref.quantize_ref`)
INT8_QMAX = 127.0


def pages_to_tokens(pages):
    """Head-major pages [..., n, KV, bs, hd] -> token rows
    [..., n * bs, KV, hd], in page order."""
    *lead, n, kv, bs, hd = pages.shape
    return jnp.swapaxes(pages, -3, -2).reshape(*lead, n * bs, kv, hd)


def tokens_to_pages(rows, block_size: int):
    """Inverse of `pages_to_tokens`: token rows [..., S, KV, hd] (S a
    multiple of `block_size`) -> head-major pages [..., S/bs, KV, bs,
    hd]."""
    *lead, s, kv, hd = rows.shape
    paged = rows.reshape(*lead, s // block_size, block_size, kv, hd)
    return jnp.swapaxes(paged, -3, -2)


def quantize_pages(pages):
    """Per-page, per-head symmetric int8 quantization of KV pages.

    `pages` is float [..., KV, bs, hd] (any number of leading page
    axes); returns `(codes int8 [..., KV, bs, hd], scales f32 [..., KV])`
    with `scale = absmax / 127` over each page's (bs, hd) plane per KV
    head. All-zero planes take scale 1.0 (the guard keeps dequant exact
    at 0 and division well-defined), matching `ref.quantize_ref`.
    """
    f = pages.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(f), axis=(-2, -1))
    scales = jnp.where(absmax > 0, absmax / INT8_QMAX, 1.0)
    scales = scales.astype(jnp.float32)
    codes = jnp.clip(
        jnp.round(f / scales[..., None, None]),
        -(INT8_QMAX + 1), INT8_QMAX,
    ).astype(jnp.int8)
    return codes, scales


def dequantize_pages(codes, scales):
    """Inverse of `quantize_pages`: int8 codes [..., KV, bs, hd] times
    their per-(page, head) f32 scales [..., KV] -> f32 pages. The single
    home of code->float conversion (RL206); the oracles and the append
    path both route through here."""
    return codes.astype(jnp.float32) * scales[..., None, None]


def requantize_page_update(codes, scales, update_fn):
    """Read-modify-write on quantized pages: dequantize the touched
    pages, apply `update_fn` on the float view (scatter new tokens in),
    and requantize under the updated per-head absmax. This is how every
    append lands in an int8 pool — the page's scale tracks its true
    content, at the cost of one rounding pass over the page's existing
    codes per append (bounded drift, covered by the tolerance parity
    tests)."""
    return quantize_pages(update_fn(dequantize_pages(codes, scales)))


def check_quantized_operands(k_pages, k_scales, v_scales) -> bool:
    """Validate the pool-dtype/scale pairing of one launch and return
    whether it is the quantized path: int8 pools MUST bring both
    per-page scale arrays, float pools must bring none — a mismatch is a
    caller bug (a scale array silently ignored, or codes folded as
    values), never something to paper over."""
    quantized = jnp.issubdtype(k_pages.dtype, jnp.integer)
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError(
            f"int8 KV pools (dtype {k_pages.dtype}) require k_scales and "
            "v_scales — quantized pages are meaningless without their "
            "per-page scale rows (DESIGN.md §16)"
        )
    if not quantized and (k_scales is not None or v_scales is not None):
        raise ValueError(
            f"float KV pools (dtype {k_pages.dtype}) must not pass "
            "k_scales/v_scales — the scale operands only ride quantized "
            "pools (DESIGN.md §16)"
        )
    return bool(quantized)


def page_scale_rows(scales, block_table):
    """Per-slot scale rows of one launch, `scales[block_table]` ->
    f32 [B, max_blocks, KV]: the table's pages' scales gathered in XLA
    and handed to the kernel as a blocked VMEM operand. A per-page DMA
    of a `[1, KV]` scale row is refused by Mosaic (a lane slice narrower
    than the 128-lane tile), so the scales travel with the slot's table
    row and the fold picks its page's row by table column."""
    return scales[block_table].astype(jnp.float32)


def load_kv_page(k_buf, v_buf, cur, head, scale_rows=None):
    """Read KV head `head` of buffer `cur`'s K/V page as f32 [bs, hd] —
    the in-register dequant point of the quantized path (DESIGN.md §16):
    with `scale_rows` (this page's `(k_row, v_row)`, each f32 [1, KV])
    every code multiplies its page's per-head scale right here, between
    the DMA wait and the softmax fold. `scale_rows=None` is the float
    path (plain astype, unchanged math)."""
    kj = k_buf[cur, head].astype(jnp.float32)
    vj = v_buf[cur, head].astype(jnp.float32)
    if scale_rows is not None:
        # a scalar, not a [1, 1] slice: Mosaic cannot broadcast [1, 1]
        # across sublanes and lanes at once
        k_row, v_row = scale_rows
        kj = kj * k_row[0, head]
        vj = vj * v_row[0, head]
    return kj, vj


def effective_walk_start(start_ref, slot, depth: int, table_width: int):
    """First table column a `depth`-bounded walk visits for `slot`.

    `start_ref[slot]` is the slot's first LIVE block (everything before
    it was retired by the sliding window — those columns point at the
    scratch page and are fully masked). The walk starts there so a
    windowed layer's grid covers only live trailing blocks; clamping to
    `table_width - depth` keeps the window [start, start + depth) inside
    the table when the launch depth over-covers the slot (the extra
    leading columns it then re-visits are retired, i.e. masked no-ops —
    and with `depth == table_width` the start degenerates to 0, which is
    exactly the pre-layer-major full walk). Returns 0 when no start
    operand rides the launch."""
    if start_ref is None:
        return 0
    return jnp.maximum(
        jnp.minimum(start_ref[slot], table_width - depth), 0
    )


def double_buffered_page_walk(
    step,         # linear grid step: slot * depth + kv_block
    n_steps,      # total grid steps: n_slots * depth
    bt_ref,       # [B, >= depth] int32 block table (scalar prefetch)
    depth: int,   # per-LAUNCH walk depth — may be narrower than the
                  # table when a bucketed dispatch bounds the grid
                  # (DESIGN.md §11); pages at column >= depth are never
                  # visited
    kp_hbm,       # [n_blocks, KV, bs, hd] K pool — ANY/HBM ref
    vp_hbm,       # V pool
    k_buf,        # [2, KV, bs, hd] VMEM landing buffers
    v_buf,
    sem,          # DMA semaphores [2 buffers, 2 pools]
    start_ref=None,  # [B] int32 first live block per slot (scalar
                     # prefetch) — None keeps the column-0 walk
):
    """Run one grid step of the double-buffered block walk: start the
    copies for step+1, wait for this step's pages, and return the buffer
    index now holding them (read them through `load_kv_page`). Pages are
    head-major, so one copy moves a whole `[KV, bs, hd]` page whose
    minor (bs, hd) plane is tile-aligned for every pool dtype (an int8
    `[bs, KV, hd]` page is not: its KV axis pads to the int8 tile)."""
    table_width = bt_ref.shape[1]

    def page_copies(s, slot):
        """The async page copies (K and V pools) of linear step `s` into
        buffer `slot` — recreated identically to start and to wait."""
        col = effective_walk_start(
            start_ref, s // depth, depth, table_width
        ) + s % depth
        page = bt_ref[s // depth, col]
        return (
            pltpu.make_async_copy(
                kp_hbm.at[pl.ds(page, 1)], k_buf.at[pl.ds(slot, 1)],
                sem.at[slot, 0],
            ),
            pltpu.make_async_copy(
                vp_hbm.at[pl.ds(page, 1)], v_buf.at[pl.ds(slot, 1)],
                sem.at[slot, 1],
            ),
        )

    @pl.when(step == 0)
    def _():
        for c in page_copies(0, 0):
            c.start()

    @pl.when(step + 1 < n_steps)
    def _():
        for c in page_copies(step + 1, (step + 1) % 2):
            c.start()

    cur = jax.lax.rem(step, 2)
    for c in page_copies(step, cur):
        c.wait()
    return cur


def reset_online_softmax(m_s, l_s, acc_s):
    """Start a slot's fold: -inf running max, zero normalizer/values."""
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)


def fold_kv_page(q_ref, k_buf, v_buf, cur, ok, m_s, l_s, acc_s,
                 ks_ref=None, vs_ref=None, col=None):
    """Fold buffer `cur`'s page into the carried (m, l, acc) state of
    every query row, one KV head at a time.

    `q_ref` is the slot's head-major query block [1, KV, R, hd] (R rows
    per KV head: the g query heads of the group, times T suffix tokens in
    prefill); `ok` is the validity mask, broadcastable to [R, bs]; the
    state refs are m/l [KV, R, 1] and acc [KV, R, hd]. Each head is a
    plain 2-D matmul pair, so nothing inside the kernel reshapes across
    the (sublane, lane) tile. Masked rows score NEG_INF, and their unit
    contributions while the running max is still NEG_INF cancel later
    through the alpha rescale (the oracle computes don't-care rows the
    same way — parity). On quantized pools `ks_ref`/`vs_ref` are the
    slot's scale rows [1, max_blocks, KV] and `col` the page's table
    column."""
    scale_rows = None
    if ks_ref is not None:
        scale_rows = (ks_ref[0, pl.ds(col, 1), :], vs_ref[0, pl.ds(col, 1), :])
    n_kv, hd = q_ref.shape[1], q_ref.shape[3]
    for h in range(n_kv):
        qf = q_ref[0, h].astype(jnp.float32) * (hd ** -0.5)     # [R, hd]
        kj, vj = load_kv_page(k_buf, v_buf, cur, h, scale_rows)
        scores = jax.lax.dot_general(
            qf, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                       # [R, bs]
        scores = jnp.where(ok, scores, NEG_INF)
        m, l, acc = m_s[h], l_s[h], acc_s[h]
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)
        m_s[h] = m_new
        l_s[h] = alpha * l + p.sum(axis=-1, keepdims=True)
        acc_s[h] = alpha * acc + jnp.dot(
            p, vj, preferred_element_type=jnp.float32
        )


def finalize_online_softmax(l_s, acc_s, out_ref):
    """Normalize the carried state into the slot's output block
    [1, KV, R, hd]; max(l, eps) keeps fully-masked rows finite (matching
    the oracles' don't-care semantics)."""
    for h in range(acc_s.shape[0]):
        out_ref[0, h] = acc_s[h] / jnp.maximum(l_s[h], 1e-30)


def bucketed_page_dispatch(launch, plan, perm, block_table, slot_operands):
    """Shared gather/launch/scatter scaffold of the bucketed dispatch
    layer (DESIGN.md §11): one kernel launch per occupancy bucket, each
    bounded at the bucket's walk depth, so no launch ever visits a table
    column past what its slots occupy.

    `launch(depth, bt_rows, *operand_rows) -> [count, ...]` runs one
    bucket; `plan`/`perm` come from `ops.make_bucket_plan`;
    `slot_operands` are the per-slot arrays (leading axis = slot) to
    gather alongside the block-table rows. A dummy all-zero row is
    appended to every gathered array — count-padding entries of `perm`
    point at it (zero table = scratch page, zero length = fully-masked
    fold) and their outputs land on the dummy output row, which is
    dropped. Real slots appear exactly once in `perm`, so the scatter
    writes every output row exactly once.

    Tail columns a bucket's bound cuts off are fully masked for every
    valid row, and a fully-masked page folds as an exact no-op (`p`
    underflows to 0, `alpha` = 1) — so the bucketed output is
    bit-identical to the single launch on every row with at least one
    unmasked position. Don't-care rows (length 0 / past `total`) remain
    don't-care: their garbage depends on how many masked pages fold.
    """
    b = block_table.shape[0]
    bt_ext = jnp.concatenate(
        [block_table, jnp.zeros_like(block_table[:1])], axis=0
    )
    ops_ext = [
        jnp.concatenate([o, jnp.zeros_like(o[:1])], axis=0)
        for o in slot_operands
    ]
    perm = jnp.asarray(perm, jnp.int32)
    outs, off = [], 0
    for bound, count in plan:
        idx = jax.lax.slice_in_dim(perm, off, off + count)
        # trace-time scope: tags the bucket launch's ops in HLO metadata
        # so profiles attribute streamed pages per bucket (free when no
        # profiler is attached — it only renames metadata)
        with jax.named_scope(f"paged_bucket_d{bound}x{count}"):
            outs.append(
                launch(bound, bt_ext[idx], *[o[idx] for o in ops_ext])
            )
        off += count
    res = jnp.concatenate(outs, axis=0)
    out_full = jnp.zeros((b + 1,) + res.shape[1:], res.dtype)
    return out_full.at[perm].set(res)[:b]
