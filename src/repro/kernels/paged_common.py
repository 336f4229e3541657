"""Shared kernel pieces of the two paged-attention kernels.

Both paged kernels (decode and prefill) walk a per-slot block table,
DMA K and V pages from ANY/HBM-resident pools into double-buffered VMEM
scratch, and fold them into a carried online softmax. Decode walks a
(slot, block) grid of `decode_pages_per_step` table columns a step and
skips the blocks past each slot's length (`double_buffered_block_walk`);
prefill walks a (slot, kv-block) grid one page a step
(`double_buffered_page_walk`). The fold math is one function for both —
decode is the T=1 case of the fold, a block just a wider page — so a fix
to the softmax numerics (NEG_INF sentinel, alpha rescale, max(l, eps)
epilogue) lands in exactly one place and cannot diverge between the
kernels (DESIGN.md §10). The bucketed dispatch scaffold (DESIGN.md §11)
lives here too: one launch per occupancy bucket, each walking only the
bucket bound instead of the full table depth.

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

#: bytes of the P K pages plus P V pages one decode grid step may move:
#: wide enough that the page copies, not the fixed cost of a grid step
#: and of a block's fold, set the pace, while both double buffers (4
#: MiB) stay well inside the default scoped VMEM (DESIGN.md §10)
DECODE_BLOCK_BYTES = 2 * 1024 * 1024

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


def load_kv_block(k_buf, v_buf, cur, head, scale_rows=None):
    """Read KV head `head` of buffer `cur`'s block of P K/V pages as f32
    [P * bs, hd], page after page. `scale_rows` (int8 pools) holds each
    page's `(k_row, v_row)`: every page then dequantizes through
    `load_kv_page` with its own per-head scale before the pages are
    stacked. `scale_rows=None` is the float path: one load of the head's
    P page planes and a tile-aligned merge of the page axis."""
    if scale_rows is None:
        kb = k_buf[cur, :, head].astype(jnp.float32)     # [P, bs, hd]
        vb = v_buf[cur, :, head].astype(jnp.float32)
        pages, bs, hd = kb.shape
        return kb.reshape(pages * bs, hd), vb.reshape(pages * bs, hd)
    kvs = [
        load_kv_page(k_buf.at[cur], v_buf.at[cur], p, head, rows)
        for p, rows in enumerate(scale_rows)
    ]
    return (jnp.concatenate([k for k, _ in kvs], axis=0),
            jnp.concatenate([v for _, v in kvs], axis=0))


def decode_pages_per_step(page_bytes: int, table_width: int) -> int:
    """P, the table columns one decode grid step walks: the largest power
    of two whose P K pages and P V pages fit `DECODE_BLOCK_BYTES`, and no
    wider than the table. It follows the operands' shapes alone — one
    pool page's bytes (KV heads x page size x head dim x itemsize) and
    the table width — never the launch depth, so every launch over one
    table (single or bucketed) folds the same aligned column ranges
    (DESIGN.md §10)."""
    pages = 1
    while (
        2 * pages <= table_width
        and 2 * (2 * pages) * page_bytes <= DECODE_BLOCK_BYTES
    ):
        pages *= 2
    return pages


def live_columns(len_ref, slot, block_size: int):
    """Table columns through `slot`'s last live position — at least one,
    so an idle (length-0) slot still has a page to fold."""
    return jnp.maximum((len_ref[slot] + block_size - 1) // block_size, 1)


def live_blocks(slot, start_ref, len_ref, block_size: int, pages: int,
                n_steps):
    """`(first, count)` of `slot`'s decode walk: the first P-column block
    it folds and how many blocks, through the last block holding a live
    position. The walk starts at the block holding the slot's first live
    column (`start_ref`, aligned down to a block boundary); a slot with
    no live position (length 0, idle) still folds one block, so every
    slot of a launch has a block to prefetch. `count` is capped at the
    launch's grid steps (a bucketed launch's bound)."""
    last = (live_columns(len_ref, slot, block_size) - 1) // pages
    first = jnp.minimum(start_ref[slot] // pages, last)
    return first, jnp.minimum(last - first + 1, n_steps)


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


def double_buffered_block_walk(
    slot,         # grid index 0
    step,         # grid index 1: the walk's block, counted from `first`
    n_slots,      # grid extent 0
    n_steps,      # grid extent 1: blocks a slot may walk in this launch
    bt_ref,       # [B, max_blocks] int32 block table (scalar prefetch)
    start_ref,    # [B] int32 first live column per slot
    len_ref,      # [B] int32 valid kv count per slot
    block_size: int,
    kp_hbm,       # [n_blocks, KV, bs, hd] K pool — ANY/HBM ref
    vp_hbm,       # V pool
    k_buf,        # [2, P, KV, bs, hd] VMEM landing buffers
    v_buf,
    sem,          # DMA semaphores [2 buffers, 2 pools]
    buf_ref,      # [1] int32 SMEM: the buffer the next live block lands in
):
    """Run one grid step of the decode walk over blocks of P table
    columns. Returns `(live, cur, first)`: whether this step folds a
    block, the buffer holding it, and the slot's first block (so the
    step's block is `first + step`).

    Only live pages move. The launch's first block is started at grid
    step (0, 0); every live step starts the next live block — the
    slot's next one, or the next slot's first when this is the slot's
    last — into the other buffer before it waits on its own, so the
    DMAs of block k+1 overlap the fold of block k. A block's copies stop
    at the slot's last live column; the block's later page slots keep
    what an earlier block left there (zeros at first), which the fold
    masks. A step past the slot's last live block starts, waits and
    folds nothing. Every started copy is waited exactly once, by the
    live step that folds it; the copies of one block share their
    buffer's semaphores."""
    pages = k_buf.shape[1]

    def n_live(s, blk):
        """Pages of slot `s`'s block `blk` up to its last live column."""
        return jnp.clip(
            live_columns(len_ref, s, block_size) - blk * pages, 1, pages
        )

    def page_copies(s, blk, b, p):
        """The K and V copies of page `p` of slot `s`'s block `blk` into
        buffer `b` — recreated identically to start and to wait."""
        page = bt_ref[s, blk * pages + p]
        return (
            pltpu.make_async_copy(
                kp_hbm.at[pl.ds(page, 1)], k_buf.at[b, pl.ds(p, 1)],
                sem.at[b, 0],
            ),
            pltpu.make_async_copy(
                vp_hbm.at[pl.ds(page, 1)], v_buf.at[b, pl.ds(p, 1)],
                sem.at[b, 1],
            ),
        )

    def start_block(s, blk, b):
        def body(p, carry):
            for c in page_copies(s, blk, b, p):
                c.start()
            return carry
        jax.lax.fori_loop(0, n_live(s, blk), body, 0)

    def wait_block(s, blk, b):
        def body(p, carry):
            for c in page_copies(s, blk, b, p):
                c.wait()
            return carry
        jax.lax.fori_loop(0, n_live(s, blk), body, 0)

    first, count = live_blocks(
        slot, start_ref, len_ref, block_size, pages, n_steps
    )

    @pl.when((slot == 0) & (step == 0))
    def _():
        # page slots past a slot's live columns are never copied into, and
        # a masked score weights their values by exactly 0: zeros keep
        # that product finite whatever the VMEM held before the launch
        v_buf[...] = jnp.zeros_like(v_buf)
        buf_ref[0] = 0
        start_block(0, first, 0)

    cur = buf_ref[0]
    live = step < count

    @pl.when(live & (step + 1 < count))
    def _():
        start_block(slot, first + step + 1, 1 - cur)

    @pl.when(live & (step + 1 == count) & (slot + 1 < n_slots))
    def _():
        nxt_first, _ = live_blocks(
            slot + 1, start_ref, len_ref, block_size, pages, n_steps
        )
        start_block(slot + 1, nxt_first, 1 - cur)

    @pl.when(live)
    def _():
        buf_ref[0] = 1 - cur
        wait_block(slot, first + step, cur)

    return live, cur, first


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
    fold_heads(
        q_ref, lambda h: load_kv_page(k_buf, v_buf, cur, h, scale_rows),
        ok, m_s, l_s, acc_s,
    )


def fold_kv_block(q_ref, k_buf, v_buf, cur, ok, m_s, l_s, acc_s,
                  ks_ref=None, vs_ref=None, col=None):
    """`fold_kv_page` for a block: fold buffer `cur`'s P pages
    ([2, P, KV, bs, hd] buffers) as one [R, P * bs] score block per KV
    head, with one online-softmax update per block. `ok` is
    broadcastable to [R, P * bs]; on quantized pools `col` is the
    block's first table column and each page folds with its own scale
    row."""
    scale_rows = None
    if ks_ref is not None:
        scale_rows = [
            (ks_ref[0, pl.ds(col + p, 1), :], vs_ref[0, pl.ds(col + p, 1), :])
            for p in range(k_buf.shape[1])
        ]
    fold_heads(
        q_ref, lambda h: load_kv_block(k_buf, v_buf, cur, h, scale_rows),
        ok, m_s, l_s, acc_s,
    )


def fold_heads(q_ref, load_head, ok, m_s, l_s, acc_s):
    """The fold itself: for each KV head, `load_head(h)` gives its f32
    keys and values [N, hd] (one page, or a block of pages), scored
    against the head's query rows [R, hd] under mask `ok` and folded
    into the carried (m, l, acc) state."""
    n_kv, hd = q_ref.shape[1], q_ref.shape[3]
    for h in range(n_kv):
        qf = q_ref[0, h].astype(jnp.float32) * (hd ** -0.5)     # [R, hd]
        kj, vj = load_head(h)
        scores = jax.lax.dot_general(
            qf, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                       # [R, N]
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
