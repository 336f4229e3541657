"""Native paged-attention kernels: interpret-mode parity vs the ref.py
oracles across the full ragged/window/offset/COW matrix, plus the strict
impl-dispatch rules (ISSUE 3 / DESIGN.md §10).

The kernels fold block-table pages with the oracle's exact masked math,
so parity must hold for every valid row, and for prefill's don't-care
rows too (padded suffix rows past `total`). Decode's walk stops at each
slot's last live block, so a length-0 row folds one fully masked block
where the oracle folds the whole table: those rows are don't-care and
only have to come out finite.

Every parity case additionally pins the length-bucketed dispatch
(DESIGN.md §11) bit-identical to the single launch on valid rows — the
parity helpers run both, so the whole matrix covers bucketing for free
(property-based coverage of the packing itself: tests/test_bucketing.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.paged_attention import (
    paged_attention,
    paged_decode_attention,
    paged_decode_attention_bucketed,
)
from repro.kernels.paged_common import quantize_pages
from repro.kernels.paged_prefill import (
    paged_prefill,
    paged_prefill_attention,
    paged_prefill_attention_bucketed,
)

TOL = dict(rtol=2e-5, atol=2e-5)

#: pinned int8 tolerance vs the FP oracle (DESIGN.md §16): per-page
#: symmetric absmax/127 quantization of unit-normal pages lands within
#: 5e-2 end-to-end; the kernel vs the QUANTIZED oracle stays at TOL —
#: quantization is lossy, the kernel's fold of the codes is not
INT8_TOL = dict(rtol=5e-2, atol=5e-2)


def _pools(rng, nb, bs, kv, hd, dtype=jnp.float32):
    kp = jnp.asarray(rng.normal(size=(nb, kv, bs, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(nb, kv, bs, hd)), dtype)
    return kp, vp


def _assert_decode_rows(got, want, lengths, tol=TOL):
    """Decode parity on every row with length >= 1; length-0 rows are
    don't-care and only have to be finite."""
    got = np.asarray(got)
    valid = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[valid], np.asarray(want)[valid], **tol)
    assert np.isfinite(got).all()


def _assert_decode_parity(q, kp, vp, bt, lengths, window, block_start=None):
    a = ref.paged_attention_ref(q, kp, vp, bt, lengths, window)
    b = paged_decode_attention(
        q, kp, vp, bt, lengths, window, block_start=block_start,
        interpret=True,
    )
    _assert_decode_rows(b, a, lengths)
    _assert_bucketed_decode_matches_single(
        q, kp, vp, bt, lengths, window, block_start
    )


def _assert_prefill_parity(q, kp, vp, bt, start, total, window):
    a = ref.paged_prefill_ref(q, kp, vp, bt, start, total, window)
    b = paged_prefill_attention(
        q, kp, vp, bt, start, total, window, interpret=True
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
    _assert_bucketed_prefill_matches_single(q, kp, vp, bt, start, total,
                                            window)


def _assert_bucketed_decode_matches_single(q, kp, vp, bt, lengths, window,
                                           block_start=None):
    """DESIGN.md §11: the bucketed dispatch is bit-identical to the
    single launch on every slot with length >= 1 (both fold the same
    aligned blocks); length-0 rows are don't-care either way. With
    `block_start` the slots bucket by live trailing blocks, as a
    windowed layer's do (DESIGN.md §12)."""
    lens = np.asarray(lengths)
    bs, mb = kp.shape[2], bt.shape[1]
    needs = None
    if block_start is not None:
        needs = -(-np.maximum(lens, 1) // bs) - np.asarray(block_start)
    plan, perm = ops.make_bucket_plan(lens, bs, mb, needs=needs)
    if plan is None:  # degenerate plan: single launch IS the dispatch
        return
    single = paged_decode_attention(
        q, kp, vp, bt, lengths, window, block_start=block_start,
        interpret=True,
    )
    bucketed = paged_decode_attention_bucketed(
        q, kp, vp, bt, lengths, window, plan, perm,
        block_start=block_start, interpret=True,
    )
    valid = lens > 0
    np.testing.assert_array_equal(
        np.asarray(single)[valid], np.asarray(bucketed)[valid]
    )


def _assert_bucketed_prefill_matches_single(q, kp, vp, bt, start, total,
                                            window):
    """Bucketed prefill (slots grouped by ceil(total / bs)) must match
    the single launch bit-for-bit on every valid query row
    (start + t < total); padded rows are don't-care either way."""
    tot = np.asarray(total)
    plan, perm = ops.make_bucket_plan(tot, kp.shape[2], bt.shape[1])
    if plan is None:
        return
    single = np.asarray(paged_prefill_attention(
        q, kp, vp, bt, start, total, window, interpret=True
    ))
    bucketed = np.asarray(paged_prefill_attention_bucketed(
        q, kp, vp, bt, start, total, window, plan, perm, interpret=True
    ))
    st_np, t = np.asarray(start), q.shape[1]
    for i in range(q.shape[0]):
        tv = max(0, min(t, int(tot[i] - st_np[i])))
        np.testing.assert_array_equal(single[i, :tv], bucketed[i, :tv])


# ---------------------------------------------------------------------------
# decode matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [64, 3, 1])
@pytest.mark.parametrize(
    "lengths", [[5, 12, 1], [0, 12, 4], [0, 0, 1], [8, 9, 3]]
)
def test_decode_ragged_lengths_and_windows(rng, window, lengths):
    """Ragged lengths including idle (0) and single-token (1) slots, full
    attention and sliding windows shorter than the longest length. The
    3-column tables walk blocks of P = 2 pages (8 tokens), so the table
    width is not a multiple of P, and the lengths end mid-page (3, 5),
    exactly at a block edge (8), one token into the next block (9) and
    one page past a block edge (12)."""
    B, H, KV, hd, bs, nb, mb = 3, 4, 2, 8, 4, 10, 3
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    _assert_decode_parity(
        q, kp, vp, bt, jnp.asarray(lengths, jnp.int32),
        jnp.asarray(window, jnp.int32),
    )


def test_decode_idle_and_parked_slots(rng):
    """The serving batch's non-decoding rows, beside slots that walk
    several blocks: an idle slot (length 1 over its own table) and a
    parked one (length 1 over an all-scratch table row), as the
    scheduler passes them (`eff` = 1). Each walks one block."""
    B, H, KV, hd, bs, nb, mb = 4, 4, 2, 8, 4, 24, 5
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = np.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), np.int32
    )
    bt[2] = 0                                    # parked: scratch row
    _assert_decode_parity(
        q, kp, vp, jnp.asarray(bt), jnp.asarray([19, 1, 1, 9], jnp.int32),
        jnp.asarray(mb * bs, jnp.int32),
    )


@pytest.mark.parametrize("window", [3, 9, 64])
def test_decode_windowed_walk_start(rng, window):
    """A sliding-window layer's walk starts at the block holding its
    first live column (`block_start`, DESIGN.md §12), aligned down to a
    block boundary: with P = 4 pages, starts 5 and 3 are unaligned, 0
    is not. The bucketed launches (by live trailing pages, one grid step
    more than their bound for the unaligned start) stay bit-identical
    to the single one."""
    B, H, KV, hd, bs, nb, mb = 3, 4, 2, 8, 4, 20, 6
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    lengths = np.asarray([24, 21, 10])
    start = np.maximum(lengths - window, 0) // bs
    _assert_decode_parity(
        q, kp, vp, bt, jnp.asarray(lengths, jnp.int32),
        jnp.asarray(window, jnp.int32), jnp.asarray(start, jnp.int32),
    )


def test_decode_pages_per_step_rule():
    """P comes from one pool page's bytes and the table width alone —
    never from a launch's depth, so every launch over one table folds
    the same aligned blocks. At the cells' shapes: qwen2-1.5b's 2 x 16 x
    128 bf16 pages over 288-column tables walk 128 pages a step,
    qwen2.5-14b's 8 x 16 x 128 pages over 736 columns 32."""
    import inspect

    from repro.kernels.paged_common import decode_pages_per_step

    assert list(inspect.signature(decode_pages_per_step).parameters) == [
        "page_bytes", "table_width",
    ]
    assert decode_pages_per_step(2 * 16 * 128 * 2, 288) == 128
    assert decode_pages_per_step(8 * 16 * 128 * 2, 736) == 32
    # the same page bytes in another head layout: the same P
    assert decode_pages_per_step(4 * 16 * 64 * 2, 288) == 128
    # int8 pages are half as wide, so twice as many fit a step
    assert decode_pages_per_step(8 * 16 * 128 * 1, 736) == 64
    # never wider than the table: the largest power of two within it
    assert [decode_pages_per_step(256, w) for w in (1, 2, 3, 5, 8)] == [
        1, 2, 2, 4, 8,
    ]


def test_decode_single_block_table(rng):
    """max_blocks == 1: the degenerate walk (warm-up step is also the
    last step of each slot)."""
    B, H, KV, hd, bs, nb = 2, 4, 2, 8, 4, 5
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray([[3], [1]], jnp.int32)
    _assert_decode_parity(
        q, kp, vp, bt, jnp.asarray([4, 2], jnp.int32),
        jnp.asarray(16, jnp.int32),
    )


def test_decode_full_pool_table(rng):
    """Every non-scratch page of the pool appears in some slot's table —
    the table capacity equals the pool."""
    B, H, KV, hd, bs, nb = 2, 4, 2, 8, 4, 9
    mb = (nb - 1) // B  # 4 blocks per slot, 8 pages = whole usable pool
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb)).reshape(B, mb), jnp.int32
    )
    _assert_decode_parity(
        q, kp, vp, bt, jnp.asarray([mb * bs, mb * bs - 3], jnp.int32),
        jnp.asarray(mb * bs, jnp.int32),
    )


def test_decode_cow_fragmented_tables(rng):
    """COW-world tables: non-contiguous, non-monotonic page ids, pages
    shared between slots, and a page repeated within one slot's table."""
    B, H, KV, hd, bs, nb, mb = 3, 4, 2, 8, 4, 12, 4
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray(
        [[7, 2, 11, 3],    # non-contiguous, non-monotonic
         [2, 7, 2, 5],     # shares pages 2 and 7 with slot 0, repeats 2
         [10, 1, 4, 9]],
        jnp.int32,
    )
    _assert_decode_parity(
        q, kp, vp, bt, jnp.asarray([13, 16, 9], jnp.int32),
        jnp.asarray(6, jnp.int32),
    )


#: (query heads, head dim, page size): a small GQA geometry, and
#: qwen2-1.5b's 12 query heads over 2 KV heads at head dim 128 on 16-token
#: pages — six query rows per KV head in the head-major fold
GEOMETRIES = [(4, 8, 4), (12, 128, 16)]


@pytest.mark.parametrize("H,hd,bs", GEOMETRIES)
def test_decode_bf16_pool(rng, H, hd, bs):
    """bf16 page pools (the serving default) load through the DMA scratch
    and fold in f32, exactly like the oracle."""
    B, KV, nb, mb = 2, 2, 6, 2
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd, jnp.bfloat16)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    _assert_decode_parity(
        q, kp, vp, bt, jnp.asarray([bs + 2, 2 * bs], jnp.int32),
        jnp.asarray(2 * bs, jnp.int32),
    )


# ---------------------------------------------------------------------------
# prefill matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [64, 5])
@pytest.mark.parametrize(
    "start,total",
    [
        ([0, 0, 0], [6, 11, 4]),     # prefix miss: full causal prefill
        ([4, 8, 4], [11, 9, 12]),    # prefix hits: offset causal mask
        ([8, 0, 11], [9, 1, 12]),    # full-hit 1-token recompute + tiny
    ],
)
def test_prefill_offsets_ragged_windows(rng, window, start, total):
    """Prefix offsets (including the full-hit single-token recompute),
    ragged totals with padded query rows, and sliding windows."""
    B, T, H, KV, hd, bs, nb, mb = 3, 8, 4, 2, 8, 4, 10, 3
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    _assert_prefill_parity(
        q, kp, vp, bt, jnp.asarray(start, jnp.int32),
        jnp.asarray(total, jnp.int32), jnp.asarray(window, jnp.int32),
    )


@pytest.mark.parametrize("H,hd,bs", GEOMETRIES)
def test_prefill_single_block_table(rng, H, hd, bs):
    B, T, KV, nb = 2, 4, 2, 5
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray([[2], [4]], jnp.int32)
    _assert_prefill_parity(
        q, kp, vp, bt, jnp.asarray([0, 0], jnp.int32),
        jnp.asarray([4, 3], jnp.int32), jnp.asarray(16, jnp.int32),
    )


def test_prefill_cow_fragmented_tables(rng):
    """Shared-prefix tables: the same prefix pages mapped into several
    slots (refcounted sharing) with distinct suffix pages, plus an
    in-slot repeated page."""
    B, T, H, KV, hd, bs, nb, mb = 3, 8, 4, 2, 8, 4, 12, 4
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray(
        [[5, 9, 1, 3],
         [5, 9, 2, 7],     # shares the 2-page prefix {5, 9} with slot 0
         [5, 5, 10, 4]],   # repeated page
        jnp.int32,
    )
    _assert_prefill_parity(
        q, kp, vp, bt, jnp.asarray([8, 8, 4], jnp.int32),
        jnp.asarray([14, 16, 10], jnp.int32), jnp.asarray(7, jnp.int32),
    )


# ---------------------------------------------------------------------------
# quantized int8 pool matrix (DESIGN.md §16)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [64, 3])
@pytest.mark.parametrize("lengths", [[5, 12, 1], [0, 12, 4], [8, 9, 3]])
def test_decode_int8_parity(rng, window, lengths):
    """int8 pools through the same kernel body: tight (TOL) vs the
    quantized oracle — both fold the identical dequantized codes — and
    within the pinned INT8_TOL vs the fp oracle on the same content."""
    B, H, KV, hd, bs, nb, mb = 3, 4, 2, 8, 4, 10, 3
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    kq, ks = quantize_pages(kp)
    vq, vs = quantize_pages(vp)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    lens = jnp.asarray(lengths, jnp.int32)
    win = jnp.asarray(window, jnp.int32)
    out = np.asarray(paged_decode_attention(
        q, kq, vq, bt, lens, win, k_scales=ks, v_scales=vs, interpret=True
    ))
    _assert_decode_rows(
        out,
        ref.paged_attention_ref(
            q, kq, vq, bt, lens, win, k_scales=ks, v_scales=vs
        ),
        lens,
    )
    _assert_decode_rows(
        out, ref.paged_attention_ref(q, kp, vp, bt, lens, win), lens,
        INT8_TOL,
    )


@pytest.mark.parametrize("H,hd,bs", GEOMETRIES)
@pytest.mark.parametrize("start,total", [([0, 0, 0], [6, 11, 4]),
                                         ([4, 8, 4], [11, 9, 12])])
def test_prefill_int8_parity(rng, start, total, H, hd, bs):
    B, T, KV, nb, mb = 3, 8, 2, 10, 3
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    kq, ks = quantize_pages(kp)
    vq, vs = quantize_pages(vp)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    st = jnp.asarray(start, jnp.int32)
    tot = jnp.asarray(total, jnp.int32)
    win = jnp.asarray(64, jnp.int32)
    out = np.asarray(paged_prefill_attention(
        q, kq, vq, bt, st, tot, win, k_scales=ks, v_scales=vs,
        interpret=True,
    ))
    np.testing.assert_allclose(
        out,
        np.asarray(ref.paged_prefill_ref(
            q, kq, vq, bt, st, tot, win, k_scales=ks, v_scales=vs
        )),
        **TOL,
    )
    np.testing.assert_allclose(
        out,
        np.asarray(ref.paged_prefill_ref(q, kp, vp, bt, st, tot, win)),
        **INT8_TOL,
    )


def test_decode_int8_bucketed_matches_single(rng):
    """The bucketed dispatch streams the scale rows with their pages —
    valid rows must stay bit-identical to the single quantized launch."""
    B, H, KV, hd, bs, nb, mb = 4, 4, 2, 8, 4, 18, 4
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    kq, ks = quantize_pages(kp)
    vq, vs = quantize_pages(vp)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    lens = np.asarray([13, 2, 0, 7])
    lens_j = jnp.asarray(lens, jnp.int32)
    win = jnp.asarray(mb * bs, jnp.int32)
    plan, perm = ops.make_bucket_plan(lens, bs, mb)
    assert plan is not None
    single = np.asarray(paged_decode_attention(
        q, kq, vq, bt, lens_j, win, k_scales=ks, v_scales=vs,
        interpret=True,
    ))
    bucketed = np.asarray(paged_decode_attention_bucketed(
        q, kq, vq, bt, lens_j, win, plan, perm, k_scales=ks, v_scales=vs,
        interpret=True,
    ))
    valid = lens > 0
    np.testing.assert_array_equal(single[valid], bucketed[valid])


def test_quantized_operand_pairing_is_strict(rng):
    """int8 pools without scales (codes folded as values) and float
    pools with scales (a scale array silently ignored) are both caller
    bugs — every dispatcher rejects the mismatch up front."""
    B, T, H, KV, hd, bs, nb, mb = 2, 4, 4, 2, 8, 4, 6, 2
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    qp = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    kq, ks = quantize_pages(kp)
    vq, vs = quantize_pages(vp)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([5, 7], jnp.int32)
    st = jnp.asarray([0, 4], jnp.int32)
    tot = jnp.asarray([4, 7], jnp.int32)
    win = jnp.asarray(8, jnp.int32)
    with pytest.raises(ValueError, match="require k_scales"):
        paged_attention(q, kq, vq, bt, lens, win, impl="ref")
    with pytest.raises(ValueError, match="require k_scales"):
        paged_prefill(qp, kq, vq, bt, st, tot, win, impl="ref")
    with pytest.raises(ValueError, match="must not pass"):
        paged_attention(
            q, kp, vp, bt, lens, win, impl="ref",
            k_scales=ks, v_scales=vs,
        )
    with pytest.raises(ValueError, match="must not pass"):
        paged_prefill(
            qp, kp, vp, bt, st, tot, win, impl="ref",
            k_scales=ks, v_scales=vs,
        )


# ---------------------------------------------------------------------------
# impl dispatch matrix (strict explicit values, silent auto)
# ---------------------------------------------------------------------------

@pytest.fixture
def decode_args(rng):
    B, H, KV, hd, bs, nb, mb = 2, 4, 2, 8, 4, 6, 2
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    return (q, kp, vp, bt, jnp.asarray([5, 7], jnp.int32),
            jnp.asarray(8, jnp.int32))


@pytest.fixture
def prefill_args(decode_args):
    q, kp, vp, bt, lengths, win = decode_args
    qp = jnp.tile(q[:, None], (1, 4, 1, 1))
    return (qp, kp, vp, bt, jnp.asarray([0, 4], jnp.int32),
            jnp.asarray([4, 7], jnp.int32), win)


@pytest.mark.skipif(
    jax.default_backend() == "tpu", reason="strictness is the off-TPU rule"
)
def test_explicit_pallas_is_strict_off_tpu(decode_args, prefill_args):
    with pytest.raises(RuntimeError, match="native TPU kernel"):
        paged_attention(*decode_args, impl="pallas")
    with pytest.raises(RuntimeError, match="native TPU kernel"):
        paged_prefill(*prefill_args, impl="pallas")
    # the shared resolve_impl rule also covers the bit-plane ops
    with pytest.raises(RuntimeError, match="native TPU kernel"):
        ops.bitplane_matmul(
            jnp.ones((2, 8)), jnp.zeros((1, 1, 4), jnp.uint8),
            jnp.ones((4,)), n_bits=8, impl="pallas",
        )


def test_unknown_impl_raises(decode_args, prefill_args):
    with pytest.raises(ValueError, match="unknown impl"):
        paged_attention(*decode_args, impl="cuda")
    with pytest.raises(ValueError, match="unknown impl"):
        paged_prefill(*prefill_args, impl="")


def test_bucketed_plan_threads_through_dispatch(rng):
    """`paged_attention`/`paged_prefill(plan=...)` route the kernel paths
    through the bucketed dispatch (matching the oracle on valid rows)
    while `ref` mode ignores the plan entirely (the oracle has no walk
    to bound)."""
    B, T, H, KV, hd, bs, nb, mb = 3, 4, 4, 2, 8, 4, 14, 4
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    qp = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    lengths = jnp.asarray([5, 2, 9], jnp.int32)
    win = jnp.asarray(mb * bs, jnp.int32)
    plan, perm = ops.make_bucket_plan(np.asarray(lengths), bs, mb)
    assert plan is not None
    got = paged_attention(
        q, kp, vp, bt, lengths, win, impl="pallas_interpret",
        plan=plan, perm=perm,
    )
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.paged_attention_ref(q, kp, vp, bt, lengths, win)),
        **TOL,
    )
    # ref ignores even a nonsense plan — no shape error, oracle output
    np.testing.assert_array_equal(
        np.asarray(paged_attention(
            q, kp, vp, bt, lengths, win, impl="ref",
            plan=((99, 99),), perm=None,
        )),
        np.asarray(ref.paged_attention_ref(q, kp, vp, bt, lengths, win)),
    )
    start = jnp.asarray([0, 2, 4], jnp.int32)
    total = jnp.asarray([4, 3, 8], jnp.int32)
    plan2, perm2 = ops.make_bucket_plan(np.asarray(total), bs, mb)
    assert plan2 is not None
    got2 = np.asarray(paged_prefill(
        qp, kp, vp, bt, start, total, win, impl="pallas_interpret",
        plan=plan2, perm=perm2,
    ))
    want2 = np.asarray(
        ref.paged_prefill_ref(qp, kp, vp, bt, start, total, win)
    )
    st_np, tot_np = np.asarray(start), np.asarray(total)
    for i in range(B):
        tv = max(0, min(T, int(tot_np[i] - st_np[i])))
        np.testing.assert_allclose(got2[i, :tv], want2[i, :tv], **TOL)


@pytest.mark.parametrize("mb", [4, 7])
def test_depth_bounds_the_walk(rng, mb):
    """An explicit `depth` must reproduce the full walk whenever it
    covers every valid page — the tail columns it cuts are exact no-ops
    (this is the exactness the bucketed dispatch rests on). At width 7
    the walk's blocks are 4 pages and a depth of 2 spans half a block."""
    B, H, KV, hd, bs, nb = 2, 4, 2, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    lengths = jnp.asarray([7, 5], jnp.int32)       # 2 pages each
    win = jnp.asarray(mb * bs, jnp.int32)
    full = paged_decode_attention(q, kp, vp, bt, lengths, win, interpret=True)
    shallow = paged_decode_attention(
        q, kp, vp, bt, lengths, win, depth=2, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(full), np.asarray(shallow))


def test_auto_and_interpret_dispatch(decode_args, prefill_args):
    """`auto` silently picks the oracle off-TPU (and the native kernel on
    TPU); `pallas_interpret` always runs the kernel body, matching the
    oracle to fp32 tolerance; `ref` is the oracle by definition."""
    expect_d = ref.paged_attention_ref(*decode_args)
    expect_p = ref.paged_prefill_ref(*prefill_args)
    if jax.default_backend() != "tpu":
        np.testing.assert_array_equal(
            np.asarray(paged_attention(*decode_args, impl="auto")),
            np.asarray(expect_d),
        )
        np.testing.assert_array_equal(
            np.asarray(paged_prefill(*prefill_args, impl="auto")),
            np.asarray(expect_p),
        )
    np.testing.assert_allclose(
        np.asarray(paged_attention(*decode_args, impl="pallas_interpret")),
        np.asarray(expect_d), **TOL,
    )
    np.testing.assert_allclose(
        np.asarray(paged_prefill(*prefill_args, impl="pallas_interpret")),
        np.asarray(expect_p), **TOL,
    )
