"""Native paged-attention kernels: interpret-mode parity vs the ref.py
oracles across the full ragged/window/offset/COW matrix, plus the strict
impl-dispatch rules (ISSUE 3 / DESIGN.md §10).

The kernels fold every block-table page with the oracle's exact masked
math, so parity must hold for ALL rows — including don't-care outputs
(length-0 idle slots, padded suffix rows past `total`).

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


def _assert_decode_parity(q, kp, vp, bt, lengths, window):
    a = ref.paged_attention_ref(q, kp, vp, bt, lengths, window)
    b = paged_decode_attention(q, kp, vp, bt, lengths, window, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
    _assert_bucketed_decode_matches_single(q, kp, vp, bt, lengths, window)


def _assert_prefill_parity(q, kp, vp, bt, start, total, window):
    a = ref.paged_prefill_ref(q, kp, vp, bt, start, total, window)
    b = paged_prefill_attention(
        q, kp, vp, bt, start, total, window, interpret=True
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
    _assert_bucketed_prefill_matches_single(q, kp, vp, bt, start, total,
                                            window)


def _assert_bucketed_decode_matches_single(q, kp, vp, bt, lengths, window):
    """DESIGN.md §11: the bucketed dispatch is bit-identical to the
    single launch on every slot with length >= 1 (the cut tail pages
    fold as exact no-ops); length-0 rows are don't-care either way."""
    lens = np.asarray(lengths)
    plan, perm = ops.make_bucket_plan(lens, kp.shape[2], bt.shape[1])
    if plan is None:  # degenerate plan: single launch IS the dispatch
        return
    single = paged_decode_attention(
        q, kp, vp, bt, lengths, window, interpret=True
    )
    bucketed = paged_decode_attention_bucketed(
        q, kp, vp, bt, lengths, window, plan, perm, interpret=True
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
@pytest.mark.parametrize("lengths", [[5, 12, 1], [0, 12, 4], [0, 0, 1]])
def test_decode_ragged_lengths_and_windows(rng, window, lengths):
    """Ragged lengths including idle (0) and single-token (1) slots, full
    attention and sliding windows shorter than the longest length."""
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
@pytest.mark.parametrize("lengths", [[5, 12, 1], [0, 12, 4]])
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
    np.testing.assert_allclose(
        out,
        np.asarray(ref.paged_attention_ref(
            q, kq, vq, bt, lens, win, k_scales=ks, v_scales=vs
        )),
        **TOL,
    )
    np.testing.assert_allclose(
        out,
        np.asarray(ref.paged_attention_ref(q, kp, vp, bt, lens, win)),
        **INT8_TOL,
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


def test_depth_bounds_the_walk(rng):
    """An explicit `depth` must reproduce the full walk whenever it
    covers every valid page — the tail columns it cuts are exact no-ops
    (this is the exactness the bucketed dispatch rests on)."""
    B, H, KV, hd, bs, nb, mb = 2, 4, 2, 8, 4, 12, 4
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    lengths = jnp.asarray([7, 5], jnp.int32)       # 2 pages each, mb = 4
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
