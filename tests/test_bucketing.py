"""Length-bucketed paged dispatch (DESIGN.md §11): property-based
coverage of the slot→bucket packing and of the bucketed-vs-single-launch
bit-parity the dispatch layer promises.

`kernels.ops.make_bucket_plan` is pure host-side policy, so hypothesis
can hammer it with arbitrary ragged length vectors; the kernel-level
property runs the interpreter on tiny shapes (one compile per distinct
plan shape, bounded by the power-of-two rounding).
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops
from repro.kernels.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_bucketed,
)


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


# ---------------------------------------------------------------------------
# packing properties
# ---------------------------------------------------------------------------

@given(st.data())
@settings(max_examples=60)
def test_plan_and_permutation_round_trip(data):
    """For ANY ragged length vector: the plan is made of power-of-two
    (bound, count) pairs; the permutation lists every slot exactly once
    (padding entries point at the dummy row `n`); every slot lands in a
    bucket deep enough for its pages; and the plan never walks more
    table entries than the single launch (else it must degrade to
    `(None, None)`)."""
    bs = data.draw(st.sampled_from([1, 2, 4, 8]), label="block_size")
    mb = data.draw(st.integers(1, 24), label="table_width")
    lengths = data.draw(
        st.lists(st.integers(0, bs * mb), min_size=1, max_size=16),
        label="lengths",
    )
    n = len(lengths)
    plan, perm = ops.make_bucket_plan(lengths, bs, mb)
    # the decode walk copies each slot's live columns (one for an idle
    # slot) and a page per padding row: never more than the plan's extent
    pages = data.draw(st.sampled_from([1, 2, 4, 8]), label="pages")
    live = sum(max(-(-x // bs), 1) for x in lengths)
    walk = ops.decode_walk_pages(lengths, [0] * n, bs, pages, plan)
    assert live <= walk <= ops.plan_streamed_pages(plan, n, mb)
    if plan is None:
        assert perm is None
        assert walk == live
        assert ops.plan_streamed_pages(plan, n, mb) == n * mb
        return
    # structure: pow2 bounds (clipped to mb) and pow2 counts, ascending
    bounds = [b for b, _ in plan]
    assert bounds == sorted(set(bounds))
    for bound, count in plan:
        assert _is_pow2(bound) or bound == mb, (bound, mb)
        assert 1 <= bound <= mb
        assert _is_pow2(count)
    # the win is strict: a plan only exists when it streams fewer pages
    assert ops.plan_streamed_pages(plan, n, mb) < n * mb
    # permutation: one entry per (bound, count) row, real slots once each
    assert perm.shape == (sum(c for _, c in plan),)
    real = perm[perm < n]
    assert sorted(real.tolist()) == list(range(n))
    assert np.all(perm[perm >= n] == n)
    # coverage: each slot's bucket bound holds all its occupied pages
    off = 0
    for bound, count in plan:
        for slot in perm[off: off + count]:
            if slot < n:
                need = max(-(-lengths[slot] // bs), 1)
                assert min(need, mb) <= bound, (slot, lengths[slot], bound)
        off += count


def test_strategy_none_and_empty_are_single_launch():
    assert ops.make_bucket_plan([3, 9], 4, 8, strategy="none") == (None, None)
    assert ops.make_bucket_plan([], 4, 8) == (None, None)
    # uniform full occupancy degenerates: no pages to save
    assert ops.make_bucket_plan([32, 32], 4, 8) == (None, None)
    with pytest.raises(ValueError, match="bucket_strategy"):
        ops.make_bucket_plan([1], 4, 8, strategy="pow4")
    with pytest.raises(ValueError, match="bucket_strategy"):
        ops.resolve_bucket_strategy("")


def test_needs_override_buckets_by_live_pages():
    """DESIGN.md §12: `needs=` replaces the length-derived walk counts —
    a windowed layer at full length but 3 live trailing blocks plans a
    shallow walk where the length-only plan degenerates to the single
    full-depth launch."""
    bs, mb = 4, 32
    lens = [mb * bs, mb * bs]              # full occupancy
    assert ops.make_bucket_plan(lens, bs, mb) == (None, None)
    plan, perm = ops.make_bucket_plan(None, bs, mb, needs=[2, 3])
    assert plan == ((2, 1), (4, 1))        # pow2 bounds of 2 and 3
    assert perm.tolist() == [0, 1]
    assert ops.plan_streamed_pages(plan, 2, mb) == 6 < 2 * mb
    # needs are clamped to >= 1 (idle slots still walk one block)
    plan0, _ = ops.make_bucket_plan(None, bs, mb, needs=[0, 0])
    assert plan0 == ((1, 2),)


def test_is_bucket_plan_distinguishes_plan_from_plan_tuple():
    plan = ((4, 2), (8, 1))
    assert ops.is_bucket_plan(plan)
    assert not ops.is_bucket_plan((plan, None))      # per-group tuple
    assert not ops.is_bucket_plan((None, plan))
    assert not ops.is_bucket_plan(None)


def test_bucket_args_grouped_static_dynamic_split():
    """Per-group packing (DESIGN.md §12): one plan per needs array, jnp
    perms, all-None degrading to the single-launch pair, and the
    oracle/none-strategy short-circuits."""
    needs = [np.asarray([2, 3]), np.asarray([8, 8])]
    plans, perms = ops.bucket_args_grouped(
        "pow2", "pallas_interpret", needs, 8
    )
    assert plans == (((2, 1), (4, 1)), None)   # group 1 is uniform-full
    assert perms[0].tolist() == [0, 1] and perms[1] is None
    assert hash(plans) is not None         # static jit key
    # every group degenerate -> single launch everywhere
    assert ops.bucket_args_grouped(
        "pow2", "pallas_interpret", [np.asarray([8, 8])], 8
    ) == (None, None)
    assert ops.bucket_args_grouped("none", "pallas_interpret", needs, 8) \
        == (None, None)
    assert ops.bucket_args_grouped("pow2", "ref", needs, 8) == (None, None)


def test_recompile_set_is_bounded():
    """Every plan drawn from ANY length vector of <= n slots over a
    table of width mb uses (bound, count) pairs from the small pow2 grid
    — the recompile-set bound the serving layer relies on."""
    rng = np.random.default_rng(0)
    bs, mb, n = 4, 16, 8
    legal_bounds = {1, 2, 4, 8, 16}
    legal_counts = {1, 2, 4, 8}
    shapes = set()
    for _ in range(200):
        lens = rng.integers(0, bs * mb + 1, size=rng.integers(1, n + 1))
        plan, _ = ops.make_bucket_plan(lens, bs, mb)
        if plan is None:
            continue
        for bound, count in plan:
            assert bound in legal_bounds and count in legal_counts
            shapes.add((bound, count))
    assert shapes  # the sweep actually produced bucketed plans
    assert len(shapes) <= len(legal_bounds) * len(legal_counts)


# ---------------------------------------------------------------------------
# kernel-level bit-parity property
# ---------------------------------------------------------------------------

@given(st.data())
@settings(max_examples=15, deadline=None)
def test_bucketed_bit_identical_to_single_launch(data):
    """For arbitrary ragged lengths (and a drawn sliding window), the
    bucketed dispatch emits bit-identical outputs to the single launch
    on every slot with length >= 1 — the exactness argument (cut tail
    pages fold as exact no-ops) holds for real floats, not just on the
    curated matrix."""
    B, H, KV, hd, bs, nb, mb = 3, 2, 1, 4, 2, 10, 4
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31), label="seed"))
    lengths = np.asarray(
        data.draw(
            st.lists(st.integers(0, bs * mb), min_size=B, max_size=B),
            label="lengths",
        )
    )
    window = data.draw(st.sampled_from([1, 3, bs * mb]), label="window")
    plan, perm = ops.make_bucket_plan(lengths, bs, mb)
    if plan is None:
        return
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, nb, size=(B, mb)), jnp.int32)
    lens_j = jnp.asarray(lengths, jnp.int32)
    win = jnp.asarray(window, jnp.int32)
    single = paged_decode_attention(
        q, kp, vp, bt, lens_j, win, interpret=True
    )
    bucketed = paged_decode_attention_bucketed(
        q, kp, vp, bt, lens_j, win, plan, perm, interpret=True
    )
    valid = lengths > 0
    np.testing.assert_array_equal(
        np.asarray(single)[valid], np.asarray(bucketed)[valid]
    )
