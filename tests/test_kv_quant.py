"""Quantized KV-page round-trip properties and cache-level edge cases
(DESIGN.md §16).

Property-based (hypothesis, skipped when not installed): the
quantize/dequantize round trip is bounded by half a code step per
element. Deterministic edges always run: all-zero pages (the scale=0
guard), full-negative-range int8 extremes (-128 survives a requantize
without overflow), ragged final pages (the scale comes from valid
tokens only), and COW-then-append on a quantized page — a content
stamp over the original codes AND scales proves shared quantized pages
are never written in place.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.kernels.paged_common import (
    INT8_QMAX,
    dequantize_pages,
    quantize_pages,
    requantize_page_update,
)
from repro.serve import PagedKVCache

ARCH = "qwen2-1.5b"


@pytest.fixture(scope="module")
def model_cfg():
    return dataclasses.replace(get_config(ARCH, smoke=True), dtype="float32")


# ---------------------------------------------------------------------------
# round-trip properties
# ---------------------------------------------------------------------------

@given(
    seed=st.integers(0, 2**31 - 1),
    scale_exp=st.integers(-12, 12),
)
@settings(max_examples=20, deadline=None)
def test_roundtrip_half_step_bound(seed, scale_exp):
    """|dequant(quant(x)) - x| <= scale/2 elementwise, across magnitudes
    from subnormal-ish to large — the rounding step is the only loss."""
    rng = np.random.default_rng(seed)
    pages = rng.normal(size=(3, 2, 4, 8)).astype(np.float32) * (
        2.0 ** scale_exp
    )
    codes, scales = quantize_pages(jnp.asarray(pages))
    codes_np = np.asarray(codes)
    assert codes_np.dtype == np.int8
    assert codes_np.min() >= -128 and codes_np.max() <= 127
    deq = np.asarray(dequantize_pages(codes, scales))
    half_step = np.asarray(scales)[:, :, None, None] / 2.0
    assert np.all(np.abs(deq - pages) <= half_step * (1 + 1e-5))


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_requantize_identity_is_stable(seed):
    """requantize_page_update with an identity update reproduces the
    same codes/scales up to one rounding step — append drift is bounded,
    not cumulative blow-up."""
    rng = np.random.default_rng(seed)
    pages = rng.normal(size=(2, 2, 4, 8)).astype(np.float32)
    codes, scales = quantize_pages(jnp.asarray(pages))
    codes2, scales2 = requantize_page_update(codes, scales, lambda f: f)
    np.testing.assert_allclose(
        np.asarray(dequantize_pages(codes2, scales2)),
        np.asarray(dequantize_pages(codes, scales)),
        rtol=0, atol=float(np.asarray(scales).max()),
    )


# ---------------------------------------------------------------------------
# deterministic edges
# ---------------------------------------------------------------------------

def test_all_zero_pages_scale_guard():
    """All-zero planes take scale 1.0 (never 0): dequant is exactly
    zero and no division blows up anywhere in the round trip."""
    codes, scales = quantize_pages(jnp.zeros((2, 2, 4, 8), jnp.float32))
    np.testing.assert_array_equal(np.asarray(scales), 1.0)
    np.testing.assert_array_equal(np.asarray(codes), 0)
    np.testing.assert_array_equal(
        np.asarray(dequantize_pages(codes, scales)), 0.0
    )
    # an all-zero UPDATE of a live page drops the scale back to the guard
    live, live_s = quantize_pages(
        jnp.ones((1, 2, 4, 8), jnp.float32) * 3.0
    )
    z_codes, z_scales = requantize_page_update(
        live, live_s, lambda f: jnp.zeros_like(f)
    )
    np.testing.assert_array_equal(np.asarray(z_scales), 1.0)
    np.testing.assert_array_equal(np.asarray(z_codes), 0)


def test_negative_extreme_maps_to_minus_127():
    """Symmetric quantization: -absmax lands on code -127 (the -128
    slot is reachable only through crafted codes, not quantize)."""
    pages = np.zeros((1, 1, 4, 4), np.float32)
    pages[0, 0, 0, 0] = -6.0
    pages[0, 0, 1, 1] = 3.0
    codes, scales = quantize_pages(jnp.asarray(pages))
    assert float(np.asarray(scales)[0, 0]) == pytest.approx(6.0 / INT8_QMAX)
    assert int(np.asarray(codes)[0, 0, 0, 0]) == -127
    deq = np.asarray(dequantize_pages(codes, scales))
    assert deq[0, 0, 0, 0] == pytest.approx(-6.0)
    assert deq[0, 0, 1, 1] == pytest.approx(3.0, rel=1e-2)


def test_full_negative_range_codes_survive_requantize():
    """Crafted -128 codes (full int8 range) requantize without overflow:
    the new absmax covers 128*scale, so the value is preserved exactly
    at code -127 under the widened scale."""
    codes = jnp.full((1, 2, 4, 8), -128, jnp.int8)
    scales = jnp.full((1, 2), 0.5, jnp.float32)
    want = np.asarray(dequantize_pages(codes, scales))  # all -64.0
    codes2, scales2 = requantize_page_update(codes, scales, lambda f: f)
    c2 = np.asarray(codes2)
    assert c2.min() >= -128 and c2.max() <= 127
    np.testing.assert_allclose(
        np.asarray(dequantize_pages(codes2, scales2)), want, rtol=1e-6
    )


def test_ragged_final_page_scale_from_valid_tokens(model_cfg):
    """A ragged suffix write (n_tokens not a page multiple) derives the
    final page's scale from the valid tokens alone — the pad tail is
    zero, so one big garbage value can never flatten the page's codes."""
    cfg = model_cfg
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4,
                      kv_dtype="int8")
    assert pc.quantized
    L = cfg.n_layers
    kvh, hd = pc.k_pages.shape[2], pc.k_pages.shape[4]
    n_tokens = 7                                  # 2 pages, ragged tail
    rng = np.random.default_rng(3)
    k = rng.normal(size=(L, n_tokens, kvh, hd)).astype(np.float32) * 0.1
    v = rng.normal(size=(L, n_tokens, kvh, hd)).astype(np.float32) * 0.1
    pc.alloc_slot(0, n_tokens)
    pc.write_suffix(0, jnp.asarray(k), jnp.asarray(v), 0, n_tokens)
    pc.check_invariants()
    pool = pc.pools[0]
    tail_page = pool._owned[0][1]
    lg = pool.layers[0]
    bs = pc.block_size
    # the tail page holds tokens [4, 7) + one zero pad row
    tail_rows = k[lg, bs:n_tokens, :, :]
    want_scale = np.abs(tail_rows).max(axis=(0, 2)) / INT8_QMAX
    got_scale = np.asarray(pc.k_scales)[lg, tail_page]
    np.testing.assert_allclose(got_scale, want_scale, rtol=1e-5)
    # and the stored rows round-trip within half a code step
    deq = np.asarray(dequantize_pages(
        pc.k_pages[lg, tail_page], pc.k_scales[lg, tail_page]
    )).swapaxes(0, 1)                             # token rows [bs, KV, hd]
    np.testing.assert_allclose(
        deq[: n_tokens - bs], tail_rows, rtol=0,
        atol=float(want_scale.max()) / 2 * 1.001,
    )
    np.testing.assert_array_equal(deq[n_tokens - bs:], 0.0)


def test_cow_then_append_content_stamp(model_cfg):
    """Appending onto a SHARED quantized page goes through COW: the
    original page's codes and scale rows are byte-identical before and
    after (the content stamp), the writing slot lands on a fresh page,
    and the appended tokens round-trip from the new page."""
    cfg = model_cfg
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4,
                      kv_dtype="int8")
    L = cfg.n_layers
    kvh, hd = pc.k_pages.shape[2], pc.k_pages.shape[4]
    rng = np.random.default_rng(4)
    k = rng.normal(size=(L, 4, kvh, hd)).astype(np.float32)
    v = rng.normal(size=(L, 4, kvh, hd)).astype(np.float32)
    pc.alloc_slot(0, 3)                 # partially filled single page
    pc.write_suffix(0, jnp.asarray(k), jnp.asarray(v), 0, 3)
    page = pc.pools[0]._owned[0][0]
    pc.retain(page)                     # external (prefix-index) share
    pc.check_invariants(external_refs={page: 1})
    assert pc.is_shared(page)
    stamp_codes = np.asarray(pc.k_pages)[:, page].copy()
    stamp_scales = np.asarray(pc.k_scales)[:, page].copy()

    tok_k = jnp.asarray(rng.normal(size=(L, 1, kvh, hd)), jnp.float32)
    tok_v = jnp.asarray(rng.normal(size=(L, 1, kvh, hd)), jnp.float32)
    pc.write_suffix(0, tok_k, tok_v, 3, 1)   # append onto the shared page
    assert pc.cow_events >= 1
    new_page = pc.pools[0]._owned[0][0]
    assert new_page != page
    pc.check_invariants(external_refs={page: 1})
    # the stamp: the shared page was never written in place
    np.testing.assert_array_equal(
        np.asarray(pc.k_pages)[:, page], stamp_codes
    )
    np.testing.assert_array_equal(
        np.asarray(pc.k_scales)[:, page], stamp_scales
    )
    # the COW'd page carries the old rows AND the appended token
    deq = np.asarray(dequantize_pages(
        pc.k_pages[0, new_page], pc.k_scales[0, new_page]
    )).swapaxes(0, 1)                             # token rows [bs, KV, hd]
    step = float(np.asarray(pc.k_scales)[0, new_page].max())
    np.testing.assert_allclose(
        deq[3], np.asarray(tok_k)[0, 0], rtol=0, atol=step * 1.001,
    )
    # old rows survive the requantize round trip within one extra step
    old_deq = dequantize_pages(
        jnp.asarray(stamp_codes[0]), jnp.asarray(stamp_scales[0])
    )
    np.testing.assert_allclose(
        deq[:3], np.asarray(old_deq).swapaxes(0, 1)[:3], rtol=0,
        atol=2 * step,
    )
