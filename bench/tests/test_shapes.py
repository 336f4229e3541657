"""The operation and byte counts the rooflines and MFU divide by,
checked against sums written out by hand."""

import pytest

from bench import model, shapes

HF = model.load_config("qwen2-1.5b")


def test_linear_flops_per_token_counts_every_projection():
    d, ff, hd = 1536, 8960, 128
    per_layer = d * 12 * hd + 2 * d * 2 * hd + 12 * hd * d + 3 * d * ff
    assert shapes.linear_flops_per_token(HF) == 2 * 28 * per_layer
    # 1.31B non-embedding weights: two FLOPs per weight per token
    assert shapes.linear_flops_per_token(HF) == pytest.approx(2 * 1.31e9,
                                                              rel=0.01)


def test_decode_bytes_are_live_pages_and_query_rows():
    page = 16 * 2 * 2 * 128 * 2          # K and V of one layer's page
    row = 12 * 128 * 2
    # an idle slot (1) walks nothing; lengths 17 and 32 need 2 pages
    got = shapes.decode_kernel_bytes(HF, [1, 17, 32], 16)
    assert got == 28 * 2 * (2 * page + 2 * row)


def test_prefill_work_is_causal():
    flops, nbytes = shapes.prefill_kernel_work(HF, 512, 520, 16)
    keys = sum(512 + i + 1 for i in range(8))
    assert flops == 4 * 28 * 12 * 128 * keys
    pages = -(-520 // 16)
    assert nbytes == 28 * (pages * 16 * 2 * 2 * 128 * 2 + 2 * 8 * 12 * 128 * 2)
    assert shapes.prefill_model_flops(HF, 0, 4) == (
        4 * shapes.linear_flops_per_token(HF)
        + shapes.attention_flops(HF, 1 + 2 + 3 + 4) + shapes.head_flops(HF))
