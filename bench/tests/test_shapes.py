"""The operation and byte counts the rooflines and MFU divide by,
checked against sums written out by hand. The stacks here are given
layer by layer through a test architecture (`bench/arch/` reads
`model_type`), so each count is the sum the hand-written one says."""

import sys
import types

import pytest

import smoke
from bench import model, shapes
from bench.arch import Layer, moe_flops

D, FF, HD, BS = 1536, 8960, 128, 16
#: a layer of 12 query heads over 2 KV heads, head size 128, d = 1536,
#: SwiGLU MLP of 8960, full attention
DENSE = Layer(heads=12, kv_heads=2, qk_dim=HD, v_dim=HD, window=0,
              proj_flops_per_token=2 * (D * 12 * HD + 2 * D * 2 * HD
                                        + 12 * HD * D),
              ffn_flops_per_token=2 * 3 * D * FF)
VOCAB = 151936


@pytest.fixture(autouse=True)
def by_hand_arch(monkeypatch):
    """`model_type: "by_hand"`: the layers and head the config lists."""
    mod = types.ModuleType("bench.arch.by_hand")
    mod.layers = lambda hf: hf["layers"]
    mod.head_flops = lambda hf: hf["head_flops"]
    monkeypatch.setitem(sys.modules, "bench.arch.by_hand", mod)


def stack(*layers, head=2 * D * VOCAB):
    return {"model_type": "by_hand", "layers": list(layers),
            "head_flops": head}


HF = stack(*[DENSE] * 28)


def test_linear_flops_per_token_counts_every_projection():
    per_layer = D * 12 * HD + 2 * D * 2 * HD + 12 * HD * D + 3 * D * FF
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


#: a windowed layer: 4 query heads over 2 KV heads of size 8, window 40
W = 40
WINDOWED = Layer(heads=4, kv_heads=2, qk_dim=8, v_dim=8, window=W,
                 proj_flops_per_token=1000, ffn_flops_per_token=2000)
WPAGE = BS * 2 * (8 + 8) * 2         # K and V of one 16-token page
WROWS = 4 * (8 + 8) * 2              # query row and output row


@pytest.mark.parametrize("e, first_page, last_page", [
    (30, 0, 1),      # below the window: positions 0..29
    (40, 0, 2),      # the window exactly: 0..39
    (57, 1, 3),      # above: positions 17..56
    (70, 1, 4),      # above: positions 30..69
])
def test_windowed_decode_walks_the_pages_of_its_window(e, first_page,
                                                       last_page):
    pages = last_page - first_page + 1
    hf = stack(WINDOWED)
    assert shapes.decode_kernel_bytes(hf, [e], BS) == pages * WPAGE + WROWS
    keys = min(e, W)
    assert shapes.decode_model_flops(hf, [e]) == (
        1000 + 2000 + 2 * 4 * 16 * keys + 2 * D * VOCAB)


@pytest.mark.parametrize("start, end, keys, pages", [
    (0, 30, sum(range(1, 31)), 2),                  # below: 1..30 keys
    (0, 40, sum(range(1, 41)), 3),                  # up to the window
    (32, 48, sum(range(33, 41)) + 8 * W, 3),        # across it: from 0
    (64, 80, 16 * W, 4),                            # above: from 25
])
def test_windowed_prefill_reads_the_window(start, end, keys, pages):
    hf = stack(WINDOWED)
    flops, nbytes = shapes.prefill_kernel_work(hf, start, end, BS)
    assert flops == 2 * 4 * (8 + 8) * keys
    assert nbytes == pages * WPAGE + (end - start) * WROWS


def test_mixed_stack_counts_each_layer_its_own_keys():
    hf = stack(DENSE, WINDOWED, DENSE, WINDOWED)
    e = 100
    dense = -(-e // BS) * BS * 2 * 2 * HD * 2 + 12 * HD * 2 * 2
    windowed = 4 * WPAGE + WROWS                    # positions 60..99
    assert shapes.decode_kernel_bytes(hf, [e], BS) == 2 * dense + 2 * windowed
    assert shapes.attention_flops(hf, e) == 2 * (4 * 12 * HD * e
                                                 + 2 * 4 * 16 * e)
    assert shapes.kv_bytes_per_token(hf) == 2 * (2 * 2 * HD * 2
                                                 + 2 * 2 * 8 * 2)


def test_expert_layer_counts_top_k_routed_shared_and_router():
    d, eff, n_exp, k, shared = 64, 32, 8, 2, 48
    got = moe_flops(d, eff, n_exp, k, shared_ff=shared)
    routed = k * 2 * 3 * d * eff          # 24576
    shared_flops = 2 * 3 * d * shared     # 18432
    router = 2 * d * n_exp                # 1024
    assert got == routed + shared_flops + router == 44032
    assert moe_flops(d, eff, n_exp, k) == routed + router
    expert = DENSE._replace(ffn_flops_per_token=got)
    hf = stack(DENSE, expert)
    assert shapes.linear_flops_per_token(hf) == (
        2 * DENSE.proj_flops_per_token + DENSE.ffn_flops_per_token + got)


@pytest.mark.parametrize("name", smoke.CONFIGS)
def test_every_configuration_counts_work_in_each_layer(name):
    hf = model.load_config(name)
    layers = model.layers(hf)
    assert len(layers) == hf["num_hidden_layers"]
    assert all(isinstance(l, Layer) for l in layers)
    assert shapes.linear_flops_per_token(hf) > 0 < shapes.head_flops(hf)
    for e in (2, 17, 4096):
        assert shapes.decode_kernel_bytes(hf, [e], BS) > 0
        assert shapes.decode_model_flops(hf, [e]) > 0
        assert min(shapes.prefill_kernel_work(hf, 0, e, BS)) > 0
