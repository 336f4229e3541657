"""Operations and bytes of the served model's work, from its shapes.

These are what the algorithm needs, counted from the configuration and
the lengths the program was given; no number here comes from the
compiler's cost analysis. FLOPs count a multiply-add as two.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def dims(hf: dict) -> Tuple[int, int, int, int, int, int, int]:
    h, kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // h
    return (hf["num_hidden_layers"], hf["hidden_size"], h, kv, hd,
            hf["intermediate_size"], hf["vocab_size"])


def linear_flops_per_token(hf: dict) -> float:
    """Projections and MLP of every layer, for one token."""
    n, d, h, kv, hd, ff, _ = dims(hf)
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff
    return 2.0 * n * per_layer


def head_flops(hf: dict) -> float:
    _, d, _, _, _, _, v = dims(hf)
    return 2.0 * d * v


def attention_flops(hf: dict, keys: int) -> float:
    """Scores and weighted sum for one query row over `keys` keys, in
    every layer and head."""
    n, _, h, _, hd, _, _ = dims(hf)
    return 4.0 * n * h * hd * keys


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """Keys and values of one token in every layer."""
    n, _, _, kv, hd, _, _ = dims(hf)
    return 2 * n * kv * hd * itemsize


def decode_kernel_bytes(hf: dict, eff_lengths: Iterable[int],
                        block_size: int, itemsize: int = 2) -> float:
    """Bytes the paged decode walk needs in one step, all layers: the
    live pages of every active slot (length + 1 >= 2), plus its query
    row and output row."""
    n, _, h, _, hd, _, _ = dims(hf)
    page = block_size * kv_bytes_per_token(hf, itemsize) // n
    total = 0.0
    for e in eff_lengths:
        if e >= 2:
            total += n * (-(-e // block_size) * page + 2 * h * hd * itemsize)
    return total


def prefill_kernel_work(hf: dict, start: int, end: int, block_size: int,
                        itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) the paged prefill kernel needs for the query rows
    [start, end) of one sequence, all layers: causal attention over
    every earlier position; the pages up to `end` read once, the query
    and output rows read and written once."""
    n, _, h, _, hd, _, _ = dims(hf)
    rows = end - start
    keys = rows * start + rows * (rows + 1) // 2
    flops = attention_flops(hf, keys)
    page = block_size * kv_bytes_per_token(hf, itemsize) // n
    pages = -(-end // block_size)
    nbytes = n * (pages * page + 2 * rows * h * hd * itemsize)
    return flops, nbytes


def prefill_model_flops(hf: dict, start: int, end: int) -> float:
    """Model FLOPs of one prefill launch: every row through the layers,
    attention over its context, and the head for the one row whose
    logits are kept."""
    rows = end - start
    keys = rows * start + rows * (rows + 1) // 2
    return (rows * linear_flops_per_token(hf) + attention_flops(hf, keys)
            + head_flops(hf))


def decode_model_flops(hf: dict, eff_lengths: Iterable[int]) -> float:
    """Model FLOPs of one decode step: one token per active slot, its
    attention over length + 1 keys, and the head."""
    total = 0.0
    for e in eff_lengths:
        if e >= 2:
            total += (linear_flops_per_token(hf) + attention_flops(hf, e)
                      + head_flops(hf))
    return total
