"""Operations and bytes of the served model's work, from its shapes.

These are what the algorithm needs, counted from the configuration and
the lengths the program was given; no number here comes from the
compiler's cost analysis. FLOPs count a multiply-add as two. The work
of each layer comes from the configuration's architecture module
(`model.layers`, one `bench.arch.Layer` per layer), so a stack that
mixes full and windowed attention, or dense and expert layers, is
counted layer by layer.

A windowed layer (window w) attends, for a query at position p, to the
keys at positions (p - w, p], the mask the kernels apply
(`kv_pos > q_pos - window`): at effective length e, min(e, w) keys on
the pages that cover positions [max(0, e - w), e).
"""

from __future__ import annotations

import collections
from typing import Iterable, Tuple

from . import model


def _stack(hf: dict):
    """(layer, how many of it) for each distinct layer of the stack."""
    return collections.Counter(model.layers(hf)).items()


def _first(layer, q: int) -> int:
    """The first key position a query at position q reads."""
    return max(0, q - layer.window + 1) if layer.window else 0


def _pages(lo: int, hi: int, block_size: int) -> int:
    """Pages that cover positions [lo, hi)."""
    return (hi - 1) // block_size - lo // block_size + 1 if hi > lo else 0


def _key_sum(layer, start: int, end: int) -> int:
    """Keys the queries at positions [start, end) read together."""
    def tri(n):
        return n * (n + 1) // 2

    w = layer.window
    if not w or end <= w:
        return tri(end) - tri(start)
    m = max(start, w)
    return tri(m) - tri(start) + (end - m) * w


def _attn_flops(layer, keys: int) -> int:
    """Scores and weighted sum over `keys` keys, every head."""
    return 2 * layer.heads * (layer.qk_dim + layer.v_dim) * keys


def _row_bytes(layer, itemsize: int) -> int:
    """One query row and one output row."""
    return layer.heads * (layer.qk_dim + layer.v_dim) * itemsize


def _kv_bytes(layer, itemsize: int) -> int:
    return layer.kv_heads * (layer.qk_dim + layer.v_dim) * itemsize


def linear_flops_per_token(hf: dict) -> float:
    """Projections and MLP (or experts) of every layer, for one token."""
    return float(sum(n * (l.proj_flops_per_token + l.ffn_flops_per_token)
                     for l, n in _stack(hf)))


def head_flops(hf: dict) -> float:
    return float(model.head_flops(hf))


def attention_flops(hf: dict, keys: int) -> float:
    """Scores and weighted sum for one query row over `keys` keys, in
    every layer and head."""
    return float(sum(n * _attn_flops(l, keys) for l, n in _stack(hf)))


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """Keys and values of one token in every layer."""
    return sum(n * _kv_bytes(l, itemsize) for l, n in _stack(hf))


def decode_kernel_bytes(hf: dict, eff_lengths: Iterable[int],
                        block_size: int, itemsize: int = 2) -> float:
    """Bytes the paged decode walk needs in one step, all layers: the
    live pages of every active slot (length + 1 >= 2; a windowed
    layer's trailing window), plus its query row and output row."""
    stack = _stack(hf)
    total = 0.0
    for e in eff_lengths:
        if e >= 2:
            total += sum(
                n * (_pages(_first(l, e - 1), e, block_size) * block_size
                     * _kv_bytes(l, itemsize) + _row_bytes(l, itemsize))
                for l, n in stack)
    return total


def prefill_kernel_work(hf: dict, start: int, end: int, block_size: int,
                        itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) the paged prefill kernel needs for the query rows
    [start, end) of one sequence, all layers: causal attention over
    every earlier position (in a windowed layer, over the window); the
    pages the rows read, once, and the query and output rows read and
    written once."""
    rows = end - start
    flops, nbytes = 0, 0
    for l, n in _stack(hf):
        flops += n * _attn_flops(l, _key_sum(l, start, end))
        pages = _pages(_first(l, start), end, block_size)
        nbytes += n * (pages * block_size * _kv_bytes(l, itemsize)
                       + rows * _row_bytes(l, itemsize))
    return float(flops), nbytes


def _attention_flops_rows(hf: dict, start: int, end: int) -> float:
    return float(sum(n * _attn_flops(l, _key_sum(l, start, end))
                     for l, n in _stack(hf)))


def prefill_model_flops(hf: dict, start: int, end: int) -> float:
    """Model FLOPs of one prefill launch: every row through the layers,
    attention over its context, and the head for the one row whose
    logits are kept."""
    return ((end - start) * linear_flops_per_token(hf)
            + _attention_flops_rows(hf, start, end) + head_flops(hf))


def decode_model_flops(hf: dict, eff_lengths: Iterable[int]) -> float:
    """Model FLOPs of one decode step: one token per active slot, its
    attention over length + 1 keys (a windowed layer's window at most),
    and the head."""
    lin, head = linear_flops_per_token(hf), head_flops(hf)
    total = 0.0
    for e in eff_lengths:
        if e >= 2:
            total += lin + _attention_flops_rows(hf, e - 1, e) + head
    return total
