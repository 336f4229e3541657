"""One module per architecture, `bench/arch/<model_type>.py`, found by
the published `model_type` key of a configuration file
(`bench/model.py`). Each module gives:

- `program_config(hf, name)`: the program's `ModelConfig`;
- `weight_shapes(hf)`: every leaf of the benchmark's layout and its
  shape;
- `init_kind(name)`: the draw a leaf gets in `model.make_weights`:
  `"norm"` (a gain, 1 + normal(0, 0.1)), `"qk"` (a query or key
  projection, at the scale `init_qk_score_std` sets) or `"plain"`
  (normal(0, initializer_range));
- `program_params(hf, w)`: the same buffers in the program's parameter
  tree;
- `smoke_config(hf, **over)`: a copy at small widths for tests on the
  CPU that keeps the layer pattern;
- `layers(hf)`: the work of each layer, one `Layer` per layer, which
  `bench/shapes.py` sums;
- `head_flops(hf)`: FLOPs of the head for one row.

A module builds its `Layer`s with `swiglu_flops` and `moe_flops`,
below.

The plain float32 reference of the architecture is a module of its own
under `bench/reference/`, named by the configuration's `reference` key.
"""

from __future__ import annotations

from typing import NamedTuple


class Layer(NamedTuple):
    """The work of one layer for one token. FLOPs count a multiply-add
    as two."""

    heads: int                  # query heads
    kv_heads: int
    qk_dim: int                 # head size of queries and keys
    v_dim: int                  # head size of values
    #: keys a query sees, itself included (the kernels' mask
    #: `kv_pos > q_pos - window`); 0 for full causal attention
    window: int
    proj_flops_per_token: int   # query, key, value and output projections
    #: a dense MLP, or the top-k routed experts, the shared experts and
    #: the router
    ffn_flops_per_token: int


def swiglu_flops(d: int, ff: int) -> int:
    """A SwiGLU MLP (gate, up and down projections) for one token."""
    return 2 * 3 * d * ff


def moe_flops(d: int, expert_ff: int, n_experts: int, top_k: int,
              shared_ff: int = 0) -> int:
    """An expert layer for one token: the top-k routed SwiGLU experts,
    the shared experts (one SwiGLU of their summed width) and the
    router's logits over every expert."""
    return (top_k * swiglu_flops(d, expert_ff)
            + (swiglu_flops(d, shared_ff) if shared_ff else 0)
            + 2 * d * n_experts)
