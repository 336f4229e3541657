"""Error-feedback int8 gradient compression for cross-pod all-reduce.

The `pod` axis is the slow boundary (DCN-ish); compressing the DP gradient
reduction over it trades 4x fewer bytes for quantization noise, with an
error-feedback residual so the bias vanishes over steps (1-bit-Adam /
EF-SGD lineage). Applied ONLY to the pod axis — intra-pod reductions stay
full precision.

compress -> all_reduce(int8-sum in int32) -> decompress, with the residual
carried in f32 alongside the optimizer state.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Any


def init_residual(grads: Params) -> Params:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    absmax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(
    grads: Params, residual: Params, axis: str
) -> Tuple[Params, Params]:
    """All-reduce `grads` over `axis` with int8 error-feedback compression.

    Must run inside shard_map with `axis` unreduced. Returns (mean grads,
    new residual).
    """
    n = jax.lax.axis_size(axis)

    def one(g, r):
        gf = g.astype(jnp.float32) + r
        # shared scale: pmax of the per-shard absmax (scalar — negligible
        # traffic) so the summed int8 payloads decompress exactly
        absmax = lax.pmax(jnp.max(jnp.abs(gf)), axis)
        scale = jnp.maximum(absmax / 127.0, 1e-12)
        q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
        new_r = gf - q.astype(jnp.float32) * scale  # local error feedback
        q_sum = lax.psum(q.astype(jnp.int32), axis)  # int8 payload, int32 sum
        approx = q_sum.astype(jnp.float32) * scale
        return (approx / n).astype(g.dtype), new_r

    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_r = jax.tree_util.tree_leaves(residual)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    new_g = tdef.unflatten([o[0] for o in out])
    new_r = tdef.unflatten([o[1] for o in out])
    return new_g, new_r


def compression_ratio(dtype=jnp.bfloat16) -> float:
    return jnp.dtype(dtype).itemsize / jnp.dtype(jnp.int8).itemsize
