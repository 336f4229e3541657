"""Pallas TPU kernel: bit-plane GEMM (prefill/training-shape variant).

Same contraction as bitplane_gemv but with the token dimension tiled as
well: grid = (B / block_b, M / block_m, K8 / block_k8). Used when the
activation matrix is too tall to keep resident in VMEM (prefill at 32k
tokens, training microbatches).

The K grid axis is innermost ("arbitrary" semantics) so each (b, m)
output tile is accumulated to completion while resident in VMEM before
the next tile starts — the in-block reduction stays zero-copy and the
output is written to HBM exactly once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitplane_gemv import fit_block, unpack_digits


def _gemm_kernel(xr_ref, planes_ref, out_ref, *, n_bits: int, group: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dpb = 8 // group
    digit_mask = (1 << group) - 1
    nd = -(-n_bits // group)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for j in range(nd):
        plane = planes_ref[j].astype(jnp.int32)
        for r in range(dpb):
            digits = unpack_digits(plane, r, group, digit_mask, xr_ref.dtype)
            acc = acc + float(2 ** (group * j)) * jnp.dot(
                xr_ref[r], digits, preferred_element_type=jnp.float32
            )
    out_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "group", "block_b", "block_m", "block_k8", "interpret"),
)
def bitplane_gemm(
    x_r: jnp.ndarray,     # [8/g, B, K8]
    planes: jnp.ndarray,  # [n_digits, K8, M] uint8
    *,
    n_bits: int,
    group: int = 1,
    block_b: int = 128,
    block_m: int = 256,
    block_k8: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    dpb, b, k8 = x_r.shape
    nd, k8p, m = planes.shape
    assert k8p == k8
    block_b = min(block_b, b)
    if b % block_b:
        raise ValueError(f"B={b} not divisible by block_b={block_b}")
    block_m = fit_block(m, block_m)
    block_k8 = fit_block(k8, block_k8)
    grid = (b // block_b, m // block_m, k8 // block_k8)
    kernel = functools.partial(_gemm_kernel, n_bits=n_bits, group=group)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((dpb, block_b, block_k8), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((nd, block_k8, block_m), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        interpret=interpret,
    )(x_r, planes)
