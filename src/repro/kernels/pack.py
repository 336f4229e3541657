"""Pallas TPU kernel: pack quantized weights into digit planes.

planes[j, q, m] = sum_r digit_j(U[q*dpb + r, m]) << (g*r)

Input arrives pre-strided as u_r [dpb, K8, M] (u_r[r, q, m] =
U[q*dpb + r, m], an XLA transpose done once at quantization time) so the
kernel is a pure VPU shift/mask/accumulate over aligned [K8, M] tiles —
no in-kernel reshapes. Packing runs once per weight matrix (at load or
after an optimizer step in quantized-serving pipelines), so this kernel
is bandwidth- not latency-critical.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitplane_gemv import fit_block


def _pack_kernel(ur_ref, out_ref, *, n_bits: int, group: int):
    dpb = 8 // group
    digit_mask = (1 << group) - 1
    nd = -(-n_bits // group)
    for j in range(nd):
        acc = jnp.zeros(out_ref.shape[1:], jnp.int32)
        for r in range(dpb):
            # 32-bit shifts: Mosaic's integer vector ops are 32-bit
            u = ur_ref[r].astype(jnp.int32)               # [bk8, bm]
            acc = acc | (((u >> (group * j)) & digit_mask) << (group * r))
        out_ref[j] = acc.astype(jnp.uint8)


@functools.partial(
    jax.jit, static_argnames=("n_bits", "group", "block_k8", "block_m", "interpret")
)
def pack_bitplanes(
    u_r: jnp.ndarray,  # [8/g, K8, M] uint8 — offset weights, pre-strided
    *,
    n_bits: int,
    group: int = 1,
    block_k8: int = 128,
    block_m: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    dpb, k8, m = u_r.shape
    assert dpb == 8 // group
    nd = -(-n_bits // group)
    block_k8 = fit_block(k8, block_k8)
    block_m = fit_block(m, block_m)
    grid = (k8 // block_k8, m // block_m)
    kernel = functools.partial(_pack_kernel, n_bits=n_bits, group=group)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((dpb, block_k8, block_m), lambda q, j: (0, q, j))],
        out_specs=pl.BlockSpec((nd, block_k8, block_m), lambda q, j: (0, q, j)),
        out_shape=jax.ShapeDtypeStruct((nd, k8, m), jnp.uint8),
        interpret=interpret,
    )(u_r)
