"""Pallas TPU kernel: bit-plane GEMV (the paper's PIM MAC array, TPU-native).

y[B, M] = sum_j 2^(g*j) * (x_r[r] @ digit_plane[j, r]) over packed digit
planes resident in VMEM — one "pass per digit plane" in place of the
paper's bit-serial partial-product walk, with the K-split partial-sum
accumulation playing the role of the in-block FOLD reduction (eqn 2).

Tiling: grid = (M / block_m, K8 / block_k8); the output block is revisited
across the K grid dimension and accumulated in place (block index map
pins the K axis), so partial sums never leave VMEM — the zero-copy
in-block reduction of PiCaSO.

The B (token) dimension is not tiled here: decode GEMV has B <= a few
hundred rows, which fits VMEM alongside the operand tiles. Use
bitplane_gemm for prefill/training shapes.

VMEM budget per grid step (defaults bm=256, bk8=128, B<=128, bf16 x):
  x_r    8 * 128 * 128 * 2  =  256 KiB
  planes n_d * 128 * 256    <= 256 KiB (n_d <= 8)
  out    128 * 256 * 4      =  128 KiB
well under the ~16 MiB/core VMEM of v5e; MXU contraction dim = block_k8
= 128 lanes, aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def fit_block(dim: int, pref: int) -> int:
    """Block size along a dimension of length `dim`: `pref` when it
    divides, else the largest multiple of 128 below `pref` that divides,
    else the whole dimension. Real projection widths are often not
    multiples of the preferred tile (qwen2-1.5b has K8 = 192 and 1120),
    and a whole dimension is always a legal block."""
    if pref <= dim and dim % pref == 0:
        return pref
    for b in range(min(pref, dim) // 128 * 128, 0, -128):
        if dim % b == 0:
            return b
    return dim


def unpack_digits(plane, r, group: int, digit_mask: int, dtype):
    """Digit `r` of each packed byte, as `dtype`. `plane` is already
    int32: Mosaic shifts and converts 32-bit integers, but has no
    uint8 -> float cast."""
    digits = (plane >> (group * r)) & digit_mask
    return digits.astype(jnp.float32).astype(dtype)


def _gemv_kernel(xr_ref, planes_ref, out_ref, *, n_bits: int, group: int):
    """One (m, k) grid step: accumulate all digit planes of this K tile."""
    k_idx = pl.program_id(1)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dpb = 8 // group
    digit_mask = (1 << group) - 1
    nd = -(-n_bits // group)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for j in range(nd):
        plane = planes_ref[j].astype(jnp.int32)  # [bk8, bm] uint8 -> int32
        for r in range(dpb):
            digits = unpack_digits(plane, r, group, digit_mask, xr_ref.dtype)
            part = jnp.dot(
                xr_ref[r], digits, preferred_element_type=jnp.float32
            )
            acc = acc + float(2 ** (group * j)) * part
    out_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "group", "block_m", "block_k8", "interpret"),
)
def bitplane_gemv(
    x_r: jnp.ndarray,       # [8/g, B, K8]  pre-strided activations
    planes: jnp.ndarray,    # [n_digits, K8, M] uint8 digit planes
    *,
    n_bits: int,
    group: int = 1,
    block_m: int = 256,
    block_k8: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw plane contraction: returns f32 [B, M] = x @ (W_q + off).

    `block_m`/`block_k8` are preferred tiles, fitted to the shape by
    `fit_block`. The caller (ops.bitplane_matmul) applies the offset correction and the
    per-channel scale epilogue.
    """
    dpb, b, k8 = x_r.shape
    nd, k8p, m = planes.shape
    assert k8p == k8, (k8p, k8)
    assert dpb == 8 // group
    block_m = fit_block(m, block_m)
    block_k8 = fit_block(k8, block_k8)

    grid = (m // block_m, k8 // block_k8)
    kernel = functools.partial(_gemv_kernel, n_bits=n_bits, group=group)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((dpb, b, block_k8), lambda j, k: (0, 0, k)),
            pl.BlockSpec((nd, block_k8, block_m), lambda j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((b, block_m), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        interpret=interpret,
        name="bitplane_gemv",
    )(x_r, planes)
