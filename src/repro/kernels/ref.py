"""Pure-jnp oracles for the Pallas kernels (bit-plane GEMV/GEMM + paged
decode attention).

The bit-plane format is the TPU adaptation of the paper's bit-serial PIM
storage (DESIGN.md §2): an n-bit signed weight matrix is stored as
`n_digits` planes of g-bit digits, 8/g digits packed per byte along the
contraction (K) dimension:

    W_q [K, M]  (int, two's complement, n_bits)
    U = W_q + 2^(n-1)                      unsigned offset form
    digit_j(U) = (U >> (g*j)) & (2^g - 1)  j = 0..n_digits-1
    planes[j, kq, m] byte = sum_r digit_j(U[kq*(8/g)+r, m]) << (g*r)

so `planes` has shape [n_digits, K*g//8, M] uint8 and the matmul is

    x @ W_q = sum_j 2^(g*j) * (x_r @ digits_j) - 2^(n-1) * sum_k x_k

g=1 is the paper's bit-serial Booth radix-2 analogue; g=2 is the
IMAGine-slice4 / Booth radix-4 analogue (half the passes, same bytes).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def check_dims(k: int, n_bits: int, group: int) -> int:
    if group not in (1, 2, 4):
        raise ValueError(f"group must be 1, 2 or 4, got {group}")
    if not 2 <= n_bits <= 8:
        raise ValueError(f"n_bits must be in [2, 8], got {n_bits}")
    digits_per_byte = 8 // group
    if k % digits_per_byte:
        raise ValueError(f"K={k} not a multiple of {digits_per_byte}")
    return digits_per_byte


def n_digits(n_bits: int, group: int) -> int:
    return -(-n_bits // group)


def quantize_ref(w: jnp.ndarray, n_bits: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-output-channel quantization of W [K, M].

    Returns (w_q int32 [K, M], scale f32 [M]) with w_q in
    [-2^(n-1), 2^(n-1)-1].
    """
    qmax = float(2 ** (n_bits - 1) - 1)
    absmax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0).astype(jnp.float32)
    w_q = jnp.clip(jnp.round(w / scale), -(qmax + 1), qmax).astype(jnp.int32)
    return w_q, scale


def pack_ref(w_q: jnp.ndarray, n_bits: int, group: int = 1) -> jnp.ndarray:
    """Pack signed w_q [K, M] into digit planes [n_digits, K*g//8, M] u8."""
    k, m = w_q.shape
    dpb = check_dims(k, n_bits, group)
    nd = n_digits(n_bits, group)
    width = nd * group
    u = (w_q + 2 ** (n_bits - 1)).astype(jnp.uint32)  # 0 .. 2^n - 1
    digit_mask = (1 << group) - 1
    planes = []
    for j in range(nd):
        digits = (u >> (group * j)) & digit_mask          # [K, M]
        digits = digits.reshape(k // dpb, dpb, m)
        shifts = (group * jnp.arange(dpb, dtype=jnp.uint32))[None, :, None]
        packed = jnp.sum(digits << shifts, axis=1).astype(jnp.uint8)
        planes.append(packed)
    return jnp.stack(planes, axis=0)


def unpack_ref(planes: jnp.ndarray, n_bits: int, group: int = 1) -> jnp.ndarray:
    """Inverse of pack_ref: planes -> signed w_q [K, M] int32."""
    nd, k8, m = planes.shape
    dpb = 8 // group
    digit_mask = (1 << group) - 1
    u = jnp.zeros((k8 * dpb, m), dtype=jnp.uint32)
    for j in range(nd):
        for r in range(dpb):
            digit = (planes[j].astype(jnp.uint32) >> (group * r)) & digit_mask
            u = u.at[r::dpb].add(digit << (group * j))
    return u.astype(jnp.int32) - 2 ** (n_bits - 1)


def prepare_x_ref(x: jnp.ndarray, group: int = 1) -> jnp.ndarray:
    """x [B, K] -> x_r [8/g, B, K*g//8] with x_r[r, :, q] = x[:, q*(8/g)+r]."""
    b, k = x.shape
    dpb = 8 // group
    return x.reshape(b, k // dpb, dpb).transpose(2, 0, 1)


def bitplane_matmul_ref(
    x: jnp.ndarray,
    planes: jnp.ndarray,
    scale: jnp.ndarray,
    n_bits: int,
    group: int = 1,
) -> jnp.ndarray:
    """Oracle: y = x @ dequant(planes) — computed the straightforward way
    (unpack to int, matmul in f32)."""
    w_q = unpack_ref(planes, n_bits, group)
    y = jnp.dot(x.astype(jnp.float32), w_q.astype(jnp.float32))
    return (y * scale[None, :]).astype(x.dtype)


def bitplane_matmul_planewise_ref(
    x: jnp.ndarray,
    planes: jnp.ndarray,
    scale: jnp.ndarray,
    n_bits: int,
    group: int = 1,
) -> jnp.ndarray:
    """Second oracle following the kernel's exact contraction order
    (digit-plane matmuls + offset correction) — used to bound the
    float-accumulation discrepancy independently of the Pallas runtime."""
    nd, k8, m = planes.shape
    dpb = 8 // group
    digit_mask = (1 << group) - 1
    x_r = prepare_x_ref(x, group).astype(jnp.float32)
    acc = jnp.zeros((x.shape[0], m), dtype=jnp.float32)
    for j in range(nd):
        for r in range(dpb):
            digits = ((planes[j] >> (group * r)) & digit_mask).astype(jnp.float32)
            acc += float(2 ** (group * j)) * jnp.dot(x_r[r], digits)
    off = float(2 ** (n_bits - 1))
    acc = acc - off * jnp.sum(x.astype(jnp.float32), axis=1, keepdims=True)
    return (acc * scale[None, :]).astype(x.dtype)


def dequantize_ref(planes, scale, n_bits: int, group: int = 1) -> jnp.ndarray:
    return unpack_ref(planes, n_bits, group).astype(jnp.float32) * scale[None, :]


# ---------------------------------------------------------------------------
# paged attention (DESIGN.md §8-§10)
#
# These two oracles are the parity anchors for the native scalar-prefetch
# Pallas kernels (paged_attention.py / paged_prefill.py): the kernels fold
# every block-table page with exactly this masked math, so interpret mode
# must match to fp32 tolerance for ALL rows — including don't-care outputs
# (length-0 slots, padded suffix rows), which both paths intentionally
# compute the same way (`acc / max(l, eps)` over fully-masked scores).
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _gather_kv_dense(pages, block_table, scales=None):
    """Dense-gather one pool's pages per slot, dequantizing int8 codes
    through `paged_common.dequantize_pages` when the per-page scales ride
    along (DESIGN.md §16) — the oracle-side twin of the kernels'
    in-fold dequant."""
    from .paged_common import dequantize_pages, pages_to_tokens

    gathered = pages[block_table]                          # [B, mb, KV, bs, hd]
    if scales is not None:
        gathered = dequantize_pages(gathered, scales[block_table])
    return pages_to_tokens(gathered)                       # [B, S, KV, hd]


def paged_attention_ref(
    q: jnp.ndarray,            # [B, H, hd] — one query token per slot
    k_pages: jnp.ndarray,      # [n_blocks, KV, block_size, hd]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_blocks] int32 page ids per slot
    lengths: jnp.ndarray,      # [B] int32 valid KV count per slot
    window: jnp.ndarray,       # scalar int32; kv_pos > q_pos - window
    k_scales: jnp.ndarray | None = None,  # [n_blocks, KV] f32 per-page
    v_scales: jnp.ndarray | None = None,  # scales (int8 pools only)
) -> jnp.ndarray:
    """Oracle: gather every slot's pages dense, masked GQA softmax.

    Logical kv position of page j, row r is `j*block_size + r`; the query
    sits at `lengths-1`. Matches the kernel's `acc / max(l, eps)` epilogue
    so empty slots (length 0) produce finite garbage, not NaNs. With
    `k_scales`/`v_scales` the gathered int8 codes dequantize before the
    fold — the tolerance-parity anchor of the quantized kernel path.
    """
    b, h, hd = q.shape
    _, kv, bs, _ = k_pages.shape
    mb = block_table.shape[1]
    g = h // kv
    k = _gather_kv_dense(k_pages, block_table, k_scales)   # [B, S, KV, hd]
    v = _gather_kv_dense(v_pages, block_table, v_scales)
    kv_pos = jnp.arange(mb * bs, dtype=jnp.int32)
    q_pos = (lengths - 1)[:, None]
    ok = (kv_pos[None, :] < lengths[:, None]) & (kv_pos[None, :] > q_pos - window)
    scores = jnp.einsum(
        "bkgh,bskh->bkgs",
        q.reshape(b, kv, g, hd).astype(jnp.float32),
        k.astype(jnp.float32),
    ) * (hd ** -0.5)
    scores = jnp.where(ok[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, hd)


def paged_prefill_ref(
    q: jnp.ndarray,            # [B, T, H, hd] — suffix queries (T padded)
    k_pages: jnp.ndarray,      # [n_blocks, KV, block_size, hd]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_blocks] int32 page ids per slot
    start: jnp.ndarray,        # [B] int32 — position of the first suffix token
    total: jnp.ndarray,        # [B] int32 — full valid length (prefix + suffix)
    window: jnp.ndarray,       # scalar int32; kv_pos > q_pos - window
    k_scales: jnp.ndarray | None = None,  # [n_blocks, KV] f32 per-page
    v_scales: jnp.ndarray | None = None,  # scales (int8 pools only)
) -> jnp.ndarray:
    """Oracle for the paged-prefill kernel (DESIGN.md §9): suffix query
    row t sits at logical position `start + t` and attends, through the
    block table, to every cached-prefix AND fresh-suffix position up to
    itself — the offset causal mask `kv_pos <= start + t` — clipped to
    `kv_pos < total` (suffix padding rows hold garbage KV) and the
    sliding window. The suffix KV must already be scattered into the
    pools. Padded query rows (start + t >= total) produce don't-care
    outputs; same `acc / max(l, eps)` epilogue as the decode oracle.
    With `k_scales`/`v_scales` the gathered int8 codes dequantize before
    the fold (DESIGN.md §16).
    """
    b, t, h, hd = q.shape
    _, kv, bs, _ = k_pages.shape
    mb = block_table.shape[1]
    g = h // kv
    k = _gather_kv_dense(k_pages, block_table, k_scales)   # [B, S, KV, hd]
    v = _gather_kv_dense(v_pages, block_table, v_scales)
    kv_pos = jnp.arange(mb * bs, dtype=jnp.int32)[None, None, :]
    q_pos = (start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :])[..., None]
    ok = (
        (kv_pos <= q_pos)
        & (kv_pos < total[:, None, None])
        & (kv_pos > q_pos - window)
    )                                                       # [B, T, S]
    scores = jnp.einsum(
        "btkgh,bskh->bkgts",
        q.reshape(b, t, kv, g, hd).astype(jnp.float32),
        k.astype(jnp.float32),
    ) * (hd ** -0.5)
    scores = jnp.where(ok[:, None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgts,bskh->bkgth", p, v.astype(jnp.float32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, hd)
