"""GQA attention with sliding windows, KV cache, and cross-attention.

Supports every attention flavour in the assigned pool:
  - GQA with arbitrary kv-head counts (MQA when n_kv=1 — granite-20b)
  - QKV biases (qwen2 family)
  - per-layer sliding windows (gemma3 5:1 local:global)
  - decode with a pre-allocated KV cache (one token, cache length S)
  - cross-attention over encoder outputs (whisper)

All projections go through quant.pim_linear so any weight can be
PIM-resident (bit-plane packed) — the paper's technique applied to the
dominant GEMV of decode.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.paged_attention import paged_attention
from ..kernels.paged_common import requantize_page_update
from ..kernels.paged_prefill import paged_prefill
from ..quant.bitplane import pim_linear
from .common import NEG_INF, Params, apply_rope, dense_init, split_keys


def _select_bucket_plan(call, bucket_plans, bucket_perms, plan_class):
    """Run the paged-attention `call(plan, perm)` under the layer's
    bucket-plan variant (DESIGN.md §12).

    `bucket_plans`/`bucket_perms` are per-layer-group tuples (the static
    and dynamic halves of `kernels.ops.bucket_args_grouped`); the scanned
    layer body selects its group's variant with `plan_class` (a traced
    per-layer scalar) through `lax.switch` — every variant traces ONCE in
    the shared scan body, so a mixed global/window stack compiles one
    kernel dispatch per distinct plan, not per layer. A single-element
    tuple (or None) skips the switch entirely."""
    if bucket_plans is None:
        return call(None, None)
    if len(bucket_plans) == 1:
        return call(bucket_plans[0], bucket_perms[0])
    branches = [
        (lambda p=p, pm=pm: call(p, pm))
        for p, pm in zip(bucket_plans, bucket_perms)
    ]
    idx = jnp.asarray(0 if plan_class is None else plan_class, jnp.int32)
    return jax.lax.switch(idx, branches)


def init_attention(
    key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
    qkv_bias: bool = False,
) -> Params:
    ks = split_keys(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
    return p


def _project_qkv(params, x, n_heads, n_kv, hd):
    b, t, _ = x.shape
    q = pim_linear(x, params["wq"])
    k = pim_linear(x, params["wk"])
    v = pim_linear(x, params["wv"])
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    from ..dist.sharding import shard
    return (
        shard(q.reshape(b, t, n_heads, hd), "batch", "seq", "heads", "head_dim"),
        shard(k.reshape(b, t, n_kv, hd), "batch", "seq", "kv_heads", "head_dim"),
        shard(v.reshape(b, t, n_kv, hd), "batch", "seq", "kv_heads", "head_dim"),
    )


def _gqa_core(
    q: jnp.ndarray,          # [B, T, H, hd]
    k: jnp.ndarray,          # [B, S, KV, hd]
    v: jnp.ndarray,          # [B, S, KV, hd]
    mask: Optional[jnp.ndarray],  # [B or 1, T, S] additive f32, or None
) -> jnp.ndarray:
    b, t, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, t, kv, g, hd)
    scale = hd ** -0.5
    scores = jnp.einsum(
        "btkgh,bskh->bkgts", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if mask is not None:
        scores = scores + mask[:, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v.astype(jnp.float32))
    return out.reshape(b, t, h * hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention for full-sequence paths
# ---------------------------------------------------------------------------

#: default blocks: [B, H, QB, KB] f32 scores stay VMEM-sized
Q_BLOCK = 512
KV_BLOCK = 1024
#: above this many score elements per (batch,head) the dense path would
#: materialize a [T, S] buffer; switch to the chunked path
DENSE_SCORE_LIMIT = 1 << 21


def _pick_block(n: int, target: int) -> int:
    bl = min(target, n)
    while n % bl:
        bl //= 2
    return max(bl, 1)


def _chunked_gqa(
    q: jnp.ndarray,            # [B, T, H, hd]
    k: jnp.ndarray,            # [B, S, KV, hd]
    v: jnp.ndarray,
    q_pos: jnp.ndarray,        # [T]
    kv_pos: jnp.ndarray,       # [S]
    window: Optional[jnp.ndarray],
    causal: bool,
) -> jnp.ndarray:
    """Online-softmax attention, O(QB*KB) score memory (the TPU-idiomatic
    flash form; on real TPUs the inner body maps onto a Pallas kernel —
    here it must stay pure JAX so the CPU dry-run lowers it)."""
    b, t, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qb = _pick_block(t, Q_BLOCK)
    kb = _pick_block(s, KV_BLOCK)
    nq, nk = t // qb, s // kb
    scale = hd ** -0.5

    q5 = q.reshape(b, nq, qb, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    qp2 = q_pos.reshape(nq, qb)
    k5 = k.reshape(b, nk, kb, kvh, hd).transpose(1, 0, 2, 3, 4)
    v5 = v.reshape(b, nk, kb, kvh, hd).transpose(1, 0, 2, 3, 4)
    kp2 = kv_pos.reshape(nk, kb)

    def q_body(_, q_in):
        qi, qp = q_in                      # [B,qb,KV,G,hd], [qb]
        qf = qi.astype(jnp.float32) * scale

        def kv_body(carry, kv_in):
            acc, m, l = carry
            kj, vj, kp = kv_in             # [B,kb,KV,hd], [kb]
            scores = jnp.einsum(
                "bqkgh,bskh->bkgqs", qf, kj.astype(jnp.float32)
            )                               # [B,KV,G,qb,kb]
            ok = jnp.ones((qb, kb), bool)
            if causal:
                ok = ok & (kp[None, :] <= qp[:, None])
            if window is not None:
                ok = ok & (kp[None, :] > qp[:, None] - window)
            scores = jnp.where(ok[None, None, None], scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            p = jnp.exp(scores - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + p.sum(axis=-1)
            acc_new = alpha[..., None] * acc + jnp.einsum(
                "bkgqs,bskh->bkgqh", p, vj.astype(jnp.float32)
            )
            return (acc_new, m_new, l_new), None

        init = (
            jnp.zeros((b, kvh, g, qb, hd), jnp.float32),
            jnp.full((b, kvh, g, qb), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, qb), jnp.float32),
        )
        (acc, m, l), _ = jax.lax.scan(
            jax.checkpoint(kv_body), init, (k5, v5, kp2)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 3, 1, 2, 4).reshape(b, qb, h * hd)

    _, outs = jax.lax.scan(q_body, None, (q5, qp2))     # [nq, B, qb, Hhd]
    return outs.transpose(1, 0, 2, 3).reshape(b, t, h * hd).astype(q.dtype)


def _full_seq_attention(
    q, k, v, q_pos, kv_pos, window, causal
) -> jnp.ndarray:
    """Dispatch dense vs chunked by score-buffer size."""
    t, s = q.shape[1], k.shape[1]
    if t * s <= DENSE_SCORE_LIMIT:
        if causal:
            ok = kv_pos[None, :] <= q_pos[:, None]
            if window is not None:
                ok = ok & (kv_pos[None, :] > q_pos[:, None] - window)
            mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)[None]
        else:
            mask = None
        return _gqa_core(q, k, v, mask)
    return _chunked_gqa(q, k, v, q_pos, kv_pos, window, causal)


def attention_forward(
    params: Params,
    x: jnp.ndarray,             # [B, T, D]
    positions: jnp.ndarray,     # [T] int32
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[jnp.ndarray] = None,  # scalar; None = full causal
    causal: bool = True,                   # False = bidirectional (encoder)
) -> jnp.ndarray:
    """Full-sequence attention (training / prefill logits)."""
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    q = apply_rope(q, positions[None, :], rope_theta)
    k = apply_rope(k, positions[None, :], rope_theta)
    out = _full_seq_attention(q, k, v, positions, positions, window, causal)
    return pim_linear(out, params["wo"])


def attention_prefill(
    params: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache_k: jnp.ndarray,       # [B, S_max, KV, hd] — pre-allocated
    cache_v: jnp.ndarray,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill: causal attention over the prompt + write KV into cache."""
    b, t, _ = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    q = apply_rope(q, positions[None, :], rope_theta)
    k = apply_rope(k, positions[None, :], rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), 0, axis=1
    )
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), 0, axis=1
    )
    out = _full_seq_attention(q, k, v, positions, positions, window, causal=True)
    return pim_linear(out, params["wo"]), cache_k, cache_v


def attention_decode(
    params: Params,
    x: jnp.ndarray,             # [B, 1, D]
    position: jnp.ndarray,      # scalar int32 — index of the new token
    cache_k: jnp.ndarray,       # [B, S, KV, hd]
    cache_v: jnp.ndarray,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode against the cache. This is the paper's workload:
    a batch of GEMVs against PIM-resident weights + a KV-cache sweep."""
    b = x.shape[0]
    s = cache_k.shape[1]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    pos = position[None, None] if position.ndim == 0 else position
    q = apply_rope(q, jnp.full((1, 1), 0, jnp.int32) + position, rope_theta)
    k = apply_rope(k, jnp.full((1, 1), 0, jnp.int32) + position, rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), position.astype(jnp.int32), axis=1
    )
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), position.astype(jnp.int32), axis=1
    )
    kv_pos = jnp.arange(s, dtype=jnp.int32)
    ok = kv_pos <= position
    if window is not None:
        ok = ok & (kv_pos > position - window)
    mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)[None, None, :]
    out = _gqa_core(q, cache_k, cache_v, mask)
    return pim_linear(out, params["wo"]), cache_k, cache_v


def attention_decode_paged(
    params: Params,
    x: jnp.ndarray,             # [B, 1, D] — one new token per slot
    positions: jnp.ndarray,     # [B] int32 — per-slot index of the new token
    k_pages: jnp.ndarray,       # [n_blocks, KV, bs, hd] shared page pool
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,   # [B, max_blocks] int32
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[jnp.ndarray] = None,  # scalar; None = full causal
    impl: str = "auto",
    block_start: Optional[jnp.ndarray] = None,  # [B] first live block
    bucket_plans=None,
    bucket_perms=None,
    plan_class=None,
    k_scales: Optional[jnp.ndarray] = None,  # [n_blocks, KV] f32 (int8 pools)
    v_scales: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, ...]:
    """One-token decode against a block-paged cache (DESIGN.md §8).

    Unlike `attention_decode` there is no global write position: each slot
    carries its own `positions[b]`, the new KV row scatters into page
    `block_table[b, positions[b] // bs]` at offset `positions[b] % bs`,
    and attention runs over the slot's ragged length — so slots refilled
    mid-run with different prompt lengths coexist in one decode batch.
    `impl` follows `kernels.ops.resolve_impl`: `auto` silently dispatches
    (oracle off-TPU, native scalar-prefetch kernel on TPU); explicit
    values are strict.

    Layer-major extras (DESIGN.md §12): `block_table` is THIS layer's
    table, `block_start` its per-slot first live block (sliding-window
    retirement), and `bucket_plans`/`bucket_perms`/`plan_class` select
    the layer group's bucket-plan variant (see `_select_bucket_plan`).

    Quantized pools (DESIGN.md §16): `k_scales`/`v_scales` are this
    layer's per-page per-head scale rows. The fresh KV row appends via
    an opaque read-modify-write requantization of the ONE touched page
    (`kernels.paged_common.requantize_page_update` — this layer never
    dequantizes anything itself, analysis rule RL206), and the updated
    scales flow into the kernel and back to the caller: the return
    grows to a 5-tuple `(out, k_pages, v_pages, k_scales, v_scales)`.
    With `k_scales=None` the float path is byte-for-byte the PR 8 code.
    """
    b = x.shape[0]
    bs = k_pages.shape[2]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    q = apply_rope(q, positions[:, None], rope_theta)
    k = apply_rope(k, positions[:, None], rope_theta)
    page = block_table[jnp.arange(b), positions // bs]      # [B]
    offset = positions % bs
    if k_scales is None:
        k_pages = k_pages.at[page, :, offset].set(
            k[:, 0].astype(k_pages.dtype)
        )
        v_pages = v_pages.at[page, :, offset].set(
            v[:, 0].astype(v_pages.dtype)
        )
    else:
        rows = jnp.arange(b)

        def scatter_row(new):                      # new: [B, KV, hd]
            def upd(pages_f):                      # [B, KV, bs, hd] f32
                return pages_f.at[rows, :, offset].set(
                    new.astype(jnp.float32)
                )
            return upd

        k_codes, k_sc = requantize_page_update(
            k_pages[page], k_scales[page], scatter_row(k[:, 0])
        )
        v_codes, v_sc = requantize_page_update(
            v_pages[page], v_scales[page], scatter_row(v[:, 0])
        )
        k_pages = k_pages.at[page].set(k_codes)
        v_pages = v_pages.at[page].set(v_codes)
        k_scales = k_scales.at[page].set(k_sc)
        v_scales = v_scales.at[page].set(v_sc)
    capacity = block_table.shape[1] * bs
    win = jnp.asarray(capacity if window is None else window, jnp.int32)

    def call(plan, perm):
        return paged_attention(
            q[:, 0], k_pages, v_pages, block_table, positions + 1, win,
            impl=impl, plan=plan, perm=perm, block_start=block_start,
            k_scales=k_scales, v_scales=v_scales,
        )                                                    # [B, H, hd] f32

    out = _select_bucket_plan(call, bucket_plans, bucket_perms, plan_class)
    out = out.reshape(b, 1, n_heads * head_dim).astype(x.dtype)
    out = pim_linear(out, params["wo"])
    if k_scales is None:
        return out, k_pages, v_pages
    return out, k_pages, v_pages, k_scales, v_scales


def attention_prefill_paged(
    params: Params,
    x: jnp.ndarray,             # [B, T, D] — uncached suffix tokens (T padded)
    start: jnp.ndarray,         # [B] int32 — cached-prefix length per slot
    total: jnp.ndarray,         # [B] int32 — full valid length per slot
    k_pages: jnp.ndarray,       # [n_blocks, KV, bs, hd] shared page pool
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,   # [B, max_blocks] int32
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[jnp.ndarray] = None,  # scalar; None = full causal
    impl: str = "auto",
    block_start: Optional[jnp.ndarray] = None,  # [B] first live block
    bucket_plans=None,
    bucket_perms=None,
    plan_class=None,
    k_scales: Optional[jnp.ndarray] = None,  # [n_blocks, KV] f32 (int8 pools)
    v_scales: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, ...]:
    """Suffix prefill against a block-paged cache (DESIGN.md §9).

    Suffix token t sits at logical position `start + t`: RoPE rotates at
    that offset, its KV scatters into page
    `block_table[b, (start+t) // bs]` at offset `(start+t) % bs`, and
    attention runs over the cached prefix pages *and* the fresh suffix
    with the offset causal mask. Padding rows (start + t >= total) write
    garbage KV beyond the slot's length (masked everywhere, overwritten
    by later decode scatters) or into the scratch page when they fall
    past the slot's allocated blocks. `impl` follows
    `kernels.ops.resolve_impl` (strict explicit values, silent `auto`).

    Chunked prefill (DESIGN.md §17) is this same suffix path called
    repeatedly with an advancing `start`: chunk k covers positions
    [start_k, start_k + T). Because the suffix KV is scattered into the
    pages BEFORE the attention walk reads them back through the block
    table, a chunk attends over every previously-written chunk exactly
    as a single-shot prefill with `total = start_k + T` would — the
    causal mask makes the two decompositions bit-identical, so no new
    kernel or mask variant is needed here.

    Layer-major extras (DESIGN.md §12): `block_table` is THIS layer's
    table (a windowed layer's retired/skipped head columns are scratch,
    masked by the window term), `block_start` the per-slot first live
    block, and `bucket_plans`/`bucket_perms`/`plan_class` select the
    layer group's bucket-plan variant — the scatter always targets the
    full table, only the read walk is bucket-bounded.

    Quantized pools (DESIGN.md §16): the suffix scatters through an
    opaque read-modify-write requantization of the slot's table row
    (`kernels.paged_common.requantize_page_update`; RL206 keeps the
    dequant itself inside the kernel scaffold). Pad rows route to a
    dummy gather row so a ragged final page's scale is set by VALID
    tokens only, and untouched columns (cached-prefix pages, possibly
    refcounted > 1, plus trailing scratch) write back to scratch page 0
    so shared pages are never written in place. Returns the 5-tuple
    `(out, k_pages, v_pages, k_scales, v_scales)`; with `k_scales=None`
    the float path is byte-for-byte the PR 8 code.
    """
    b, t, _ = x.shape
    bs = k_pages.shape[2]
    mb = block_table.shape[1]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    block_idx = positions // bs                              # [B, T]
    page = jnp.take_along_axis(
        block_table, jnp.minimum(block_idx, mb - 1), axis=1
    )
    # padding rows past the table's capacity must land in scratch, NOT
    # clamp into the slot's (valid) last page
    page = jnp.where(block_idx < mb, page, 0)
    offset = positions % bs
    if k_scales is None:
        k_pages = k_pages.at[page, :, offset].set(k.astype(k_pages.dtype))
        v_pages = v_pages.at[page, :, offset].set(v.astype(v_pages.dtype))
    else:
        valid = (positions < total[:, None]) & (block_idx < mb)  # [B, T]
        row = jnp.where(valid, block_idx, mb)          # pads → dummy row
        col = jnp.arange(mb, dtype=jnp.int32)[None, :]           # [1, mb]
        touched = (col >= start[:, None] // bs) & (col * bs < total[:, None])
        write_pages = jnp.where(touched, block_table, 0)
        rows = jnp.arange(b)[:, None]

        def scatter_suffix(new):                   # new: [B, T, KV, hd]
            def upd(pages_f):                      # [B, mb, KV, bs, hd] f32
                padded = jnp.concatenate(
                    [pages_f, jnp.zeros_like(pages_f[:, :1])], axis=1
                )
                padded = padded.at[rows, row, :, offset].set(
                    new.astype(jnp.float32)
                )
                return padded[:, :mb]
            return upd

        k_codes, k_sc = requantize_page_update(
            k_pages[block_table], k_scales[block_table], scatter_suffix(k)
        )
        v_codes, v_sc = requantize_page_update(
            v_pages[block_table], v_scales[block_table], scatter_suffix(v)
        )
        k_pages = k_pages.at[write_pages].set(k_codes)
        v_pages = v_pages.at[write_pages].set(v_codes)
        k_scales = k_scales.at[write_pages].set(k_sc)
        v_scales = v_scales.at[write_pages].set(v_sc)
    capacity = mb * bs
    win = jnp.asarray(capacity if window is None else window, jnp.int32)

    def call(plan, perm):
        return paged_prefill(
            q, k_pages, v_pages, block_table, start, total, win,
            impl=impl, plan=plan, perm=perm, block_start=block_start,
            k_scales=k_scales, v_scales=v_scales,
        )                                                    # [B, T, H, hd]

    out = _select_bucket_plan(call, bucket_plans, bucket_perms, plan_class)
    out = out.reshape(b, t, n_heads * head_dim).astype(x.dtype)
    out = pim_linear(out, params["wo"])
    if k_scales is None:
        return out, k_pages, v_pages
    return out, k_pages, v_pages, k_scales, v_scales


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, d_model: int, n_heads: int, head_dim: int) -> Params:
    ks = split_keys(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim),
        "wk": dense_init(ks[1], d_model, n_heads * head_dim),
        "wv": dense_init(ks[2], d_model, n_heads * head_dim),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model),
    }


def cross_attention_kv(params: Params, enc_out: jnp.ndarray, n_heads: int, hd: int):
    from ..dist.sharding import shard
    b, s, _ = enc_out.shape
    k = pim_linear(enc_out, params["wk"]).reshape(b, s, n_heads, hd)
    v = pim_linear(enc_out, params["wv"]).reshape(b, s, n_heads, hd)
    k = shard(k, "batch", "seq", "heads", "head_dim")
    v = shard(v, "batch", "seq", "heads", "head_dim")
    return k, v


def cross_attention_forward(
    params: Params,
    x: jnp.ndarray,           # [B, T, D] decoder states
    k: jnp.ndarray,           # [B, S, H, hd] precomputed encoder K
    v: jnp.ndarray,
    n_heads: int,
    head_dim: int,
) -> jnp.ndarray:
    from ..dist.sharding import shard
    b, t, _ = x.shape
    s = k.shape[1]
    q = shard(
        pim_linear(x, params["wq"]).reshape(b, t, n_heads, head_dim),
        "batch", "seq", "heads", "head_dim",
    )
    out = _full_seq_attention(
        q, k, v,
        jnp.arange(t, dtype=jnp.int32), jnp.arange(s, dtype=jnp.int32),
        window=None, causal=False,
    )
    return pim_linear(out, params["wo"])
