"""Unified decoder-only LM covering the assigned architecture families.

One parameter tree + three entry points per model:

    forward(params, tokens, cfg)              -> (logits, aux)      train
    prefill(params, tokens, cfg, cache_len)   -> (logits, cache)    serving
    decode_step(params, token, cache, pos, cfg) -> (logits, cache)  serving

Layer stacks are *scanned* (stacked [L, ...] parameter pytrees +
lax.scan) so the compiled HLO contains one layer body regardless of
depth — essential for 62-layer 32k-seq dry-runs on a CPU host.

Heterogeneity is handled three ways (DESIGN.md §4):
  - per-layer scalars (attention windows — gemma3 5:1 local:global) ride
    as scanned arrays;
  - xLSTM's mLSTM/sLSTM alternation scans a *union* parameter stack and
    lax.cond selects the active cell (24 small layers — cheap);
  - zamba2's weight-shared attention block runs *between* scanned groups
    (one python-level group per shared-attention site) so each site gets
    its own KV-cache slot without dynamic indexing inside the scan.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..dist.sharding import shard
from .attention import (
    attention_decode,
    attention_decode_paged,
    attention_forward,
    attention_prefill,
    attention_prefill_paged,
    init_attention,
)
from .common import Params, compute_dtype, embed_init, rmsnorm, rmsnorm_params, split_keys
from .mamba2 import conv_dim, init_mamba2, mamba2_decode, mamba2_forward
from .mlp import init_mlp, init_swiglu, mlp, swiglu
from .moe import init_moe, moe_forward
from .xlstm import (
    init_mlstm,
    init_slstm,
    mlstm_decode,
    mlstm_forward,
    mlstm_init_state,
    slstm_decode,
    slstm_forward,
    slstm_init_state,
)

Cache = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig) -> Params:
    ks = split_keys(key, 4)
    if cfg.block_kind == "attn":
        p: Params = {
            "ln1": rmsnorm_params(cfg.d_model),
            "attn": init_attention(
                ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                cfg.qkv_bias,
            ),
            "ln2": rmsnorm_params(cfg.d_model),
        }
        if cfg.n_experts:
            p["moe"] = init_moe(
                ks[1], cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_shared_experts
            )
        elif cfg.mlp_kind == "plain":
            p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff)
        else:
            p["mlp"] = init_swiglu(ks[1], cfg.d_model, cfg.d_ff)
        return p
    if cfg.block_kind == "mamba":
        return {
            "ln1": rmsnorm_params(cfg.d_model),
            "mamba": init_mamba2(
                ks[0], cfg.d_model, cfg.d_inner, cfg.ssm_heads, cfg.ssm_state,
                cfg.ssm_conv,
            ),
        }
    if cfg.block_kind == "xlstm":
        return {
            "ln1": rmsnorm_params(cfg.d_model),
            "mlstm": init_mlstm(ks[0], cfg.d_model, cfg.n_heads, cfg.ssm_expand),
            "ln_s": rmsnorm_params(cfg.d_model),
            "slstm": init_slstm(ks[1], cfg.d_model, cfg.n_heads),
        }
    raise ValueError(cfg.block_kind)


def init_lm(key, cfg: ModelConfig) -> Params:
    cfg.validate()
    keys = split_keys(key, cfg.n_layers + 3)
    layers = [init_layer(keys[i], cfg) for i in range(cfg.n_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    params: Params = {
        "embed": embed_init(keys[-1], cfg.vocab_size, cfg.d_model),
        "layers": stacked,
        "final_norm": rmsnorm_params(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[-2], cfg.d_model, cfg.vocab_size)
    if cfg.attn_every > 0:
        ks = split_keys(keys[-3], 2)
        params["shared"] = {
            "ln": rmsnorm_params(cfg.d_model),
            "attn": init_attention(
                ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, False
            ),
            "ln2": rmsnorm_params(cfg.d_model),
            "mlp": init_swiglu(ks[1], cfg.d_model, cfg.d_ff),
        }
    return params


def count_params(params: Params) -> int:
    return sum(
        x.size for x in jax.tree_util.tree_leaves(params) if hasattr(x, "size")
    )


# ---------------------------------------------------------------------------
# block bodies
# ---------------------------------------------------------------------------

def _attn_kwargs(cfg: ModelConfig) -> Dict[str, Any]:
    return dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        rope_theta=cfg.rope_theta,
    )


def _attn_block(lp, x, positions, window, cfg, aux_sum):
    h = attention_forward(
        lp["attn"], rmsnorm(lp["ln1"], x, cfg.norm_eps), positions,
        window=window, **_attn_kwargs(cfg),
    )
    x = x + h
    hin = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    if cfg.n_experts:
        h2, aux = moe_forward(
            lp["moe"], hin, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, act=cfg.act,
        )
        aux_sum = aux_sum + aux["load_balance_loss"]
    else:
        h2 = _ffn(lp, hin, cfg)
    return x + h2, aux_sum


def _ffn(lp, x, cfg):
    if cfg.mlp_kind == "plain":
        return mlp(lp["mlp"], x, cfg.act)
    return swiglu(lp["mlp"], x, cfg.act)


def _mamba_block(lp, x, cfg):
    h = mamba2_forward(
        lp["mamba"], rmsnorm(lp["ln1"], x, cfg.norm_eps),
        n_heads=cfg.ssm_heads, n_state=cfg.ssm_state, d_inner=cfg.d_inner,
    )
    return x + h


def _xlstm_block(lp, x, is_slstm, cfg):
    def do_m(x):
        return x + mlstm_forward(
            lp["mlstm"], rmsnorm(lp["ln1"], x, cfg.norm_eps), n_heads=cfg.n_heads
        )

    def do_s(x):
        return x + slstm_forward(
            lp["slstm"], rmsnorm(lp["ln_s"], x, cfg.norm_eps), n_heads=cfg.n_heads
        )

    return jax.lax.cond(is_slstm, do_s, do_m, x)


# ---------------------------------------------------------------------------
# full-sequence forward (training)
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg, extra_embeds):
    dt = compute_dtype(cfg.dtype)
    x = params["embed"][tokens].astype(dt)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(dt), x], axis=1)
    return shard(x, "batch", "seq", "embed")


def _head(params, x, cfg):
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    w = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = jnp.dot(x, w.astype(x.dtype))
    return shard(logits, "batch", "seq", "vocab")


def _window_array(cfg: ModelConfig, seq_len: int) -> jnp.ndarray:
    return jnp.asarray(cfg.window_schedule(seq_len), jnp.int32)


def forward(
    params: Params,
    tokens: jnp.ndarray,           # [B, T_txt]
    cfg: ModelConfig,
    extra_embeds: Optional[jnp.ndarray] = None,  # [B, T_front, D] stub
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    x = _embed(params, tokens, cfg, extra_embeds)
    t = x.shape[1]
    positions = jnp.arange(t, dtype=jnp.int32)
    windows = _window_array(cfg, t)
    flags = cfg.layer_flags()
    aux0 = jnp.zeros((), jnp.float32)

    if cfg.block_kind == "attn":

        def body(carry, xs):
            xc, aux = carry
            lp, w = xs
            xc, aux = _attn_block(lp, xc, positions, w, cfg, aux)
            return (xc, aux), None

        body = jax.checkpoint(body) if cfg.remat else body
        (x, aux), _ = jax.lax.scan(body, (x, aux0), (params["layers"], windows))

    elif cfg.block_kind == "mamba":
        x, aux = _mamba_stack_forward(params, x, positions, cfg)

    elif cfg.block_kind == "xlstm":
        slstm_flags = jnp.asarray(flags["is_slstm"])

        def body(carry, xs):
            lp, fl = xs
            return _xlstm_block(lp, carry, fl, cfg), None

        body = jax.checkpoint(body) if cfg.remat else body
        x, _ = jax.lax.scan(body, x, (params["layers"], slstm_flags))
        aux = aux0
    else:
        raise ValueError(cfg.block_kind)

    return _head(params, x, cfg), {"moe_aux": aux}


def _group_slices(cfg: ModelConfig):
    """Split the layer stack into zamba2 groups ending in a shared-attn."""
    ae = cfg.attn_every
    n = cfg.n_layers
    if ae <= 0:
        return [(0, n, False)]
    out = []
    start = 0
    while start < n:
        end = min(start + ae, n)
        has_attn = (end - start) == ae  # full group ends with shared attn
        out.append((start, end, has_attn))
        start = end
    return out


def _slice_layers(stacked: Params, lo: int, hi: int) -> Params:
    return jax.tree.map(lambda a: a[lo:hi], stacked)


def _mamba_stack_forward(params, x, positions, cfg):
    aux = jnp.zeros((), jnp.float32)

    def body(xc, lp):
        return _mamba_block(lp, xc, cfg), None

    body = jax.checkpoint(body) if cfg.remat else body
    for lo, hi, has_attn in _group_slices(cfg):
        x, _ = jax.lax.scan(body, x, _slice_layers(params["layers"], lo, hi))
        if has_attn and "shared" in params:
            sp = params["shared"]
            h = attention_forward(
                sp["attn"], rmsnorm(sp["ln"], x, cfg.norm_eps), positions,
                window=None, **_attn_kwargs(cfg),
            )
            x = x + h
            x = x + swiglu(sp["mlp"], rmsnorm(sp["ln2"], x, cfg.norm_eps), cfg.act)
    return x, aux


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> Cache:
    """Pre-allocated decode cache (bf16 KV, f32 recurrent states)."""
    dt = compute_dtype(cfg.dtype)
    l = cfg.n_layers
    cache: Cache = {"position": jnp.zeros((), jnp.int32)}
    if cfg.block_kind == "attn":
        kv_shape = (l, batch, cache_len, cfg.n_kv_heads, cfg.hd)
        cache["k"] = jnp.zeros(kv_shape, dt)
        cache["v"] = jnp.zeros(kv_shape, dt)
    elif cfg.block_kind == "mamba":
        p = cfg.d_inner // cfg.ssm_heads
        cache["ssm"] = jnp.zeros((l, batch, cfg.ssm_heads, p, cfg.ssm_state), jnp.float32)
        cache["conv"] = jnp.zeros(
            (l, batch, cfg.ssm_conv - 1, conv_dim(cfg.d_inner, cfg.ssm_state)), dt
        )
        n_sites = sum(1 for *_x, ha in _group_slices(cfg) if ha)
        if n_sites:
            shp = (n_sites, batch, cache_len, cfg.n_kv_heads, cfg.hd)
            cache["shared_k"] = jnp.zeros(shp, dt)
            cache["shared_v"] = jnp.zeros(shp, dt)
    elif cfg.block_kind == "xlstm":
        hd = cfg.ssm_expand * cfg.d_model // cfg.n_heads
        m = mlstm_init_state(batch, cfg.n_heads, hd)
        s = slstm_init_state(batch, cfg.d_model)
        rep = lambda a: jnp.broadcast_to(a[None], (l,) + a.shape)
        cache.update({
            "C": rep(m["C"]), "n": rep(m["n"]), "m": rep(m["m"]),
            "sc": rep(s["c"]), "sn": rep(s["n"]), "sm": rep(s["m"]), "sh": rep(s["h"]),
        })
    return shard_cache(cache)


def shard_cache(cache: Cache) -> Cache:
    """Sequence-parallel layout: KV sequence over `data` (long_500k)."""
    out = {}
    for k, v in cache.items():
        if k in ("k", "v", "shared_k", "shared_v"):
            out[k] = shard(v, "layers", "batch", "kv_seq", "kv_heads", "cache_head_dim")
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill(
    params: Params,
    tokens: jnp.ndarray,
    cfg: ModelConfig,
    cache_len: int,
    extra_embeds: Optional[jnp.ndarray] = None,
    last_pos: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Cache]:
    """Process the prompt, build the cache, return last-position logits.

    `last_pos` (dynamic scalar) selects which position's logits to return
    instead of T-1 — callers that right-pad ragged prompts to a shared
    bucketed shape (paged serving) pass the true prompt end, so one XLA
    compilation covers every prompt length in the bucket (causality keeps
    positions < last_pos unaffected by the padding)."""
    x = _embed(params, tokens, cfg, extra_embeds)
    b, t, _ = x.shape
    positions = jnp.arange(t, dtype=jnp.int32)
    cache = init_cache(cfg, b, cache_len)
    windows = _window_array(cfg, cache_len)

    if cfg.block_kind == "attn":

        def body(xc, xs):
            lp, w, ck, cv = xs
            h, ck, cv = attention_prefill(
                lp["attn"], rmsnorm(lp["ln1"], xc, cfg.norm_eps), positions,
                ck, cv, window=w, **_attn_kwargs(cfg),
            )
            xc = xc + h
            hin = rmsnorm(lp["ln2"], xc, cfg.norm_eps)
            if cfg.n_experts:
                h2, _ = moe_forward(
                    lp["moe"], hin, top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor, act=cfg.act,
                )
            else:
                h2 = _ffn(lp, hin, cfg)
            return xc + h2, (ck, cv)

        x, (cache["k"], cache["v"]) = jax.lax.scan(
            body, x, (params["layers"], windows, cache["k"], cache["v"])
        )

    elif cfg.block_kind == "mamba":
        ssm_list, conv_list = [], []
        site = 0

        def body(xc, xs):
            lp, _ = xs
            xin = rmsnorm(lp["ln1"], xc, cfg.norm_eps)
            h, (st, cv) = mamba2_forward(
                lp["mamba"], xin, n_heads=cfg.ssm_heads, n_state=cfg.ssm_state,
                d_inner=cfg.d_inner, return_state=True,
            )
            return xc + h, (st, cv)

        for lo, hi, has_attn in _group_slices(cfg):
            sub = _slice_layers(params["layers"], lo, hi)
            dummy = jnp.zeros((hi - lo,), jnp.int32)
            x, (sts, cvs) = jax.lax.scan(body, x, (sub, dummy))
            ssm_list.append(sts)
            conv_list.append(cvs)
            if has_attn and "shared" in params:
                sp = params["shared"]
                h, ck, cv = attention_prefill(
                    sp["attn"], rmsnorm(sp["ln"], x, cfg.norm_eps), positions,
                    cache["shared_k"][site], cache["shared_v"][site],
                    window=None, **_attn_kwargs(cfg),
                )
                x = x + h
                x = x + swiglu(sp["mlp"], rmsnorm(sp["ln2"], x, cfg.norm_eps), cfg.act)
                cache["shared_k"] = cache["shared_k"].at[site].set(ck)
                cache["shared_v"] = cache["shared_v"].at[site].set(cv)
                site += 1
        cache["ssm"] = jnp.concatenate(ssm_list, axis=0)
        cache["conv"] = jnp.concatenate(conv_list, axis=0)

    elif cfg.block_kind == "xlstm":
        flags = jnp.asarray(cfg.layer_flags()["is_slstm"])

        def body(xc, xs):
            lp, fl, C, n, m, sc, sn, sm, sh = xs

            def do_m(x):
                y, st = mlstm_forward(
                    lp["mlstm"], rmsnorm(lp["ln1"], x, cfg.norm_eps),
                    n_heads=cfg.n_heads,
                    init_state={"C": C, "n": n, "m": m}, return_state=True,
                )
                return x + y, (st["C"], st["n"], st["m"], sc, sn, sm, sh)

            def do_s(x):
                y, st = slstm_forward(
                    lp["slstm"], rmsnorm(lp["ln_s"], x, cfg.norm_eps),
                    n_heads=cfg.n_heads,
                    init_state={"c": sc, "n": sn, "m": sm, "h": sh},
                    return_state=True,
                )
                return x + y, (C, n, m, st["c"], st["n"], st["m"], st["h"])

            xc, states = jax.lax.cond(fl, do_s, do_m, xc)
            return xc, states

        x, (C, n, m, sc, sn, sm, sh) = jax.lax.scan(
            body, x,
            (params["layers"], flags, cache["C"], cache["n"], cache["m"],
             cache["sc"], cache["sn"], cache["sm"], cache["sh"]),
        )
        cache.update({"C": C, "n": n, "m": m, "sc": sc, "sn": sn, "sm": sm, "sh": sh})

    cache["position"] = jnp.asarray(t, jnp.int32)
    cache = shard_cache(cache)
    if last_pos is None:
        xe = x[:, -1:]
    else:
        xe = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(last_pos, jnp.int32), 1, axis=1
        )
    logits = _head(params, xe, cfg)
    return logits, cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_step(
    params: Params,
    token: jnp.ndarray,      # [B, 1] int32
    cache: Cache,
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Cache]:
    """One new token against the cache — the paper's GEMV workload."""
    dt = compute_dtype(cfg.dtype)
    x = params["embed"][token].astype(dt)
    pos = cache["position"]
    cache_len = _cache_len(cache, cfg)
    windows = _window_array(cfg, cache_len)
    new_cache = dict(cache)

    if cfg.block_kind == "attn":

        def body(xc, xs):
            lp, w, ck, cv = xs
            h, ck, cv = attention_decode(
                lp["attn"], rmsnorm(lp["ln1"], xc, cfg.norm_eps), pos,
                ck, cv, window=w, **_attn_kwargs(cfg),
            )
            xc = xc + h
            hin = rmsnorm(lp["ln2"], xc, cfg.norm_eps)
            if cfg.n_experts:
                h2, _ = moe_forward(
                    lp["moe"], hin, top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor, act=cfg.act,
                )
            else:
                h2 = _ffn(lp, hin, cfg)
            return xc + h2, (ck, cv)

        x, (new_cache["k"], new_cache["v"]) = jax.lax.scan(
            body, x, (params["layers"], windows, cache["k"], cache["v"])
        )

    elif cfg.block_kind == "mamba":
        ssm_list, conv_list = [], []
        site = 0

        def body(xc, xs):
            lp, st, cv = xs
            xin = rmsnorm(lp["ln1"], xc, cfg.norm_eps)
            h, st, cv = mamba2_decode(
                lp["mamba"], xin, st, cv, n_heads=cfg.ssm_heads,
                n_state=cfg.ssm_state, d_inner=cfg.d_inner,
            )
            return xc + h, (st, cv)

        for lo, hi, has_attn in _group_slices(cfg):
            sub = _slice_layers(params["layers"], lo, hi)
            x, (sts, cvs) = jax.lax.scan(
                body, x, (sub, cache["ssm"][lo:hi], cache["conv"][lo:hi])
            )
            ssm_list.append(sts)
            conv_list.append(cvs)
            if has_attn and "shared" in params:
                sp = params["shared"]
                h, ck, cv = attention_decode(
                    sp["attn"], rmsnorm(sp["ln"], x, cfg.norm_eps), pos,
                    cache["shared_k"][site], cache["shared_v"][site],
                    window=None, **_attn_kwargs(cfg),
                )
                x = x + h
                x = x + swiglu(sp["mlp"], rmsnorm(sp["ln2"], x, cfg.norm_eps), cfg.act)
                new_cache["shared_k"] = new_cache["shared_k"].at[site].set(ck)
                new_cache["shared_v"] = new_cache["shared_v"].at[site].set(cv)
                site += 1
        new_cache["ssm"] = jnp.concatenate(ssm_list, axis=0)
        new_cache["conv"] = jnp.concatenate(conv_list, axis=0)

    elif cfg.block_kind == "xlstm":
        flags = jnp.asarray(cfg.layer_flags()["is_slstm"])

        def body(xc, xs):
            lp, fl, C, n, m, sc, sn, sm, sh = xs

            def do_m(x):
                y, st = mlstm_decode(
                    lp["mlstm"], rmsnorm(lp["ln1"], x, cfg.norm_eps),
                    {"C": C, "n": n, "m": m}, n_heads=cfg.n_heads,
                )
                return x + y, (st["C"], st["n"], st["m"], sc, sn, sm, sh)

            def do_s(x):
                y, st = slstm_decode(
                    lp["slstm"], rmsnorm(lp["ln_s"], x, cfg.norm_eps),
                    {"c": sc, "n": sn, "m": sm, "h": sh}, n_heads=cfg.n_heads,
                )
                return x + y, (C, n, m, st["c"], st["n"], st["m"], st["h"])

            xc, states = jax.lax.cond(fl, do_s, do_m, xc)
            return xc, states

        x, (C, n, m, sc, sn, sm, sh) = jax.lax.scan(
            body, x,
            (params["layers"], flags, cache["C"], cache["n"], cache["m"],
             cache["sc"], cache["sn"], cache["sm"], cache["sh"]),
        )
        new_cache.update({"C": C, "n": n, "m": m, "sc": sc, "sn": sn,
                          "sm": sm, "sh": sh})

    new_cache["position"] = pos + 1
    new_cache = shard_cache(new_cache)
    logits = _head(params, x, cfg)
    return logits, new_cache


# ---------------------------------------------------------------------------
# paged decode (DESIGN.md §8, §12)
# ---------------------------------------------------------------------------

def layer_attn_groups(
    cfg: ModelConfig, capacity: int
) -> list:
    """Partition the layer stack by attention pattern (DESIGN.md §12).

    Returns `[(window, layers), ...]` where `window` is the layer group's
    sliding window (None = global/full attention — any scheduled window
    that covers the whole `capacity`) and `layers` the tuple of model
    layer indices sharing it. This is THE grouping contract of the
    layer-major paged cache: `serve.paged_cache.PagedKVCache` keeps one
    physical page pool / free list / block table per group, and the
    paged model entry points map each scanned layer to its group's table
    and bucket plan — both sides derive the partition from this one
    function, so they can never disagree. Global groups sort first, then
    windowed groups by ascending window (a single-group config — no
    sliding windows — therefore always has the global pool at group 0,
    preserving the lockstep-era behavior exactly)."""
    groups: Dict[Optional[int], list] = {}
    for l, w in enumerate(cfg.window_schedule(capacity)):
        key = None if w >= capacity else int(w)
        groups.setdefault(key, []).append(l)
    keys = sorted(groups, key=lambda k: (k is not None, k or 0))
    return [(k, tuple(groups[k])) for k in keys]


def layer_group_index(cfg: ModelConfig, capacity: int) -> np.ndarray:
    """[L] int32: each layer's index into `layer_attn_groups`."""
    cls = np.zeros((cfg.n_layers,), np.int32)
    for gid, (_, layers) in enumerate(layer_attn_groups(cfg, capacity)):
        cls[list(layers)] = gid
    return cls


def init_paged_pool(
    cfg: ModelConfig, n_blocks: int, block_size: int, kv_dtype: str = "bf16"
) -> Tuple[jnp.ndarray, ...]:
    """Per-layer KV page pools [L, n_blocks, KV, bs, hd] (head-major
    pages: each head's (bs, hd) plane is one tile-aligned slab, DESIGN.md
    §10). Page-id SPACES
    are per layer group (DESIGN.md §12): layer l only ever reads pool[l]
    through its own group's block table, so two groups may hand out the
    same page index without aliasing — the stacked array is a physical
    layout, not a shared id space.

    `kv_dtype` selects the pool storage (DESIGN.md §16): "bf16" keeps
    the dense-cache compute dtype and returns `(k_pages, v_pages)`;
    "int8" stores symmetric per-page per-(layer,head) quantized codes
    and returns `(k_pages, v_pages, k_scales, v_scales)` with the
    scales [L, n_blocks, KV] f32, initialized to 1.0 (so an untouched
    all-zero page dequantizes to exact zeros)."""
    if cfg.block_kind != "attn":
        raise ValueError(
            f"paged KV cache requires attention layers, got {cfg.block_kind}"
        )
    shape = (cfg.n_layers, n_blocks, cfg.n_kv_heads, block_size, cfg.hd)
    if kv_dtype == "bf16":
        dt = compute_dtype(cfg.dtype)
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)
    if kv_dtype != "int8":
        raise ValueError(f"kv_dtype must be 'bf16' or 'int8', got {kv_dtype!r}")
    sshape = (cfg.n_layers, n_blocks, cfg.n_kv_heads)
    return (
        jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
        jnp.ones(sshape, jnp.float32), jnp.ones(sshape, jnp.float32),
    )


def _per_layer_paged_views(cfg, block_table, block_start, bucket_plan,
                           bucket_perm, capacity):
    """Normalize the paged entry points' layer-major arguments.

    `block_table` may be one shared [B, mb] table (lockstep-era callers,
    broadcast to every layer) or the layer-major [L, B, mb] stack;
    `block_start` likewise [B] / [L, B] (None = zeros). `bucket_plan`
    may be a single BucketPlan (applied to every layer) or a per-group
    tuple of plans aligned with `layer_attn_groups`. Returns
    (bt [L,B,mb], starts [L,B], plans tuple|None, perms tuple|None,
    cls [L] int32)."""
    from ..kernels.ops import is_bucket_plan

    l = cfg.n_layers
    if block_table.ndim == 2:
        block_table = jnp.broadcast_to(
            block_table[None], (l,) + block_table.shape
        )
    b = block_table.shape[1]
    if block_start is None:
        block_start = jnp.zeros((l, b), jnp.int32)
    elif block_start.ndim == 1:
        block_start = jnp.broadcast_to(block_start[None], (l, b))
    if bucket_plan is None:
        plans, perms = None, None
    elif is_bucket_plan(bucket_plan):
        plans, perms = (bucket_plan,), (bucket_perm,)
    else:
        plans, perms = tuple(bucket_plan), tuple(bucket_perm)
    if plans is not None and len(plans) > 1:
        cls = jnp.asarray(layer_group_index(cfg, capacity))
    else:
        cls = jnp.zeros((l,), jnp.int32)
    return block_table, block_start, plans, perms, cls


def decode_step_paged(
    params: Params,
    token: jnp.ndarray,        # [B, 1] int32 — one token per slot
    k_pages: jnp.ndarray,      # [L, n_blocks, KV, bs, hd]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [L, B, max_blocks] int32 per-layer tables
                               # (a [B, max_blocks] table broadcasts)
    positions: jnp.ndarray,    # [B] int32 — per-slot index of the new token
    cfg: ModelConfig,
    impl: str = "auto",
    bucket_plan=None,
    bucket_perm=None,
    block_start=None,          # [L, B] int32 first live block (or [B]/None)
    k_scales=None,             # [L, n_blocks, KV] f32 (int8 pools only)
    v_scales=None,
) -> Tuple[jnp.ndarray, ...]:
    """One decode step against the block-paged cache: per-slot positions
    instead of the dense cache's single global write offset, so every slot
    may sit at a different sequence length. `impl` selects the paged
    attention kernel path (ops.resolve_impl semantics).

    Layer-major (DESIGN.md §12): each layer scans with ITS OWN block
    table and first-live-block vector — a sliding-window layer's table
    holds only live trailing pages (retired head columns are scratch).
    `bucket_plan`/`bucket_perm` may be a single plan over `positions + 1`
    (every layer, the §11 behavior) or per-group tuples from
    `kernels.ops.bucket_args_grouped` — windowed groups bucketed by live
    trailing pages; the scanned body selects each layer's variant.

    Quantized pools (DESIGN.md §16): pass the [L, n_blocks, KV] scale
    stacks and each scanned layer threads its own scale rows through
    `attention_decode_paged`; the return grows to
    `(logits, k_pages, v_pages, k_scales, v_scales)`. With
    `k_scales=None` this is byte-for-byte the PR 8 float path."""
    if cfg.block_kind != "attn":
        raise ValueError("decode_step_paged supports attention stacks only")
    dt = compute_dtype(cfg.dtype)
    x = params["embed"][token].astype(dt)
    capacity = block_table.shape[-1] * k_pages.shape[3]
    windows = _window_array(cfg, capacity)
    block_table, block_start, plans, perms, cls = _per_layer_paged_views(
        cfg, block_table, block_start, bucket_plan, bucket_perm, capacity
    )
    quantized = k_scales is not None

    def body(xc, xs):
        if quantized:
            lp, w, c, bt, st, kp, vp, ks, vs = xs
        else:
            (lp, w, c, bt, st, kp, vp), ks, vs = xs, None, None
        res = attention_decode_paged(
            lp["attn"], rmsnorm(lp["ln1"], xc, cfg.norm_eps), positions,
            kp, vp, bt, window=w, impl=impl, block_start=st,
            bucket_plans=plans, bucket_perms=perms, plan_class=c,
            k_scales=ks, v_scales=vs,
            **_attn_kwargs(cfg),
        )
        if quantized:
            h, kp, vp, ks, vs = res
        else:
            h, kp, vp = res
        xc = xc + h
        hin = rmsnorm(lp["ln2"], xc, cfg.norm_eps)
        if cfg.n_experts:
            h2, _ = moe_forward(
                lp["moe"], hin, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, act=cfg.act,
            )
        else:
            h2 = _ffn(lp, hin, cfg)
        return xc + h2, ((kp, vp, ks, vs) if quantized else (kp, vp))

    xs = (params["layers"], windows, cls, block_table, block_start,
          k_pages, v_pages)
    if quantized:
        x, (k_pages, v_pages, k_scales, v_scales) = jax.lax.scan(
            body, x, xs + (k_scales, v_scales)
        )
    else:
        x, (k_pages, v_pages) = jax.lax.scan(body, x, xs)
    logits = _head(params, x, cfg)
    if quantized:
        return logits, k_pages, v_pages, k_scales, v_scales
    return logits, k_pages, v_pages


def prefill_paged(
    params: Params,
    tokens: jnp.ndarray,       # [B, T] int32 — uncached suffix (T padded)
    k_pages: jnp.ndarray,      # [L, n_blocks, KV, bs, hd]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [L, B, max_blocks] int32 per-layer tables
                               # (a [B, max_blocks] table broadcasts)
    start: jnp.ndarray,        # [B] int32 — cached-prefix length per slot
    total: jnp.ndarray,        # [B] int32 — full valid length per slot
    cfg: ModelConfig,
    last_pos: Optional[jnp.ndarray] = None,
    impl: str = "auto",
    bucket_plan=None,
    bucket_perm=None,
    block_start=None,          # [L, B] int32 first live block (or [B]/None)
    k_scales=None,             # [L, n_blocks, KV] f32 (int8 pools only)
    v_scales=None,
) -> Tuple[jnp.ndarray, ...]:
    """Prefill only the uncached suffix directly into the paged pools
    (DESIGN.md §9): the suffix KV scatters through the block table
    in-graph — no dense cache allocation, no host round trip — and each
    layer's attention covers the cached prefix pages via the paged-prefill
    kernel's offset causal mask. With start = 0 this is a full paged
    prefill; with a prefix hit the cached pages contribute reads only.
    Chunked prefill (DESIGN.md §17) is the same call iterated with an
    advancing `start` — each chunk reads the previous chunks' pages as
    "cached prefix", so the decomposition is bit-exact vs single-shot.

    `last_pos` (dynamic scalar, suffix-relative) selects which suffix
    position's logits to return instead of T-1 — callers right-pad ragged
    suffixes to a block-size bucket and pass the true suffix end.
    Layer-major (DESIGN.md §12): per-layer tables/starts as in
    `decode_step_paged`; `bucket_plan`/`bucket_perm` accept a single plan
    over the per-slot totals or per-group tuples.

    Quantized pools (DESIGN.md §16): as in `decode_step_paged` — scale
    stacks ride the scan and the return grows to
    `(logits, k_pages, v_pages, k_scales, v_scales)`.
    """
    if cfg.block_kind != "attn":
        raise ValueError("prefill_paged supports attention stacks only")
    dt = compute_dtype(cfg.dtype)
    x = _embed(params, tokens, cfg, None)
    capacity = block_table.shape[-1] * k_pages.shape[3]
    windows = _window_array(cfg, capacity)
    block_table, block_start, plans, perms, cls = _per_layer_paged_views(
        cfg, block_table, block_start, bucket_plan, bucket_perm, capacity
    )
    quantized = k_scales is not None

    def body(xc, xs):
        if quantized:
            lp, w, c, bt, st, kp, vp, ks, vs = xs
        else:
            (lp, w, c, bt, st, kp, vp), ks, vs = xs, None, None
        res = attention_prefill_paged(
            lp["attn"], rmsnorm(lp["ln1"], xc, cfg.norm_eps), start, total,
            kp, vp, bt, window=w, impl=impl, block_start=st,
            bucket_plans=plans, bucket_perms=perms, plan_class=c,
            k_scales=ks, v_scales=vs,
            **_attn_kwargs(cfg),
        )
        if quantized:
            h, kp, vp, ks, vs = res
        else:
            h, kp, vp = res
        xc = xc + h
        hin = rmsnorm(lp["ln2"], xc, cfg.norm_eps)
        if cfg.n_experts:
            h2, _ = moe_forward(
                lp["moe"], hin, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, act=cfg.act,
            )
        else:
            h2 = _ffn(lp, hin, cfg)
        return xc + h2, ((kp, vp, ks, vs) if quantized else (kp, vp))

    xs = (params["layers"], windows, cls, block_table, block_start,
          k_pages, v_pages)
    if quantized:
        x, (k_pages, v_pages, k_scales, v_scales) = jax.lax.scan(
            body, x, xs + (k_scales, v_scales)
        )
    else:
        x, (k_pages, v_pages) = jax.lax.scan(body, x, xs)
    if last_pos is None:
        xe = x[:, -1:]
    else:
        xe = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(last_pos, jnp.int32), 1, axis=1
        )
    logits = _head(params, xe, cfg)
    if quantized:
        return logits, k_pages, v_pages, k_scales, v_scales
    return logits, k_pages, v_pages


def _cache_len(cache: Cache, cfg: ModelConfig) -> int:
    if "k" in cache:
        return cache["k"].shape[2]
    if "shared_k" in cache:
        return cache["shared_k"].shape[2]
    return 1  # pure-recurrent archs have no positional cache
