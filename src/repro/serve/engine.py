"""Serving engine: prefill + decode with PIM-quantized weights.

The decode step is the paper's workload — per-token GEMVs against
resident weights. `ServeEngine.quantize()` converts the projection
weights to packed bit-planes (PimWeight), after which every decode matmul
runs through the bit-plane kernel path (interpret-mode Pallas on CPU,
native on TPU), cutting decode HBM traffic by 16/n_bits.

`ServeConfig.paged=True` swaps the dense pre-allocated KV cache for the
block-paged cache (serve.paged_cache, DESIGN.md §8): decode attention
gathers pages through a block table with per-slot positions. The dense
path remains the default fallback.

`ServeConfig.bucket_strategy="pow2"` (the default) routes every paged
kernel launch through the length-bucketed dispatch (DESIGN.md §11):
slots are packed into power-of-two page-occupancy buckets per launch so
the block walk never streams a slot's dead tail pages; `"none"` keeps
the single full-depth launch.

`ServeConfig.eos_token >= 0` enables early stopping: a sequence that
emits the EOS token stops decoding (the EOS itself is kept in the
output), and generation returns as soon as every batch row has stopped —
rows that finished earlier are padded with the EOS token, so the
returned width is the number of decode iterations actually run, not
`max_new_tokens`. In the paged path a stopped row also releases its
pages immediately; its slot's block table falls back to the scratch
page, which absorbs the remaining ticks' unconditional KV scatters.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..kernels.ops import bucket_args_grouped, resolve_bucket_strategy
from ..obs import ServeTelemetry
from ..quant.bitplane import PimQuantConfig, quantize_tree, tree_packed_fraction
from .compiled import (
    jit_dense_decode,
    jit_dense_prefill,
    jit_paged_decode,
    jit_paged_prefill,
)
from .paged_cache import PagedKVCache


@dataclasses.dataclass
class ServeConfig:
    max_cache_len: int = 512
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 = greedy
    eos_token: int = -1       # -1 = never stop early
    paged: bool = False       # block-paged KV cache (per-slot positions)
    block_size: int = 16      # KV page size in tokens (paged mode)
    kernel_impl: str = "auto"  # paged-attention kernel path (resolve_impl)
    #: length-bucketed kernel dispatch (DESIGN.md §11): "pow2" bounds
    #: every paged launch at its bucket's page occupancy; "none" keeps
    #: the single full-depth launch
    bucket_strategy: str = "pow2"
    #: KV page-pool storage (DESIGN.md §16): "bf16" keeps the compute
    #: dtype; "int8" stores per-page-scaled quantized pages (paged only)
    kv_dtype: str = "bf16"
    #: chunked prefill (DESIGN.md §17): > 0 splits the prompt into
    #: block-multiple chunks of at most this many tokens, prefilled as
    #: successive launches, so a windowed group's transient allocation
    #: caps at window + chunk instead of the full prompt. 0 = one shot.
    prefill_chunk: int = 0
    #: per-group live-draw slack on top of ceil(window/bs) (§17);
    #: None derives the exact worst case from prefill_chunk
    group_pool_slack: Optional[int] = None
    #: per-group pool sizing (§17): None = uniform, "auto" sizes each
    #: retiring windowed group at n_slots * live_bound, or {gid: n}
    group_blocks: Any = None


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params: Any, serve_cfg: ServeConfig,
                 telemetry: Optional[ServeTelemetry] = None):
        self.cfg = cfg
        self.sc = serve_cfg
        self.params = params
        self.packed_fraction = 0.0
        #: observability facade (DESIGN.md §13); None = metrics off,
        #: every hook site guards on it (zero registry calls on the
        #: uninstrumented path)
        self.telemetry = telemetry
        #: monotone uid base so rows of successive generate() calls get
        #: distinct trace uids
        self._uid_base = 0
        watcher = None if telemetry is None else telemetry.compile_watcher()
        self._prefill = jit_dense_prefill(
            cfg, serve_cfg.max_cache_len, watcher=watcher
        )
        self._decode = jit_dense_decode(cfg, watcher=watcher)
        self._decode_paged = jit_paged_decode(
            cfg, impl=serve_cfg.kernel_impl, watcher=watcher,
            kv_dtype=serve_cfg.kv_dtype,
        )
        self._prefill_paged = jit_paged_prefill(
            cfg, impl=serve_cfg.kernel_impl, watcher=watcher,
            kv_dtype=serve_cfg.kv_dtype,
        )
        if serve_cfg.kv_dtype != "bf16" and not serve_cfg.paged:
            raise ValueError("kv_dtype='int8' requires paged=True")
        resolve_bucket_strategy(serve_cfg.bucket_strategy)

    def _trace_admit(self, b: int, prompt_tokens: int) -> list:
        """Open one trace per batch row (the engine's generate() admits
        the whole batch at once — submit and admit coincide)."""
        uids = list(range(self._uid_base, self._uid_base + b))
        self._uid_base += b
        tel = self.telemetry
        if tel is None:
            return uids
        for slot, uid in enumerate(uids):
            tel.on_submit(uid, prompt_tokens, self.sc.max_new_tokens)
            tel.on_admit(uid, slot)
        return uids

    def quantize(self, qcfg: Optional[PimQuantConfig] = None) -> float:
        """Convert projection weights to PIM-resident bit-planes."""
        qcfg = qcfg or PimQuantConfig(
            n_bits=self.cfg.quant_bits, group=self.cfg.quant_group,
            min_features=1,
        )
        self.params = quantize_tree(self.params, qcfg)
        self.packed_fraction = tree_packed_fraction(self.params)
        return self.packed_fraction

    # -- EOS bookkeeping ---------------------------------------------------

    def _eos_hits(self, tok: jnp.ndarray) -> np.ndarray:
        """[B] bool: which rows of a [B, 1] token batch just emitted EOS."""
        if self.sc.eos_token < 0:
            return np.zeros((tok.shape[0],), bool)
        return np.asarray(tok[:, 0]) == self.sc.eos_token

    def _pad_done(self, tok: jnp.ndarray, done: np.ndarray) -> jnp.ndarray:
        """Rows that already stopped keep emitting EOS (output padding)."""
        if self.sc.eos_token < 0 or not done.any():
            return tok
        return jnp.where(
            jnp.asarray(done)[:, None], jnp.int32(self.sc.eos_token), tok
        )

    def generate(
        self, prompts: jnp.ndarray, rng: Optional[jax.Array] = None
    ) -> jnp.ndarray:
        """Greedy/temperature generation for a [B, T] prompt batch.
        Returns [B, n] with n <= max_new_tokens when eos_token stops every
        row early."""
        if self.sc.paged:
            return self._generate_paged(prompts, rng)
        b, t = prompts.shape
        tel = self.telemetry
        uids = self._trace_admit(b, t) if tel is not None else None
        logits, cache = self._prefill(self.params, prompts)
        out = []
        done = np.zeros((b,), bool)
        tok = self._sample(logits[:, -1], rng)
        if tel is not None:
            for uid in uids:
                tel.on_prefill(uid, t)
                tel.on_first_token(uid)
        for i in range(self.sc.max_new_tokens):
            tok = self._pad_done(tok, done)
            out.append(tok)
            newly = ~done & self._eos_hits(tok)
            done = done | newly
            if tel is not None:
                for r in np.flatnonzero(newly):
                    tel.on_finish(uids[r])
            if done.all() or i == self.sc.max_new_tokens - 1:
                break  # the last appended token needs no follow-up decode
            logits, cache = self._decode(self.params, tok, cache)
            if tel is not None:
                tel.on_decode([uids[r] for r in np.flatnonzero(~done)])
                tel.end_tick(0, int((~done).sum()))
            tok = self._sample(logits[:, -1], rng)
        if tel is not None:
            for r in np.flatnonzero(~done):
                tel.on_finish(uids[r])  # budget-finished rows
        return jnp.concatenate(out, axis=-1)

    def _generate_paged(
        self, prompts: jnp.ndarray, rng: Optional[jax.Array]
    ) -> jnp.ndarray:
        """Paged generation end-to-end: prefill writes straight into the
        page pools through the block table (models.prefill_paged) — no
        dense cache allocation and no device→host→device copy of the
        prompt KV, which the old path paid per generate call."""
        b, t = prompts.shape
        bs = self.sc.block_size
        tel = self.telemetry
        uids = self._trace_admit(b, t) if tel is not None else None
        pc = PagedKVCache(
            self.cfg, n_slots=b, max_len=self.sc.max_cache_len,
            block_size=bs, kv_dtype=self.sc.kv_dtype,
            prefill_chunk=self.sc.prefill_chunk,
            group_pool_slack=self.sc.group_pool_slack,
            group_blocks=self.sc.group_blocks,
        )
        # whole-batch prefill, chunked when prefill_chunk > 0 (§17): the
        # batch is length-uniform, so every row advances through the
        # same [start, end) spans; with one chunk the loop body is the
        # original single-shot launch verbatim. Each chunk's KV scatters
        # into the pages before its queries read back through the block
        # table, so tokens are bit-exact vs the single shot, while a
        # windowed group's transient allocation caps at window + chunk.
        chunk = pc.prefill_chunk or t
        zeros = jnp.zeros((b,), jnp.int32)
        logits = None
        start = 0
        while start < t:
            end = min(start + chunk, t)
            n = end - start
            pad = -(-n // bs) * bs
            for i in range(b):
                # retire window-dead blocks, grow capacity for the chunk
                pc.begin_append(i, start, n)
            toks = jnp.pad(prompts[:, start:end], ((0, 0), (0, pad - n)))
            eff = np.full((b,), end)
            plans, perms = self._bucket_args(pc, eff)
            if tel is not None:
                tel.account_paged_launch(
                    "prefill", plans, b, pc, eff_lengths=eff,
                    strategy=self.sc.bucket_strategy,
                    kernel_impl=self.sc.kernel_impl,
                )
            if pc.quantized:
                (logits, pc.k_pages, pc.v_pages,
                 pc.k_scales, pc.v_scales) = self._prefill_paged(
                    self.params, toks, pc.k_pages, pc.v_pages,
                    pc.k_scales, pc.v_scales,
                    pc.device_block_tables(), pc.device_block_starts(),
                    zeros + start, zeros + end,
                    jnp.asarray(n - 1, jnp.int32), perms, plans=plans,
                )
            else:
                logits, pc.k_pages, pc.v_pages = self._prefill_paged(
                    self.params, toks, pc.k_pages, pc.v_pages,
                    pc.device_block_tables(), pc.device_block_starts(),
                    zeros + start, zeros + end,
                    jnp.asarray(n - 1, jnp.int32), perms, plans=plans,
                )
            pc.lengths[:] = end
            if tel is not None:
                for uid in uids:
                    tel.on_prefill(uid, pad)
            start = end
        out = []
        done = np.zeros((b,), bool)
        tok = self._sample(logits[:, -1], rng)
        if tel is not None:
            for uid in uids:
                tel.on_first_token(uid)
        for it in range(self.sc.max_new_tokens):
            tok = self._pad_done(tok, done)
            out.append(tok)
            for i in np.flatnonzero(self._eos_hits(tok) & ~done):
                # a stopped row releases its pages immediately; its table
                # falls back to scratch, which absorbs later KV scatters
                pc.free_slot(int(i))
                done[i] = True
                if tel is not None:
                    tel.on_finish(uids[i])
            if done.all() or it == self.sc.max_new_tokens - 1:
                break  # the last appended token needs no follow-up decode
            for i in range(b):
                if not done[i]:
                    # grows capacity, COWs shared tail pages, and retires
                    # window-dead blocks per layer group (DESIGN.md §12)
                    pc.begin_append(i, int(pc.lengths[i]), 1)
            plans, perms = self._bucket_args(pc, pc.lengths + 1)
            if tel is not None:
                tel.account_paged_launch(
                    "decode", plans, b, pc, eff_lengths=pc.lengths + 1,
                    strategy=self.sc.bucket_strategy,
                    kernel_impl=self.sc.kernel_impl,
                )
            if pc.quantized:
                (logits, pc.k_pages, pc.v_pages,
                 pc.k_scales, pc.v_scales) = self._decode_paged(
                    self.params, tok, pc.k_pages, pc.v_pages,
                    pc.k_scales, pc.v_scales,
                    pc.device_block_tables(), pc.device_block_starts(),
                    pc.device_positions(), perms, plans=plans,
                )
            else:
                logits, pc.k_pages, pc.v_pages = self._decode_paged(
                    self.params, tok, pc.k_pages, pc.v_pages,
                    pc.device_block_tables(), pc.device_block_starts(),
                    pc.device_positions(), perms, plans=plans,
                )
            for i in range(b):
                if not done[i]:
                    pc.lengths[i] += 1
            if tel is not None:
                tel.on_decode([uids[r] for r in np.flatnonzero(~done)])
                tel.end_tick(
                    0, int((~done).sum()),
                    pool_gauges=pc.pool_gauges(),
                    dedup=pc.cross_layer_dedup_stats(),
                    occupancy=pc.slot_occupancy(),
                )
            tok = self._sample(logits[:, -1], rng)
        if tel is not None:
            for r in np.flatnonzero(~done):
                tel.on_finish(uids[r])  # budget-finished rows
        return jnp.concatenate(out, axis=-1)

    def _bucket_args(self, pc: PagedKVCache, eff_lengths):
        """Per-group slot→bucket packing for one launch (DESIGN.md
        §11-§12): the shared `ops.bucket_args_grouped` policy over this
        call's layer-major pools."""
        return bucket_args_grouped(
            self.sc.bucket_strategy, self.sc.kernel_impl,
            pc.bucket_needs(eff_lengths), pc.max_blocks_per_slot,
        )

    def _sample(self, logits: jnp.ndarray, rng) -> jnp.ndarray:
        if self.sc.temperature <= 0.0 or rng is None:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        probs = jax.nn.softmax(logits / self.sc.temperature, axis=-1)
        return jax.random.categorical(rng, jnp.log(probs))[:, None].astype(jnp.int32)
