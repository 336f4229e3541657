"""Continuous-batching request scheduler (slot-based).

A fixed decode batch of `n_slots`; finished sequences release their slot
and a queued request is prefilled into it (batch-dim insert into the live
cache). One decode step always advances every active slot — the engine
never idles while requests are queued, which keeps the decode GEMV batch
(the paper's workload) full.

Two cache modes:

  dense (paged=False): the seed behaviour. The cache keeps one global
  write position, so all requests must share a (padded) prompt length and
  slots refilled after tick 0 write their KV at the global offset.

  paged (paged=True): block-paged KV with per-slot positions
  (DESIGN.md §8). Requests may have arbitrary distinct prompt lengths, a
  finished slot's pages are recycled through the free list, and a queued
  request is prefilled into a free slot at ANY tick without corrupting
  its KV placement. Prefill writes straight into the page pools through
  the jitted `prefill_paged` path — no dense cache allocation, no
  device→host→device copy.

  With `prefix=True` (paged only) a radix index over full KV pages
  (DESIGN.md §9) dedups shared prompt prefixes: admission looks the
  prompt up first, a hit maps the leading pages refcounted-shared into
  the slot's table, prefill runs on the uncached suffix only, and the
  completed pages are published back to the index for future requests.

Admission scans the queue for the FIRST request the pool can admit
(FIFO among admissible) instead of blocking on the queue head — a large
request waiting for pages no longer starves small ones behind it.

Liveness: a slot whose request completes AT prefill (max_new_tokens=1,
or an EOS continuation) frees its pages and is retried immediately, so
the freed pages can admit a queued request within the same tick; and
`run_until_drained` raises the moment a tick advances nothing and
admits nothing while requests are queued (a deadlock — nothing can ever
free pages) instead of spinning out the tick budget.

`eos_token >= 0` stops a slot early when it emits that token: the EOS
is kept in the output and the slot's pages recycle the same tick.

Every paged kernel launch goes through the length-bucketed dispatch
layer (DESIGN.md §11) unless `bucket_strategy="none"`: each tick the
scheduler packs slots into power-of-two page-occupancy buckets
(`kernels.ops.make_bucket_plan`) and the compiled step launches one
kernel per bucket, bounded at the bucket depth — a slot holding 2 pages
of a 64-page-deep table no longer streams 62 dead tail pages per layer.
On CPU with `kernel_impl="auto"` the oracle path runs and the plan is
inert, so tokens are unchanged either way (they are bit-identical on
the kernel paths too — the cut tail pages fold as exact no-ops).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs.base import ModelConfig
from ..kernels.ops import bucket_args_grouped, resolve_bucket_strategy
from ..models import init_cache
from ..obs import SPANS, ServeTelemetry
from .compiled import (
    jit_dense_decode,
    jit_dense_prefill,
    jit_paged_decode,
    jit_paged_prefill,
)
from .paged_cache import PagedKVCache
from .prefix_cache import PrefixIndex

_TICK, _ADMIT, _PREFILL_LAUNCH, _FIRST_TOKEN, _DECODE_LAUNCH, _TOKEN_SYNC = \
    SPANS


@dataclasses.dataclass
class Request:
    uid: int
    prompt: jnp.ndarray          # [T] int32
    max_new_tokens: int = 16
    generated: List[int] = dataclasses.field(default_factory=list)
    #: memoized prefix-index block keys — a queued request is re-probed
    #: every admission tick, but its prompt never changes
    block_keys: Optional[List[Tuple[int, ...]]] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens


def _insert_batch(cache_tree, slot_tree, idx: int):
    """Write a batch-1 cache into slot `idx` of a batch-N cache."""
    def ins(full, one):
        if getattr(full, "ndim", 0) == 0 or full.ndim == getattr(one, "ndim", 0) - 1:
            return full  # scalars (position) stay global
        # batch axis: attn caches [L, B, ...], recurrent states [L, B, ...]
        return jax.lax.dynamic_update_slice_in_dim(full, one.astype(full.dtype), idx, axis=1)

    out = {}
    for k in cache_tree:
        if k == "position":
            out[k] = cache_tree[k]
        else:
            out[k] = ins(cache_tree[k], slot_tree[k])
    return out


class ContinuousBatcher:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        n_slots: int,
        cache_len: int,
        prompt_len: Optional[int] = None,
        *,
        paged: bool = False,
        block_size: int = 16,
        n_blocks: int = 0,
        prefix: bool = False,
        eos_token: int = -1,
        kernel_impl: str = "auto",
        bucket_strategy: str = "pow2",
        prefix_max_retained_fraction: float = 1.0,
        window_retirement: bool = True,
        kv_dtype: str = "bf16",
        prefill_chunk: int = 0,
        group_pool_slack: Optional[int] = None,
        group_blocks=None,
        telemetry: Optional[ServeTelemetry] = None,
    ):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.prompt_len = prompt_len
        self.paged = paged
        #: length-bucketed kernel dispatch (DESIGN.md §11): "pow2" packs
        #: slots into power-of-two occupancy buckets each tick so the
        #: paged kernels never stream a slot's dead tail pages; "none"
        #: keeps the PR-3 single launch over the full table depth.
        #: Plans are only built when a kernel path actually runs — the
        #: oracle ("ref", incl. auto-on-CPU) has no walk to bound, and
        #: building plans for it would recompile the step per plan for
        #: zero streamed-byte benefit. "pallas" stays strict lazily: the
        #: off-TPU raise happens at first launch, not construction.
        self.bucket_strategy = resolve_bucket_strategy(bucket_strategy)
        self._kernel_impl = kernel_impl
        #: -1 = never stop early; >= 0 = a slot that emits this token
        #: finishes immediately and frees its pages the same tick
        self.eos_token = eos_token
        #: observability facade (DESIGN.md §13). None (default) is the
        #: metrics-OFF contract: every instrumentation site below guards
        #: on it, so an uninstrumented drain makes ZERO registry calls
        #: on the hot path (asserted via obs.metrics.mutation_count)
        self.telemetry = telemetry
        self.queue: Deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.tokens = jnp.zeros((n_slots, 1), jnp.int32)
        self.finished: Dict[int, List[int]] = {}
        self.ticks = 0
        #: prompt tokens actually run through prefill compute (padded
        #: suffix lengths — prefix hits shrink this, benchmarked)
        self.prefill_tokens = 0
        if prefix and not paged:
            raise ValueError("prefix sharing requires paged=True")
        if kv_dtype != "bf16" and not paged:
            raise ValueError("kv_dtype='int8' requires paged=True")
        if (prefill_chunk or group_blocks is not None) and not paged:
            raise ValueError(
                "prefill_chunk / group_blocks require paged=True "
                "(chunked prefill and per-group sizing are page-pool "
                "machinery, DESIGN.md §17)"
            )
        #: KV pool storage dtype (DESIGN.md §16): "int8" threads the
        #: per-page scale stacks through every compiled step below
        self.kv_dtype = kv_dtype
        self.prefix = (
            PrefixIndex(
                block_size,
                max_retained_fraction=prefix_max_retained_fraction,
            )
            if prefix else None
        )
        # telemetry attached => compile-cache introspection on: every
        # XLA compile of a serve step is observed (DESIGN.md §14)
        watcher = None if telemetry is None else telemetry.compile_watcher()
        if paged:
            self.pcache = PagedKVCache(
                cfg, n_slots, max_len=cache_len, block_size=block_size,
                n_blocks=n_blocks, window_retirement=window_retirement,
                kv_dtype=kv_dtype, prefill_chunk=prefill_chunk,
                group_pool_slack=group_pool_slack,
                group_blocks=group_blocks,
            )
            self.cache = None
            self._decode_paged = jit_paged_decode(
                cfg, impl=kernel_impl, watcher=watcher, kv_dtype=kv_dtype,
            )
            # suffixes are right-padded to a block-size multiple, so this
            # retraces once per bucket and `last_pos` selects the true
            # suffix end dynamically
            self._prefill_paged = jit_paged_prefill(
                cfg, impl=kernel_impl, watcher=watcher, kv_dtype=kv_dtype,
            )
            #: chunked prefill (DESIGN.md §17): a prompt whose uncached
            #: suffix exceeds this many tokens prefills in block-multiple
            #: chunks, ONE chunk per tick, interleaved with decode — the
            #: cache already block-rounded the value. 0 = single-shot.
            self.prefill_chunk = self.pcache.prefill_chunk
        else:
            self.pcache = None
            self.cache = init_cache(cfg, n_slots, cache_len)
            self._decode = jit_dense_decode(cfg, watcher=watcher)
            self._prefill_dense = jit_dense_prefill(
                cfg, cache_len, watcher=watcher
            )
            self.prefill_chunk = 0
        #: slot -> next un-prefilled prompt position of an in-flight
        #: chunked prefill; such a slot is queue-busy but parked out of
        #: the decode active set until its final chunk lands
        self._chunk_pos: Dict[int, int] = {}

    def submit(self, req: Request):
        self.queue.append(req)
        if self.telemetry is not None:
            self.telemetry.on_submit(
                req.uid, int(req.prompt.shape[0]), req.max_new_tokens
            )

    # -- prefill -----------------------------------------------------------

    def _fill_slots(self):
        for i in range(self.n_slots):
            # a request can complete AT prefill (max_new_tokens == 1, or
            # the prompt's continuation is EOS): its pages free
            # immediately and the slot stays empty — retry the SAME slot,
            # since the freed pages may make a queued request admissible
            # this very tick instead of idling the slot for a whole tick
            while self.slots[i] is None and self.queue:
                if self.paged:
                    with TraceAnnotation(_ADMIT, slot=i,
                                         queued=len(self.queue)):
                        admitted = self._admit_paged(i)
                    if admitted is None:
                        # nothing in the queue fits right now; later slots
                        # see the same pool, so stop scanning this tick
                        return
                    req, attach_plan, n_cached = admitted
                    self._prefill_into_paged(i, req, attach_plan, n_cached)
                else:
                    self._prefill_into_dense(i, self.queue.popleft())

    # -- paged admission (reservation + prefix lookup) -----------------------

    def _try_reserve(self, slot: int, req: Request):
        """Reserve worst-case pages (prompt + all decode growth + COW)
        for `req` in EVERY layer group, after a prefix-index lookup.
        Returns (attach_plan, n_cached) on success — `attach_plan` the
        per-group page mapping of `PagedKVCache.plan_attach` (None for a
        miss) — or the per-group pool-draw deficit dict when some group
        cannot admit right now."""
        pc = self.pcache
        t = int(req.prompt.shape[0])
        total = t + max(req.max_new_tokens - 1, 0)
        attach_plan = None
        n_cached, cow = 0, False
        if self.prefix is not None:
            if req.block_keys is None:
                req.block_keys = self.prefix.block_keys(
                    np.asarray(req.prompt)
                )
            chain = self.prefix.lookup_chain(
                req.prompt, keys=req.block_keys
            )
            n_cached, cow = self.prefix.split_prompt(req.prompt, chain)
            if n_cached:
                nbh = -(-n_cached // pc.block_size)
                attach_plan = pc.plan_attach(
                    [n.pages for n in chain[:nbh]], n_cached
                )
                if attach_plan is None:
                    # some windowed group is missing a block its window
                    # still reaches — shrinking the hit only widens the
                    # reach, so take the miss path
                    n_cached, cow = 0, False
        if attach_plan is not None and n_cached:
            shared, n_cow = pc.attach_plan_counts(attach_plan, cow)
        else:
            attach_plan, shared, n_cow = None, 0, 0
        if pc.reserve_slot(slot, total, n_shared=shared, n_cow=n_cow):
            return attach_plan, n_cached
        return pc.reserve_deficits(total, shared, n_cow)

    def _admit_paged(self, slot: int):
        """First admissible queued request (FIFO among admissible): the
        admission check runs down the whole queue, so one large request
        waiting for pages cannot head-of-line-block small ones behind it.
        Cached index pages are only sacrificed as a last resort: a second
        pass evicts exactly a request's per-group missing draw counts and
        retries, and only runs when NOTHING was admissible without
        eviction."""
        pc = self.pcache
        deficits = []
        for qi in range(len(self.queue)):
            got = self._try_reserve(slot, self.queue[qi])
            if not isinstance(got, dict):
                req = self.queue[qi]
                del self.queue[qi]
                return (req,) + got
            deficits.append(got)
        if self.prefix is None:
            return None
        for qi, deficit in enumerate(deficits):
            if self.prefix.evict(pc, deficit):
                # the eviction may have dropped matched pages (they carry
                # the freshest stamps, so they go last) — redo lookup +
                # reservation from scratch
                got = self._try_reserve(slot, self.queue[qi])
                if not isinstance(got, dict):
                    req = self.queue[qi]
                    del self.queue[qi]
                    return (req,) + got
        return None

    def _prefill_into_dense(self, i: int, req: Request):
        if self.telemetry is not None:
            self.telemetry.on_admit(req.uid, i)
        t = int(req.prompt.shape[0])
        with TraceAnnotation(_PREFILL_LAUNCH, uid=req.uid, slot=i,
                             start=0, end=t):
            logits, c1 = self._prefill_dense(self.params,
                                             req.prompt[None, :])
            self.cache = _insert_batch(self.cache, c1, i)
        self.prefill_tokens += t
        if self.telemetry is not None:
            self.telemetry.on_prefill(req.uid, t)
        self._start_slot(i, req, logits)

    def _prefill_into_paged(
        self, i: int, req: Request, attach_plan, n_cached: int
    ):
        """Suffix-only prefill: attach the prefix-hit pages refcounted
        (per layer group — a windowed group maps only the blocks its
        window still reaches), COW/grow for the suffix window, run the
        jitted paged prefill on the uncached tokens, then publish the
        completed full-page blocks back to the index.

        Chunked prefill (DESIGN.md §17): when the uncached suffix
        exceeds `prefill_chunk`, only the FIRST chunk runs now — the
        rest advance one chunk per tick (`_advance_chunked`),
        interleaved with the decode batch, so a long prompt's windowed
        groups never hold more than window + chunk live tokens and the
        other slots keep decoding. Tokens are bit-exact vs single-shot:
        every chunk scatters its KV into the pages BEFORE the kernel
        reads back through the block table, so each query row folds the
        same stored bytes either way."""
        pc = self.pcache
        t = int(req.prompt.shape[0])
        if self.telemetry is not None:
            self.telemetry.on_admit(req.uid, i, n_cached)
        if attach_plan is not None:
            pc.attach_chain(i, attach_plan)
        if self.prefix is not None:
            self.prefix.lookups += 1
            self.prefix.hits += bool(n_cached)
            self.prefix.cached_tokens_served += n_cached
        if self.prefill_chunk and t - n_cached > self.prefill_chunk:
            end = n_cached + self.prefill_chunk
            self._launch_prefill_chunk(i, req, n_cached, end)
            self._chunk_pos[i] = end
            self.slots[i] = req       # queue-busy, parked out of decode
            return
        logits = self._launch_prefill_chunk(i, req, n_cached, t)
        self._finish_prefill(i, req, logits)

    def _launch_prefill_chunk(self, i: int, req: Request,
                              start: int, end: int):
        """One jitted prefill launch over prompt positions [start, end)
        of slot `i` — the single-shot path is just one chunk spanning
        the whole uncached suffix. The launch width pads to a block
        multiple, so the compile set stays bounded by the §11 pow2 plan
        machinery: mid chunks are always exactly `prefill_chunk` wide
        and only the tail chunk is ragged."""
        with TraceAnnotation(_PREFILL_LAUNCH, uid=req.uid, slot=i,
                             start=start, end=end):
            pc = self.pcache
            bs = pc.block_size
            n = end - start
            pad = -(-n // bs) * bs
            # host-side page prep BEFORE the device table snapshot:
            # retire blocks behind the chunk's window, grow capacity for
            # the chunk, COW any shared page the scatter touches
            pc.begin_append(i, start, n)
            toks = jnp.pad(req.prompt[start:end], (0, pad - n))[None, :]
            # bucket the one-slot launch by the slot's LIVE page
            # occupancy per layer group so the prefill walk stops at the
            # bucket bound instead of streaming the slot's whole
            # max_blocks-deep table
            plans, perms = self._bucket_args([end], slots=[i])
            bt, st = pc.device_block_tables(), pc.device_block_starts()
            if bt.ndim == 2:               # single group: [B, mb] / [B]
                bt, st = bt[i: i + 1], st[i: i + 1]
            else:                          # layer-major: [L, B, mb] / [L, B]
                bt, st = bt[:, i: i + 1], st[:, i: i + 1]
            if pc.quantized:
                (logits, pc.k_pages, pc.v_pages,
                 pc.k_scales, pc.v_scales) = self._prefill_paged(
                    self.params, toks, pc.k_pages, pc.v_pages,
                    pc.k_scales, pc.v_scales, bt, st,
                    jnp.asarray([start], jnp.int32),
                    jnp.asarray([end], jnp.int32),
                    jnp.asarray(n - 1, jnp.int32), perms, plans=plans,
                )
            else:
                logits, pc.k_pages, pc.v_pages = self._prefill_paged(
                    self.params, toks, pc.k_pages, pc.v_pages, bt, st,
                    jnp.asarray([start], jnp.int32),
                    jnp.asarray([end], jnp.int32),
                    jnp.asarray(n - 1, jnp.int32), perms, plans=plans,
                )
            pc.lengths[i] = end
            self.prefill_tokens += pad
            if self.telemetry is not None:
                self.telemetry.on_prefill(req.uid, pad)
                # one-slot launch: n_rows=1 (the table snapshot was
                # sliced); geometry inputs let the perf model re-predict
                # the launch — per-chunk accounting right after the
                # chunk's begin_append reads the same live pool state the
                # plan was built from, so the §14 predicted-vs-measured
                # gate stays at exactly 0
                self.telemetry.account_paged_launch(
                    "prefill", plans, 1, pc, eff_lengths=[end], slots=[i],
                    strategy=self.bucket_strategy,
                    kernel_impl=self._kernel_impl,
                )
            return logits

    def _finish_prefill(self, i: int, req: Request, logits):
        """Post-prefill bookkeeping once the FULL prompt's KV is in the
        pages: publish completed blocks to the prefix index, then start
        (or immediately finish) the slot from the prefill logits."""
        if self.prefix is not None:
            self.prefix.publish(req.prompt, self.pcache, i,
                                keys=req.block_keys)
        self._start_slot(i, req, logits)

    def _advance_chunked(self) -> int:
        """Advance every in-flight chunked prefill by ONE chunk; a slot
        whose final chunk lands gets its first token this tick (and may
        decode this very tick, matching the single-shot path's
        prefill-then-decode tick shape). Returns slots advanced — chunk
        progress counts for the drain loop's liveness check."""
        advanced = 0
        for i in sorted(self._chunk_pos):
            req = self.slots[i]
            pos = self._chunk_pos[i]
            t = int(req.prompt.shape[0])
            end = min(pos + self.prefill_chunk, t)
            logits = self._launch_prefill_chunk(i, req, pos, end)
            advanced += 1
            if end >= t:
                del self._chunk_pos[i]
                self.slots[i] = None  # _finish_prefill re-seats or ends
                self._finish_prefill(i, req, logits)
            else:
                self._chunk_pos[i] = end
        return advanced

    def _hit_eos(self, tok: int) -> bool:
        return self.eos_token >= 0 and tok == self.eos_token

    def _start_slot(self, i: int, req: Request, logits):
        with TraceAnnotation(_FIRST_TOKEN, uid=req.uid, slot=i):
            nxt = int(jnp.argmax(logits[0, -1]))
            req.generated.append(nxt)
            if self.telemetry is not None:
                self.telemetry.on_first_token(req.uid)
            if req.done or self._hit_eos(nxt):
                # the prefill token completes the request (max_new_tokens
                # == 1, or the prompt's continuation is EOS) — entering
                # decode would emit an extra token (and write KV past the
                # slot's reservation); pages free immediately
                self.finished[req.uid] = req.generated
                if self.paged:
                    self.pcache.free_slot(i)
                if self.telemetry is not None:
                    self.telemetry.on_finish(req.uid)
                return
            self.tokens = self.tokens.at[i, 0].set(nxt)
            self.slots[i] = req

    # -- decode ------------------------------------------------------------

    def step(self) -> int:
        """One scheduler tick: advance in-flight chunked prefills one
        chunk each, fill free slots, decode once. Returns the number of
        slots advanced (decode + chunk progress — both count for the
        drain loop's liveness check)."""
        with TraceAnnotation(_TICK, tick=self.ticks):
            n_finished = len(self.finished)
            # chunks first: a finishing final chunk may free its slot (done
            # at prefill) for this very tick's admission pass below
            chunked = self._advance_chunked() if self._chunk_pos else 0
            self._fill_slots()
            active = [
                i for i, s in enumerate(self.slots)
                if s is not None and i not in self._chunk_pos
            ]
            if not active:
                if chunked or len(self.finished) > n_finished:
                    # prefill-only tick: chunk progress, or every admitted
                    # request completed AT prefill — real work, count it
                    self.ticks += 1
                if self.telemetry is not None:
                    self._sample_tick()
                return chunked
            with TraceAnnotation(_DECODE_LAUNCH, active=len(active)):
                if self.paged:
                    nxt = self._step_paged(active)
                else:
                    logits, self.cache = self._decode(self.params, self.tokens,
                                                      self.cache)
                    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            if self.telemetry is not None:
                self.telemetry.on_decode([self.slots[i].uid for i in active])
            with TraceAnnotation(_TOKEN_SYNC, active=len(active)):
                for i in active:
                    req = self.slots[i]
                    tok = int(nxt[i])
                    req.generated.append(tok)
                    if req.done or self._hit_eos(tok):
                        # finished by budget or by EOS: the slot's pages
                        # recycle this very tick, before the next
                        # _fill_slots admission
                        self.finished[req.uid] = req.generated
                        if self.paged:
                            self.pcache.free_slot(i)
                        self.slots[i] = None
                        if self.telemetry is not None:
                            self.telemetry.on_finish(req.uid)
                self.tokens = nxt[:, None]
            self.ticks += 1
            if self.telemetry is not None:
                self._sample_tick()
            return len(active) + chunked

    def _sample_tick(self):
        """End-of-tick gauge sample (telemetry attached only): queue
        depth, active slots, per-group pool state, dedup bytes, prefix
        index — everything the per-tick series and peak gauges need."""
        tel = self.telemetry
        if tel is None:
            return
        queued = len(self.queue)
        active = sum(s is not None for s in self.slots)
        if not self.paged:
            tel.end_tick(queued, active)
            return
        pc = self.pcache
        tel.end_tick(
            queued, active,
            pool_gauges=pc.pool_gauges(),
            dedup=pc.cross_layer_dedup_stats(),
            occupancy=pc.slot_occupancy(),
            prefix=None if self.prefix is None else self.prefix.stats(),
        )

    def _bucket_args(self, eff_lengths, slots=None):
        """Per-group slot→bucket packing for one launch (DESIGN.md
        §11-§12): global groups bucket by total occupancy, windowed
        groups by LIVE trailing pages (their retired head is skipped by
        the kernels' walk start)."""
        return bucket_args_grouped(
            self.bucket_strategy, self._kernel_impl,
            self.pcache.bucket_needs(eff_lengths, slots),
            self.pcache.max_blocks_per_slot,
        )

    def _step_paged(self, active: List[int]) -> jnp.ndarray:
        pc = self.pcache
        for i in active:  # page for the incoming token must exist (and be
            # exclusively owned — COW; window-dead blocks retire) before
            # the jitted scatter
            pc.begin_append(i, int(pc.lengths[i]), 1)
        # this decode attends over position + 1 kv rows per slot (idle
        # slots: 1 scratch row) — bucket the batch by that occupancy.
        # Mid-prefill (chunked) slots ride the batched decode like idle
        # slots: a scratch table row parks their unconditional KV
        # scatter in scratch page 0 — never in their half-written live
        # pages — and occupancy 1 keeps their dead weight out of the
        # launch's streamed bytes (§17)
        eff = pc.lengths + 1
        parked = sorted(self._chunk_pos)
        if parked:
            eff = np.array(eff)
            eff[parked] = 1
        plans, perms = self._bucket_args(eff)
        if self.telemetry is not None:
            self.telemetry.account_paged_launch(
                "decode", plans, self.n_slots, pc,
                eff_lengths=eff,
                strategy=self.bucket_strategy,
                kernel_impl=self._kernel_impl,
            )
        bt = pc.device_block_tables(scratch_slots=parked)
        st = pc.device_block_starts(scratch_slots=parked)
        pos = pc.device_positions(scratch_slots=parked)
        if pc.quantized:
            (logits, pc.k_pages, pc.v_pages,
             pc.k_scales, pc.v_scales) = self._decode_paged(
                self.params, self.tokens, pc.k_pages, pc.v_pages,
                pc.k_scales, pc.v_scales,
                bt, st, pos, perms, plans=plans,
            )
        else:
            logits, pc.k_pages, pc.v_pages = self._decode_paged(
                self.params, self.tokens, pc.k_pages, pc.v_pages,
                bt, st, pos, perms, plans=plans,
            )
        for i in active:
            pc.lengths[i] += 1
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    def _pool_diagnostic(self) -> str:
        """Per-layer-group pool state for the deadlock diagnostic — with
        layer-major pools a single global free count is meaningless: one
        starved group (usually the global layers) blocks admission while
        the windowed groups sit half empty. Reports each group's
        free-vs-promised draw ledger (free pages against each group's
        OWN pool size, reservations outstanding after retirement
        drawdown) and the head-of-queue request's per-group draw
        deficit, so a pool-sizing failure is diagnosable straight from
        the raised message (§17). The head-of-queue deficit is the
        no-prefix worst case — an actual admission pass may shrink it
        via prefix attach or index eviction."""
        if self.pcache is None:
            return ""
        pc = self.pcache
        per_group = ", ".join(
            f"g{p.gid}[{'global' if p.window is None else f'w={p.window}'}"
            f"×{len(p.layers)}L]: {p.n_free}/{p.n_blocks - 1} free, "
            f"{p.available_blocks()} unreserved, "
            f"{sum(r - p._drawn[s] for s, r in p._reserved.items())}"
            f" draws promised"
            for p in pc.pools
        )
        head = ""
        if self.queue:
            req = self.queue[0]
            t = int(req.prompt.shape[0])
            total = t + max(req.max_new_tokens - 1, 0)
            deficits = pc.reserve_deficits(total)
            short = ", ".join(
                f"g{g}:-{d}" for g, d in sorted(deficits.items())
            ) or "none"
            head = (
                f"; head-of-queue uid={req.uid} needs {total} tokens"
                f" ({t} prompt), per-group draw deficit: {short}"
            )
        return (
            f"; pools: {per_group}{head}; "
            f"occupancy={pc.slot_occupancy():.2f}"
        )

    def run_until_drained(
        self, max_ticks: int = 10_000, strict: bool = True, on_tick=None
    ) -> Dict[int, List[int]]:
        """Drain the queue. If `max_ticks` is exhausted with work still
        pending, raise RuntimeError (strict=True, default) or warn —
        never silently return partial results; completed requests stay
        available in `self.finished` either way. `on_tick(self)`, if
        given, runs after every tick — a measurement hook (e.g. sampling
        pool-sharing stats at their peak) that keeps callers out of the
        business of re-implementing this drain loop.

        A tick that advances zero slots, admits nothing AND frees no
        pages while requests are still queued is a livelock, not slow
        progress: with no active slot and an unchanged pool, no future
        tick can ever free pages, so spinning the remaining `max_ticks`
        would burn time and then mis-report the deadlock as a
        tick-budget problem. That state raises immediately (regardless
        of `strict`) with a pool-occupancy diagnostic. The free-count
        check matters with the prefix index: a failed admission may
        still have EVICTED index pages, which a later tick's smaller
        deficit can turn into an admission."""
        ticks = 0
        while (self.queue or any(s is not None for s in self.slots)) and ticks < max_ticks:
            queued_before = len(self.queue)
            free_before = self.pcache.free_state() if self.paged else ()
            advanced = self.step()
            ticks += 1
            if on_tick is not None:
                on_tick(self)
            if (
                advanced == 0
                and self.queue
                and len(self.queue) == queued_before
                and (not self.paged
                     or self.pcache.free_state() == free_before)
            ):
                diagnostic = self._pool_diagnostic()
                if self.telemetry is not None:
                    # machine-readable twin of the exception message —
                    # the raise below keeps its wording untouched
                    self.telemetry.on_deadlock(
                        ticks, len(self.queue), len(self.finished),
                        {p.gid: p.n_free for p in self.pcache.pools}
                        if self.paged else {},
                        diagnostic,
                    )
                raise RuntimeError(
                    f"run_until_drained: deadlock at tick {ticks} — no "
                    f"slot is active and none of the {len(self.queue)} "
                    f"queued requests is admissible, so no future tick "
                    f"can free pages or make progress "
                    f"({len(self.finished)} finished)"
                    f"{diagnostic}"
                )
        pending = len(self.queue) + sum(s is not None for s in self.slots)
        if pending:
            msg = (
                f"run_until_drained: exhausted max_ticks={max_ticks} with "
                f"{len(self.queue)} queued and "
                f"{sum(s is not None for s in self.slots)} active requests "
                f"({len(self.finished)} finished)"
            )
            if strict:
                raise RuntimeError(msg)
            import warnings

            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return self.finished
