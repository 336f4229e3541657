"""Layer-major paged KV pools (DESIGN.md §12): per-group block tables,
window-aware page retirement, walk-start kernels, per-group prefix
dedup, and COW independence between layer groups.

The end-to-end anchor is a mixed global/window config (the gemma3 5:1
local:global smoke shape): greedy tokens must be bit-identical across
{oracle, interpreted kernel} x {bucketed, single-launch} x {retirement
on, off} — retired columns are window-masked, so the layout never
changes the math — while the windowed groups' resident pages shrink.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import init_lm, layer_attn_groups, layer_group_index
from repro.serve import ContinuousBatcher, PagedKVCache, PrefixIndex, Request

WINDOWED_ARCH = "gemma3-27b"   # 5 local (window 8) : 1 global in smoke


def two_group_cfg() -> ModelConfig:
    """2 layers, layer 0 sliding-window(4), layer 1 global — the
    smallest cfg with two independent layer groups."""
    return ModelConfig(
        name="two-group", family="dense", n_layers=2, d_model=8,
        n_heads=2, n_kv_heads=1, d_ff=16, vocab_size=32, dtype="float32",
        local_global_ratio=1, sliding_window=4,
    )


@pytest.fixture(scope="module")
def windowed_model():
    cfg = dataclasses.replace(
        get_config(WINDOWED_ARCH, smoke=True), dtype="float32"
    )
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def _prompt(uid: int, t: int, vocab: int) -> jnp.ndarray:
    return jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(17), uid), (t,), 0, vocab
    ).astype(jnp.int32)


def _stamp_kv(cfg, stamps, hd: int = 4):
    """[L, T, KV=1, hd] rows: layer l, position p holds l*1000 + stamp."""
    a = np.asarray(stamps, np.float32)[None, :, None, None]
    layer_off = (
        np.arange(cfg.n_layers, dtype=np.float32)[:, None, None, None] * 1000
    )
    return jnp.asarray(
        (a + layer_off) * np.ones((cfg.n_layers, len(stamps), 1, hd), np.float32)
    )


def _group_stamps(pc: PagedKVCache, gid: int, slot: int, positions):
    """Read back per-position stamps through ONE group's table, using
    that group's first layer's pool rows."""
    pool = np.asarray(pc.k_pages)
    g = pc.pools[gid]
    layer = g.layers[0]
    bs = pc.block_size
    out = []
    for p in positions:
        page = g._owned[slot][p // bs]
        assert page is not None, (gid, slot, p)
        out.append(float(pool[layer, page, 0, p % bs, 0]) - layer * 1000)
    return out


# ---------------------------------------------------------------------------
# group partition contract
# ---------------------------------------------------------------------------

def test_layer_groups_partition(windowed_model):
    cfg, _ = windowed_model
    groups = layer_attn_groups(cfg, capacity=64)
    # gemma3 smoke: 6 layers, i % 6 == 5 global, rest window 8
    assert groups == [(None, (5,)), (8, (0, 1, 2, 3, 4))]
    cls = layer_group_index(cfg, 64)
    assert cls.tolist() == [1, 1, 1, 1, 1, 0]
    # capacity <= window: every layer is effectively global -> one group
    assert layer_attn_groups(cfg, capacity=8) == [(None, tuple(range(6)))]
    # a config without sliding windows is always single-group at group 0
    plain = dataclasses.replace(cfg, local_global_ratio=0)
    assert layer_attn_groups(plain, 64) == [(None, tuple(range(6)))]


# ---------------------------------------------------------------------------
# kernels: walk-start (retired head skip) parity
# ---------------------------------------------------------------------------

def test_decode_walk_start_bit_exact(rng):
    """A depth-bounded walk starting at the first live block is
    bit-identical to the full walk AND matches the oracle: the retired
    head columns (scratch) are fully window-masked, and masked folds are
    exact no-ops in the online softmax."""
    from repro.kernels import ref
    from repro.kernels.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_bucketed,
    )

    B, H, KV, hd, bs, nb, mb = 3, 4, 2, 8, 4, 24, 6
    W = 5
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    bt = np.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), np.int32
    )
    lengths = np.asarray([22, 9, 24], np.int32)
    starts = np.maximum(0, (lengths - 1 - W + 1) // bs)  # retired blocks
    for i in range(B):
        bt[i, : starts[i]] = 0                           # head -> scratch
    win = jnp.asarray(W, jnp.int32)
    full = paged_decode_attention(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths), win,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(full),
        np.asarray(ref.paged_attention_ref(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths), win
        )),
        rtol=2e-5, atol=2e-5,
    )
    live_need = -(-lengths // bs) - starts
    depth = int(live_need.max())
    cut = paged_decode_attention(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths), win,
        block_start=jnp.asarray(starts), depth=depth, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cut))
    # bucketed by LIVE need (the §12 windowed plan), starts threaded
    plan, perm = ops.make_bucket_plan(None, bs, mb, needs=live_need)
    assert plan is not None
    bucketed = paged_decode_attention_bucketed(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths), win, plan, perm,
        block_start=jnp.asarray(starts), interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(full), np.asarray(bucketed))


def test_prefill_walk_start_bit_exact(rng):
    """Prefill analogue: suffix queries over a table whose window-dead
    head was skipped at attach — the bounded walk starting at the first
    live block matches the full walk bit-for-bit on valid rows."""
    from repro.kernels.paged_prefill import paged_prefill_attention

    B, T, H, KV, hd, bs, nb, mb = 2, 4, 4, 2, 8, 4, 20, 6
    W = 5
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    bt = np.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), np.int32
    )
    start = np.asarray([16, 12], np.int32)   # deep prefix hits
    total = np.asarray([20, 15], np.int32)
    # blocks dead for the earliest suffix query: (j+1)*bs - 1 <= start - W
    blk = np.maximum(0, (start - W + 1) // bs)
    for i in range(B):
        bt[i, : blk[i]] = 0
    win = jnp.asarray(W, jnp.int32)
    args = (q, kp, vp, jnp.asarray(bt), jnp.asarray(start),
            jnp.asarray(total), win)
    full = np.asarray(paged_prefill_attention(*args, interpret=True))
    live_need = -(-total // bs) - blk
    cut = np.asarray(paged_prefill_attention(
        *args, block_start=jnp.asarray(blk), depth=int(live_need.max()),
        interpret=True,
    ))
    for i in range(B):
        tv = max(0, min(T, int(total[i] - start[i])))
        np.testing.assert_array_equal(full[i, :tv], cut[i, :tv])


# ---------------------------------------------------------------------------
# cache: window-aware retirement
# ---------------------------------------------------------------------------

def test_window_retirement_frees_only_windowed_group():
    cfg = two_group_cfg()                      # layer 0: W=4, layer 1: global
    pc = PagedKVCache(cfg, n_slots=1, max_len=32, block_size=4)
    win_pool = next(p for p in pc.pools if p.window == 4)
    glob_pool = next(p for p in pc.pools if p.window is None)
    stamps = list(range(1, 13))
    pc.write_suffix(0, _stamp_kv(cfg, stamps), _stamp_kv(cfg, stamps), 0, 12)
    assert glob_pool.live_pages(0) == 3 and win_pool.live_pages(0) == 3
    # decode forward to length 20: window 4 keeps ~2 trailing blocks live
    for _ in range(8):
        pc.append_position(0)
    pc.check_invariants()
    assert glob_pool.live_pages(0) == 5             # global never retires
    assert win_pool.live_pages(0) < 5               # windowed retired head
    assert win_pool.pages_retired > 0
    assert int(win_pool.first_block[0]) > 0
    # retired columns fell back to scratch; live trailing stamps intact
    assert all(
        win_pool.block_table[0, j] == 0
        for j in range(int(win_pool.first_block[0]))
    )
    live_lo = int(win_pool.first_block[0]) * 4
    assert _group_stamps(pc, win_pool.gid, 0, range(live_lo, 12)) == \
        stamps[live_lo:]
    assert _group_stamps(pc, glob_pool.gid, 0, range(12)) == stamps
    # layer-major resident accounting beats the lockstep equivalent
    assert pc.resident_page_bytes() < pc.lockstep_equiv_page_bytes()
    pc.free_slot(0)
    pc.check_invariants()
    assert all(p.n_free == pc.n_blocks - 1 for p in pc.pools)


def test_window_retirement_off_is_lockstep_residency():
    cfg = two_group_cfg()
    pc = PagedKVCache(cfg, n_slots=1, max_len=32, block_size=4,
                      window_retirement=False)
    pc.alloc_slot(0, 12)
    pc.lengths[0] = 12
    for _ in range(8):
        pc.append_position(0)
    pc.check_invariants()
    assert pc.pages_retired == 0
    assert pc.resident_page_bytes() == pc.lockstep_equiv_page_bytes()


# ---------------------------------------------------------------------------
# cache: per-group COW independence (ISSUE satellite)
# ---------------------------------------------------------------------------

def test_cow_in_one_group_never_touches_the_other():
    """A write that COWs the GLOBAL group's shared page must not copy or
    touch any page of the WINDOWED group (content-stamp readback per
    layer), and vice versa the windowed group's exclusively-owned page is
    written in place."""
    cfg = two_group_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4)
    win_pool = next(p for p in pc.pools if p.window == 4)
    glob_pool = next(p for p in pc.pools if p.window is None)
    stamps = [1, 2, 3, 4, 5]
    pc.write_suffix(0, _stamp_kv(cfg, stamps), _stamp_kv(cfg, stamps), 0, 5)
    # share ONLY the global group's first page into slot 1 (the shape a
    # deep-window prefix hit produces: the windowed group skipped it)
    donor_glob = glob_pool._owned[0][0]
    pc.attach_chain(1, {
        glob_pool.gid: (0, [donor_glob]),
        win_pool.gid: (0, []),
    })
    win_bytes_before = np.asarray(pc.k_pages)[list(win_pool.layers)].copy()
    win_alloc_before = win_pool.pages_allocated
    # slot 1 writes mid-page at position 3: the GLOBAL group COWs its
    # shared page; the windowed group draws fresh pages (nothing shared
    # there — no COW, and no other windowed page may be touched)
    pc.write_suffix(1, _stamp_kv(cfg, [77, 88]), _stamp_kv(cfg, [77, 88]),
                    3, 2)
    assert glob_pool.cow_events == 1
    assert win_pool.cow_events == 0
    assert glob_pool._owned[1][0] != donor_glob      # private copy
    # donor slot's bytes untouched in BOTH groups
    assert _group_stamps(pc, glob_pool.gid, 0, range(5)) == stamps
    assert _group_stamps(pc, win_pool.gid, 0, range(5)) == stamps
    # slot 1's global view: shared head + its write
    assert _group_stamps(pc, glob_pool.gid, 1, range(5)) == [1, 2, 3, 77, 88]
    # the windowed group's PRE-EXISTING pages are bit-untouched: only the
    # pages slot 1 freshly drew changed
    win_after = np.asarray(pc.k_pages)[list(win_pool.layers)]
    fresh = [p for p in win_pool._owned[1] if p is not None]
    untouched = [p for p in range(pc.n_blocks) if p not in fresh]
    np.testing.assert_array_equal(
        win_bytes_before[:, untouched], win_after[:, untouched]
    )
    assert win_pool.pages_allocated == win_alloc_before + 2
    assert len(fresh) == 2
    pc.check_invariants()


@given(st.data())
@settings(deadline=None, max_examples=25)
def test_two_group_random_ops_keep_invariants_and_content(data):
    """Random start/append/free sequences on a two-group cache: every
    group's refcount/free-list accounting stays exact after every op
    (per-pool check_invariants), a write to one slot never corrupts
    another slot's readback in EITHER group, and windowed retirement
    never drops a live (in-window) position."""
    cfg = two_group_cfg()
    bs, max_len = 4, 24
    pc = PagedKVCache(cfg, n_slots=3, max_len=max_len, block_size=bs,
                      n_blocks=20)
    win_pool = next(p for p in pc.pools if p.window == 4)
    expected = {}
    next_stamp = [1.0]

    def fresh(n):
        out = [next_stamp[0] + i for i in range(n)]
        next_stamp[0] += n
        return out

    def check_content():
        for slot, exp in expected.items():
            n = len(exp)
            for p in pc.pools:
                if p.retire_window is None:
                    lo = 0
                else:
                    lo = int(p.first_block[slot]) * bs
                    # retirement may only drop positions behind the
                    # window of the NEXT query (position n)
                    assert lo <= max(0, n - p.retire_window)
                assert _group_stamps(pc, p.gid, slot, range(lo, n)) == \
                    exp[lo:], (p.gid, slot)

    for _ in range(data.draw(st.integers(4, 12), label="n_ops")):
        live = sorted(expected)
        empty = [s for s in range(3) if s not in expected]
        ops_ = []
        if empty and min(p.n_free for p in pc.pools) >= max_len // bs:
            ops_.append("start")
        if live:
            ops_.append("free")
            if min(p.n_free for p in pc.pools) >= 2:
                ops_.append("append")
        if not ops_:
            break
        op = data.draw(st.sampled_from(ops_), label="op")
        if op == "start":
            slot = data.draw(st.sampled_from(empty), label="slot")
            n = data.draw(st.integers(1, max_len), label="n")
            stamps = fresh(n)
            pc.write_suffix(slot, _stamp_kv(cfg, stamps),
                            _stamp_kv(cfg, stamps), 0, n)
            expected[slot] = stamps
        elif op == "append":
            slot = data.draw(st.sampled_from(live), label="slot")
            n = len(expected[slot])
            if n >= max_len:
                continue
            stamps = fresh(1)
            pc.write_suffix(slot, _stamp_kv(cfg, stamps),
                            _stamp_kv(cfg, stamps), n, 1)
            expected[slot] += stamps
        else:
            slot = data.draw(st.sampled_from(live), label="slot")
            pc.free_slot(slot)
            del expected[slot]
        pc.check_invariants({})
        check_content()

    for slot in sorted(expected):
        pc.free_slot(slot)
    pc.check_invariants({})
    # per-group free-list conservation: every page recycled in every pool
    assert all(p.n_free == pc.n_blocks - 1 for p in pc.pools)
    assert win_pool.pages_retired >= 0


# ---------------------------------------------------------------------------
# cache: window-aware attach planning
# ---------------------------------------------------------------------------

def test_plan_attach_skips_dead_blocks_and_rejects_missing_live_ones():
    cfg = two_group_cfg()                  # W=4, bs=4 -> one block of slack
    pc = PagedKVCache(cfg, n_slots=2, max_len=32, block_size=4)
    win_pool = next(p for p in pc.pools if p.window == 4)
    glob_pool = next(p for p in pc.pools if p.window is None)
    stamps = list(range(1, 17))
    pc.write_suffix(0, _stamp_kv(cfg, stamps), _stamp_kv(cfg, stamps), 0, 16)
    chain = [pc.slot_block_pages(0, j) for j in range(4)]
    # deep hit (n_cached = 16): windowed group needs only blocks past
    # (16 - 4 + 1) // 4 = 3 -> attaches block 3 alone, skipping 3 dead
    plan = pc.plan_attach(chain, n_cached=16)
    assert plan is not None
    g_j0, g_pages = plan[glob_pool.gid]
    w_j0, w_pages = plan[win_pool.gid]
    assert (g_j0, len(g_pages)) == (0, 4)
    assert (w_j0, len(w_pages)) == (3, 1)
    shared, cow = pc.attach_plan_counts(plan, needs_cow=False)
    assert shared == {glob_pool.gid: 4, win_pool.gid: 4}  # dead count too
    # a chain MISSING a windowed block the window still reaches -> reject
    broken = [dict(d) for d in chain]
    del broken[3][win_pool.gid]
    assert pc.plan_attach(broken, n_cached=16) is None
    # ... but a missing DEAD block is fine
    broken2 = [dict(d) for d in chain]
    del broken2[0][win_pool.gid]
    assert pc.plan_attach(broken2, n_cached=16) is not None
    # shallow hit: every block within window reach -> full attach in both
    plan3 = pc.plan_attach(chain[:1], n_cached=4)
    assert plan3[win_pool.gid] == (0, [chain[0][win_pool.gid]])


def test_attach_chain_window_skip_roundtrip(windowed_model):
    """End-to-end on the gemma3 smoke config: a deep shared prefix is
    attached window-skipped — the windowed group holds fewer retains
    than the global group while tokens stay identical to the unshared
    run (the scheduler-level §12 dedup story)."""
    cfg, params = windowed_model
    pre = _prompt(99, 16, cfg.vocab_size)      # 4 blocks, window 8
    prompts = [
        jnp.concatenate([pre, _prompt(u, t, cfg.vocab_size)])
        for u, t in enumerate([5, 3])
    ]

    def drain(prefix):
        cb = ContinuousBatcher(
            cfg, params, n_slots=2, cache_len=48, paged=True, block_size=4,
            prefix=prefix,
        )
        for u, p in enumerate(prompts):
            cb.submit(Request(uid=u, prompt=p, max_new_tokens=4))
        res = cb.run_until_drained()
        if prefix:
            cb.pcache.check_invariants(cb.prefix.page_refs())
        else:
            cb.pcache.check_invariants()
        return res, cb

    res_u, _ = drain(False)
    res_s, cb = drain(True)
    assert res_u == res_s
    assert cb.prefix.hits >= 1
    # during the hit, the windowed pool attached fewer pages than the
    # global pool: its slot-2 attach skipped the dead head blocks, so its
    # allocation counter stayed lower
    win = next(p for p in cb.pcache.pools if p.window == 8)
    glob = next(p for p in cb.pcache.pools if p.window is None)
    assert win.pages_allocated <= glob.pages_allocated


# ---------------------------------------------------------------------------
# end-to-end: mixed global/window parity matrix
# ---------------------------------------------------------------------------

def _drain_matrix(cfg, params, *, impl, strategy, retire):
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=32, paged=True, block_size=4,
        kernel_impl=impl, bucket_strategy=strategy,
        window_retirement=retire,
    )
    for u, t in enumerate([5, 14, 22]):
        cb.submit(Request(uid=u, prompt=_prompt(u, t, cfg.vocab_size),
                          max_new_tokens=5))
    res = cb.run_until_drained()
    cb.pcache.check_invariants()
    return res, cb


def test_windowed_serving_parity_matrix(windowed_model):
    """Greedy tokens on the mixed global/window stack are identical
    across oracle/interpreted-kernel, bucketed/single-launch, and
    retirement on/off — and the retirement run actually retires."""
    cfg, params = windowed_model
    base, _ = _drain_matrix(cfg, params, impl="ref", strategy="none",
                            retire=False)
    for impl, strategy in (("ref", "pow2"), ("pallas_interpret", "pow2")):
        res, cb = _drain_matrix(cfg, params, impl=impl, strategy=strategy,
                                retire=True)
        assert res == base, (impl, strategy)
        assert cb.pcache.pages_retired > 0
        win_pools = [p for p in cb.pcache.pools if p.window is not None]
        assert sum(p.pages_retired for p in win_pools) == \
            cb.pcache.pages_retired
    # every page recycled in every group after the drain
    assert all(
        p.n_free == cb.pcache.n_blocks - 1 for p in cb.pcache.pools
    )


def test_deadlock_diagnostic_reports_per_group_pools(windowed_model):
    """ISSUE satellite: the run_until_drained deadlock diagnostic lists
    every layer group's free count — a single global number is
    meaningless once pools are per-group."""
    cfg, params = windowed_model
    cb = ContinuousBatcher(
        cfg, params, n_slots=1, cache_len=16, paged=True, block_size=4
    )
    glob = next(p for p in cb.pcache.pools if p.window is None)
    while glob.n_free > 1:
        glob._ref[glob.free_blocks.popleft()] = 1
    cb.submit(Request(uid=0, prompt=_prompt(0, 8, cfg.vocab_size),
                      max_new_tokens=4))
    with pytest.raises(RuntimeError) as ei:
        cb.run_until_drained()
    msg = str(ei.value)
    assert "g0[global" in msg and "g1[w=8" in msg, msg
    assert "1/4 free" in msg             # the starved global group
    assert "4/4 free" in msg             # the idle windowed group


# ---------------------------------------------------------------------------
# prefix index: per-group retention + scoring
# ---------------------------------------------------------------------------

def test_publish_retains_per_group_and_fill_in():
    cfg = two_group_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=32, block_size=4)
    win_pool = next(p for p in pc.pools if p.window == 4)
    glob_pool = next(p for p in pc.pools if p.window is None)
    ix = PrefixIndex(block_size=4)
    prompt = np.arange(8)
    # publisher that window-skipped block 0 (attach-like state): build it
    # by attaching only the global page for block 0
    stamps = list(range(1, 9))
    pc.write_suffix(0, _stamp_kv(cfg, stamps), _stamp_kv(cfg, stamps), 0, 8)
    # drop the windowed page of block 0 to emulate a deep-hit publisher
    win_pool.release(win_pool._owned[0][0])
    win_pool._owned[0][0] = None
    win_pool.block_table[0, 0] = 0
    win_pool.first_block[0] = 1
    added = ix.publish(prompt, pc, 0)
    assert added == 3                     # 2 global pages + 1 windowed
    chain = ix.lookup_chain(prompt)
    assert glob_pool.gid in chain[0].pages
    assert win_pool.gid not in chain[0].pages
    assert ix.retained_by_group[glob_pool.gid] == 2
    assert ix.retained_by_group[win_pool.gid] == 1
    pc.check_invariants(ix.page_refs())
    # a second publisher owning block 0 in BOTH groups fills the gap
    pc.write_suffix(1, _stamp_kv(cfg, stamps), _stamp_kv(cfg, stamps), 0, 8)
    assert ix.publish(prompt, pc, 1) == 1            # the fill-in retain
    assert win_pool.gid in ix.lookup_chain(prompt)[0].pages
    assert ix.retained_by_group[win_pool.gid] == 2
    pc.check_invariants(ix.page_refs())
    pc.free_slot(0)
    pc.free_slot(1)
    ix.drop_all(pc)
    pc.check_invariants({})
    assert all(p.n_free == pc.n_blocks - 1 for p in pc.pools)


def test_eviction_scoring_prefers_cold_heavy_nodes():
    """ISSUE satellite: eviction is hit-count x retained-bytes aware —
    a never-hit prefix is displaced before an older but repeatedly-hit
    one, and (via _evict_score) a node pinning more layers' bytes ranks
    below an equally-hit lighter node."""
    from repro.serve.prefix_cache import _Node

    cfg = two_group_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4, n_blocks=17)
    ix = PrefixIndex(block_size=4)
    hot, cold = np.arange(4), np.arange(100, 104)
    pc.alloc_slot(0, 4)
    ix.publish(hot, pc, 0)
    pc.free_slot(0)
    pc.alloc_slot(0, 4)
    ix.publish(cold, pc, 0)
    pc.free_slot(0)
    for _ in range(3):                    # the OLDER prefix is the hot one
        assert ix.lookup(hot) != []
    assert ix.evict(pc, 1) == len(pc.pools)   # one node = one page/group
    assert ix.lookup(hot) != []               # survived despite its age
    assert ix.lookup(cold) == []
    pc.check_invariants(ix.page_refs())
    # weight term: equal hits, more layer-bytes -> lower score
    heavy = _Node(key=(1,), pages={p.gid: 1 for p in pc.pools}, parent=None)
    light = _Node(key=(2,), pages={pc.pools[0].gid: 2}, parent=None)
    assert ix._evict_score(pc, heavy) < ix._evict_score(pc, light)
    ix.drop_all(pc)
    pc.check_invariants({})


def test_per_group_deficit_eviction_spares_unrelated_nodes():
    """Regression: an eviction driven by ONE group's deficit must not
    wipe index entries that hold no page in that group — even when
    value-density scoring ranks them as cheaper victims."""
    cfg = two_group_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4)
    win_pool = next(p for p in pc.pools if p.window == 4)
    glob_pool = next(p for p in pc.pools if p.window is None)
    ix = PrefixIndex(block_size=4)
    stamps = [1, 2, 3, 4]
    # node A: pages in BOTH groups
    pc.write_suffix(0, _stamp_kv(cfg, stamps), _stamp_kv(cfg, stamps), 0, 4)
    ix.publish(np.arange(4), pc, 0)
    pc.free_slot(0)
    # node B: GLOBAL page only (windowed block dropped, deep-hit shape)
    pc.write_suffix(1, _stamp_kv(cfg, stamps), _stamp_kv(cfg, stamps), 0, 4)
    win_pool.release(win_pool._owned[1][0])
    win_pool._owned[1][0] = None
    win_pool.block_table[1, 0] = 0
    win_pool.first_block[1] = 1
    ix.publish(np.arange(100, 104), pc, 1)
    pc.free_slot(1)
    pc.check_invariants(ix.page_refs())
    # a windowed-group deficit: only node A can satisfy it — node B
    # (global-only, lighter, therefore LOWER-scored) must survive
    released = ix.evict(pc, {win_pool.gid: 1})
    assert released == 2                  # node A's two group pages
    assert ix.lookup(np.arange(100, 104)) != []   # B untouched
    assert ix.lookup(np.arange(4)) == []
    assert ix.retained_by_group[glob_pool.gid] == 1
    assert ix.retained_by_group[win_pool.gid] == 0
    pc.check_invariants(ix.page_refs())
    ix.drop_all(pc)


def test_grouped_bucket_args_shapes():
    """bucket_args_grouped: per-group plans with windowed groups
    bucketing by live trailing pages; all-None degenerates to the
    single-launch pair."""
    cfg = two_group_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=32, block_size=4)
    win_pool = next(p for p in pc.pools if p.window == 4)
    pc.write_suffix(0, _stamp_kv(cfg, list(range(20))),
                    _stamp_kv(cfg, list(range(20))), 0, 20)
    pc.write_suffix(1, _stamp_kv(cfg, list(range(6))),
                    _stamp_kv(cfg, list(range(6))), 0, 6)
    # one decode append retires slot 0's window-dead head in the
    # windowed group — the state a steady decode tick sees
    pc.append_position(0)
    needs = pc.bucket_needs(pc.lengths + 1)
    # windowed group's live need is smaller than its total occupancy
    win_idx = [p.gid for p in pc.pools].index(win_pool.gid)
    glob_idx = 1 - win_idx
    assert needs[win_idx][0] < needs[glob_idx][0]
    plans, perms = ops.bucket_args_grouped("pow2", "pallas_interpret",
                                           needs, pc.max_blocks_per_slot)
    assert plans is not None and len(plans) == len(pc.pools)
    streamed = [
        ops.plan_streamed_pages(p, 2, pc.max_blocks_per_slot)
        for p in plans
    ]
    assert streamed[win_idx] <= streamed[glob_idx]
    assert ops.bucket_args_grouped("none", "pallas_interpret", needs,
                                   pc.max_blocks_per_slot) == (None, None)
    assert ops.bucket_args_grouped("pow2", "ref", needs,
                                   pc.max_blocks_per_slot) == (None, None)
    pc.free_slot(0)
    pc.free_slot(1)
