"""Shared-prefix KV subsystem (DESIGN.md §9): radix index semantics,
refcount/copy-on-write page lifecycle, paged-prefill kernel/oracle
parity on ragged suffixes, and scheduler-level prefix sharing parity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels import ref
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.models import init_lm
from repro.serve import (
    ContinuousBatcher,
    PagedKVCache,
    PrefixIndex,
    Request,
    ServeConfig,
    ServeEngine,
)

ARCH = "qwen2-1.5b"


def tiny_cfg() -> ModelConfig:
    """1-layer config for cheap cache-level device ops."""
    return ModelConfig(
        name="tiny", family="dense", n_layers=1, d_model=8, n_heads=2,
        n_kv_heads=1, d_ff=16, vocab_size=32, dtype="float32",
    )


@pytest.fixture(scope="module")
def model():
    # fp32 activations: greedy-token parity across differently-compiled
    # paths needs argmax stability (see tests/test_paged_cache.py)
    cfg = dataclasses.replace(get_config(ARCH, smoke=True), dtype="float32")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(uid: int, t: int, vocab: int) -> jnp.ndarray:
    return jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(11), uid), (t,), 0, vocab
    ).astype(jnp.int32)


def _stamp_kv(stamps, hd: int = 4):
    """[L=1, T, KV=1, hd] rows holding one recognizable value per token."""
    a = jnp.asarray(np.array(stamps, np.float32))[None, :, None, None]
    return a * jnp.ones((1, len(stamps), 1, hd), jnp.float32)


def _slot_stamps(pc: PagedKVCache, slot: int, n: int) -> list:
    """Read back position-p stamp values through the slot's block table."""
    pool = np.asarray(pc.k_pages)
    bs = pc.block_size
    owned = pc.owned_blocks(slot)
    return [float(pool[0, owned[p // bs], 0, p % bs, 0]) for p in range(n)]


# ---------------------------------------------------------------------------
# radix index semantics
# ---------------------------------------------------------------------------

def test_index_longest_full_page_prefix_match():
    cfg = tiny_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4)
    ix = PrefixIndex(block_size=4)
    prompt = np.arange(10)            # blocks (0..3), (4..7); 8,9 partial
    pc.alloc_slot(0, 10)
    ix.publish(prompt, pc, 0)
    assert len(ix) == 2               # only FULL pages are indexed

    assert ix.lookup(prompt) == list(pc.owned_blocks(0)[:2])
    assert ix.lookup(np.arange(6)) == [pc.owned_blocks(0)[0]]
    assert ix.lookup(np.arange(3)) == []          # sub-block: no match
    # same second block under a different first block must NOT match:
    # a block's KV depends on its entire token history
    other = np.concatenate([np.arange(100, 104), np.arange(4, 8)])
    assert ix.lookup(other) == []


def test_index_publish_is_first_writer_wins():
    cfg = tiny_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4)
    ix = PrefixIndex(block_size=4)
    prompt = np.arange(8)
    pc.alloc_slot(0, 8)
    pc.alloc_slot(1, 8)
    assert ix.publish(prompt, pc, 0) == 2
    assert ix.publish(prompt, pc, 1) == 0         # duplicate content: no-op
    assert ix.lookup(prompt) == list(pc.owned_blocks(0))
    pc.check_invariants(ix.page_refs())


def test_split_prompt_always_leaves_one_token():
    ix = PrefixIndex(block_size=4)
    # partial coverage: aligned cut, no COW
    assert ix.split_prompt(np.arange(10), [7, 8]) == (8, False)
    # full block-aligned hit: recompute the last token -> mid-page COW
    assert ix.split_prompt(np.arange(8), [7, 8]) == (7, True)
    assert ix.split_prompt(np.arange(4), [7]) == (3, True)


def test_index_eviction_respects_refcounts():
    cfg = tiny_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4, n_blocks=9)
    ix = PrefixIndex(block_size=4)
    pc.alloc_slot(0, 8)
    ix.publish(np.arange(8), pc, 0)
    # slot 0 still holds its pages: nothing is index-only, evict is a no-op
    assert ix.evict(pc, 4) == 0
    pc.free_slot(0)
    pc.check_invariants(ix.page_refs())
    free_before = pc.n_free
    # all-or-nothing: a deficit eviction could never satisfy must not
    # partially drain the index
    assert ix.evict(pc, 8) == 0
    assert len(ix) == 2
    assert ix.evict(pc, 1) == 1                  # leaf (deepest block) first
    assert pc.n_free == free_before + 1
    assert len(ix) == 1
    assert ix.evict(pc, 1) == 1                  # parent became a leaf
    assert len(ix) == 0
    pc.check_invariants({})


def test_retained_fraction_cap_bounds_the_index():
    """`max_retained_fraction` (ISSUE 4 satellite): the index never pins
    more than that fraction of the usable pool. Once at the cap,
    publishing a new prefix evicts the coldest index-only page to make
    room; when nothing is evictable (all retained pages still slot-held)
    publishing stops instead of overshooting."""
    cfg = tiny_cfg()
    # usable pool = 16 pages, cap = 0.25 -> at most 4 index-retained
    pc = PagedKVCache(cfg, n_slots=4, max_len=16, block_size=4, n_blocks=17)
    ix = PrefixIndex(block_size=4, max_retained_fraction=0.25)
    assert ix.page_cap(pc) == 4
    # churn: publish-and-release three distinct 2-page prefixes — the
    # third must displace the coldest instead of growing past the cap
    for i in range(3):
        pc.alloc_slot(0, 8)
        ix.publish(np.arange(i * 100, i * 100 + 8), pc, 0)
        pc.free_slot(0)
        pc.check_invariants(ix.page_refs())
    assert ix.retained_pages == len(ix) == 4
    assert ix.evicted_pages == 2                 # oldest prefix paid
    # when every retained page is still slot-held nothing is evictable:
    # a further publish adds nothing rather than overshoot the cap
    ix.drop_all(pc)
    pc.check_invariants({})
    pc.alloc_slot(0, 8)
    ix.publish(np.arange(8), pc, 0)
    pc.alloc_slot(1, 8)
    ix.publish(np.arange(50, 58), pc, 1)
    assert ix.retained_pages == 4
    pc.alloc_slot(2, 8)
    assert ix.publish(np.arange(900, 908), pc, 2) == 0
    assert ix.retained_pages == 4
    pc.check_invariants(ix.page_refs())
    for slot in range(3):
        pc.free_slot(slot)
    pc.check_invariants(ix.page_refs())
    # default preserves the uncapped behavior
    ix2 = PrefixIndex(block_size=4)
    assert ix2.max_retained_fraction == 1.0 and ix2.page_cap(pc) == 16
    with pytest.raises(ValueError, match="max_retained_fraction"):
        PrefixIndex(block_size=4, max_retained_fraction=1.5)


def test_cap_eviction_never_detaches_the_publish_path():
    """Regression: with the cap at 1 page, publishing [A, B] after [A]
    was already index-only must NOT evict node A (the chain the new B
    node hangs off) — that would attach B under a detached parent,
    leak its retain, and corrupt the trie. The publish path is
    protected; B is simply not published."""
    cfg = tiny_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4, n_blocks=17)
    ix = PrefixIndex(block_size=4, max_retained_fraction=1 / 16)
    assert ix.page_cap(pc) == 1
    prompt_a = np.arange(4)
    pc.alloc_slot(0, 4)
    ix.publish(prompt_a, pc, 0)
    pc.free_slot(0)                              # node A is index-only now
    assert ix.retained_pages == len(ix) == 1
    prompt_ab = np.arange(8)                     # blocks: [A, B]
    pc.alloc_slot(1, 8)
    added = ix.publish(prompt_ab, pc, 1)
    # A (the matched chain) survives; B is not published (cap is full
    # and the only candidate victim is protected)
    assert added == 0
    assert ix.retained_pages == len(ix) == 1
    assert ix.lookup(prompt_a) != []
    pc.check_invariants(ix.page_refs())
    pc.free_slot(1)
    pc.check_invariants(ix.page_refs())
    # an unrelated cold prefix IS still displaced at the cap
    pc.alloc_slot(0, 4)
    assert ix.publish(np.arange(100, 104), pc, 0) == 1
    assert ix.retained_pages == len(ix) == 1
    pc.check_invariants(ix.page_refs())
    pc.free_slot(0)


def test_retained_fraction_cap_threads_through_batcher(model):
    """Scheduler-level: a capped batcher drains a shared-prefix trace
    with the index never exceeding its page cap, and the knob defaults
    to the uncapped PR-2/PR-3 behavior."""
    cfg, params = model
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=48, paged=True, block_size=4,
        prefix=True, prefix_max_retained_fraction=0.2,
    )
    cap = cb.prefix.page_cap(cb.pcache)
    shared = _prompt(0, 12, cfg.vocab_size)
    for uid in range(5):
        cb.submit(Request(
            uid=uid,
            prompt=jnp.concatenate([shared, _prompt(uid + 1, 6, cfg.vocab_size)]),
            max_new_tokens=2,
        ))
    done = cb.run_until_drained()
    assert len(done) == 5
    assert cb.prefix.retained_pages <= cap
    cb.pcache.check_invariants(cb.prefix.page_refs())


def test_cross_layer_dedup_stats_count_shared_columns():
    """ISSUE 4 satellite (measurement only): a page shared by k holders
    stores n_layers physical copies once but stands for k logical
    columns — `extra_refs` * n_layers per-layer copies deduped."""
    cfg = tiny_cfg()                             # n_layers = 1
    pc = PagedKVCache(cfg, n_slots=3, max_len=16, block_size=4)
    s0 = pc.cross_layer_dedup_stats()
    assert s0["allocated_pages"] == s0["extra_refs"] == 0
    pc.alloc_slot(0, 8)                          # 2 private pages
    pc.attach_shared(1, list(pc.owned_blocks(0)))
    pc.attach_shared(2, list(pc.owned_blocks(0))[:1])
    s = pc.cross_layer_dedup_stats()
    assert s["n_layers"] == 1
    assert s["allocated_pages"] == 2
    assert s["shared_pages"] == 2                # refcounts 3 and 2
    assert s["extra_refs"] == 3                  # (3-1) + (2-1)
    assert s["physical_page_copies"] == 2        # 1 layer x 2 pages
    assert s["deduped_page_copies"] == 3
    # bytes: one page in one layer = 2 pools * bs * KV * hd * itemsize
    assert s["physical_bytes"] == 2 * s["page_layer_bytes"]
    assert s["deduped_bytes"] == 3 * s["page_layer_bytes"]
    for slot in range(3):
        pc.free_slot(slot)
    assert pc.cross_layer_dedup_stats()["allocated_pages"] == 0


def test_gauge_counters_follow_every_refcount_change():
    """The pool keeps `sum(refcount - 1)` and the count of pages held
    more than once as counters, so the per-tick gauges never walk the
    refcounts: each refcount change (draw, attach, retain, release,
    copy-on-write, free) must keep both equal to the recomputed sums,
    and `check_invariants` catches a counter that drifted."""
    cfg = tiny_cfg()
    pc = PagedKVCache(cfg, n_slots=3, max_len=16, block_size=4)
    pool = pc.pools[0]
    ext = {}

    def check(extra, shared):
        assert pool.extra_refs() == extra
        assert pool.shared_pages() == shared
        g = pc.pool_gauges()[0]
        s = pc.cross_layer_dedup_stats()
        assert g["shared_refs"] == s["extra_refs"] == extra
        assert s["shared_pages"] == shared
        pc.check_invariants(ext)

    pc.write_suffix(0, _stamp_kv([1, 2, 3, 4, 5]), _stamp_kv([1, 2, 3, 4, 5]),
                    0, 5)                        # draws: refcount 1
    pages = list(pc.owned_blocks(0))
    check(0, 0)
    pc.attach_shared(1, pages[:1])               # attach: 2
    check(1, 1)
    pc.retain(pages[0])                          # an index retain: 3
    ext = {pages[0]: 1}
    check(2, 1)
    pc.attach_shared(2, pages)                   # 4 and 2
    check(4, 2)
    pc.write_suffix(1, _stamp_kv([7, 8]), _stamp_kv([7, 8]), 3, 2)
    assert pc.cow_events == 1                    # copy-on-write: 3
    check(3, 2)
    pc.free_slot(2)                              # release: 2 and 1
    check(1, 1)
    pc.release(pages[0])                         # index release: 1
    ext = {}
    check(0, 0)
    pool._extra_refs += 1
    with pytest.raises(AssertionError, match="extra-refs counter"):
        pc.check_invariants(ext)
    pool._extra_refs -= 1
    pool._shared_pages += 1
    with pytest.raises(AssertionError, match="shared-pages counter"):
        pc.check_invariants(ext)


# ---------------------------------------------------------------------------
# refcount / copy-on-write page lifecycle
# ---------------------------------------------------------------------------

def test_attach_shared_refcounts_and_last_free_recycles():
    cfg = tiny_cfg()
    pc = PagedKVCache(cfg, n_slots=3, max_len=16, block_size=4)
    pc.alloc_slot(0, 8)
    pages = list(pc.owned_blocks(0))
    pc.attach_shared(1, pages)
    pc.attach_shared(2, pages[:1])
    assert pc.refcount(pages[0]) == 3 and pc.refcount(pages[1]) == 2
    pc.check_invariants({})

    free0 = pc.n_free
    pc.free_slot(0)
    assert pc.n_free == free0                    # still referenced: no recycle
    pc.free_slot(1)
    assert pc.n_free == free0 + 1                # pages[1] hit refcount 0
    pc.free_slot(2)
    assert pc.n_free == free0 + 2                # last sharer frees pages[0]
    pc.check_invariants({})


def test_cow_never_writes_shared_page_in_place():
    cfg = tiny_cfg()
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4)
    pc.write_suffix(0, _stamp_kv([1, 2, 3, 4, 5]), _stamp_kv([1, 2, 3, 4, 5]),
                    0, 5)
    pages = list(pc.owned_blocks(0))
    pc.attach_shared(1, pages[:1])               # share slot 0's full page
    # slot 1 appends mid-page (the full-hit recompute shape): COW
    pc.write_suffix(1, _stamp_kv([77, 88]), _stamp_kv([77, 88]), 3, 2)
    assert pc.cow_events == 1
    assert pc.owned_blocks(1)[0] != pages[0]     # private copy
    assert _slot_stamps(pc, 0, 5) == [1, 2, 3, 4, 5]   # donor untouched
    assert _slot_stamps(pc, 1, 5) == [1, 2, 3, 77, 88]
    assert pc.refcount(pages[0]) == 1
    pc.check_invariants({})


def test_exclusive_page_append_skips_cow():
    cfg = tiny_cfg()
    pc = PagedKVCache(cfg, n_slots=1, max_len=16, block_size=4)
    pc.write_suffix(0, _stamp_kv([1, 2]), _stamp_kv([1, 2]), 0, 2)
    page = pc.owned_blocks(0)[0]
    pc.write_suffix(0, _stamp_kv([3]), _stamp_kv([3]), 2, 1)
    assert pc.cow_events == 0
    assert pc.owned_blocks(0)[0] == page
    assert _slot_stamps(pc, 0, 3) == [1, 2, 3]


def test_reservations_account_shared_and_cow_draws():
    cfg = tiny_cfg()
    # 8 usable pages
    pc = PagedKVCache(cfg, n_slots=3, max_len=32, block_size=4, n_blocks=9)
    pc.alloc_slot(0, 16)                         # 4 pages drawn
    shared = list(pc.owned_blocks(0))
    # slot 1 shares all 4 pages and may COW one: draws = 8 - 4 + 1 = 5
    assert not pc.reserve_slot(1, 32, n_shared=4, n_cow=1)   # 5 > 4 free
    assert pc.reserve_slot(1, 28, n_shared=4, n_cow=1)       # 4 <= 4 free
    pc.attach_shared(1, shared)
    pc.begin_append(1, 15, 1)                    # mid-page write: COW draw
    assert pc.cow_events == 1
    pc.ensure_capacity(1, 28)                    # growth stays within promise
    pc.check_invariants({})
    assert pc.available_blocks() >= 0


# ---------------------------------------------------------------------------
# hypothesis property tests: random admit/share/append/free sequences
# ---------------------------------------------------------------------------

@given(st.data())
@settings(deadline=None)
def test_pool_random_ops_keep_invariants_and_content(data):
    """Random op sequences: shared pages are never written in place (every
    slot's readback always equals its own written stamps), refcounts hit
    zero exactly when the last sharer frees, pool accounting stays exact
    (checked by check_invariants after every op)."""
    cfg = tiny_cfg()
    bs, max_len = 4, 24
    pc = PagedKVCache(cfg, n_slots=3, max_len=max_len, block_size=bs,
                      n_blocks=20)
    expected = {}                         # slot -> stamp per position
    next_stamp = [1.0]

    def fresh(n):
        out = [next_stamp[0] + i for i in range(n)]
        next_stamp[0] += n
        return out

    for _ in range(data.draw(st.integers(4, 12), label="n_ops")):
        live = sorted(expected)
        empty = [s for s in range(3) if s not in expected]
        ops = []
        if empty and pc.n_free >= max_len // bs:
            ops.append("start")
        if live:
            ops.append("free")
            if pc.n_free >= 2:
                ops.append("append")
        if not ops:
            break
        op = data.draw(st.sampled_from(ops), label="op")

        if op == "start":
            slot = data.draw(st.sampled_from(empty), label="slot")
            donors = [s for s in live if len(expected[s]) >= bs]
            start = 0
            if donors and data.draw(st.booleans(), label="share"):
                donor = data.draw(st.sampled_from(donors), label="donor")
                # cap so start < max_len: at least one token is writable
                k_max = min(len(expected[donor]) // bs, (max_len - 1) // bs)
                k = data.draw(st.integers(1, k_max), label="k")
                pc.attach_shared(slot, pc.owned_blocks(donor)[:k])
                # aligned continue, or mid-page (full-hit recompute -> COW)
                start = k * bs - int(data.draw(st.booleans(), label="mid"))
                expected[slot] = list(expected[donor][:start])
            else:
                expected[slot] = []
            n = data.draw(st.integers(1, max_len - start), label="n")
            stamps = fresh(n)
            pc.write_suffix(slot, _stamp_kv(stamps), _stamp_kv(stamps),
                            start, n)
            expected[slot] += stamps

        elif op == "append":
            slot = data.draw(st.sampled_from(live), label="slot")
            n = len(expected[slot])
            if n >= max_len:
                continue
            stamps = fresh(1)
            pc.write_suffix(slot, _stamp_kv(stamps), _stamp_kv(stamps), n, 1)
            expected[slot] += stamps

        else:  # free
            slot = data.draw(st.sampled_from(live), label="slot")
            pc.free_slot(slot)
            del expected[slot]

        pc.check_invariants({})
        for slot, exp in expected.items():
            assert _slot_stamps(pc, slot, len(exp)) == exp, (slot, op)

    for slot in sorted(expected):
        pc.free_slot(slot)
    pc.check_invariants({})
    assert pc.n_free == pc.n_blocks - 1   # every page recycled exactly once


@given(st.data())
@settings(deadline=None)
def test_refcount_zero_exactly_at_last_release(data):
    cfg = tiny_cfg()
    pc = PagedKVCache(cfg, n_slots=3, max_len=8, block_size=4)
    pc.alloc_slot(0, 4)
    page = pc.owned_blocks(0)[0]
    holders = data.draw(st.integers(0, 2), label="extra_slots")
    retains = data.draw(st.integers(0, 3), label="index_retains")
    for s in range(1, 1 + holders):
        pc.attach_shared(s, [page])
    for _ in range(retains):
        pc.retain(page)
    total = 1 + holders + retains
    for i in range(total):
        assert pc.refcount(page) == total - i
        assert page not in pc.free_blocks
        pc.release(page)
    assert pc.refcount(page) == 0
    assert page in pc.free_blocks


# ---------------------------------------------------------------------------
# Pallas paged-prefill kernel vs jnp oracle (ragged suffixes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [100, 5])
@pytest.mark.parametrize(
    "starts,totals",
    [([0, 9], [7, 12]),       # full prefill vs deep-prefix ragged suffix
     ([15, 4], [16, 10])],    # full-hit 1-token recompute vs mid prefix
)
def test_paged_prefill_kernel_matches_oracle(rng, window, starts, totals):
    B, T, H, KV, hd, bs, nb, mb = 2, 8, 4, 2, 8, 4, 12, 4
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    start = jnp.asarray(starts, jnp.int32)
    total = jnp.asarray(totals, jnp.int32)
    win = jnp.asarray(window, jnp.int32)
    a = ref.paged_prefill_ref(q, kp, vp, bt, start, total, win)
    b = paged_prefill_attention(q, kp, vp, bt, start, total, win,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_paged_prefill_oracle_matches_dense_softmax(rng):
    """Suffix row t of the page-gathered attention equals plain causal
    softmax attention over the first start+t+1 gathered positions."""
    B, T, H, KV, hd, bs, nb, mb = 1, 4, 4, 2, 8, 4, 9, 3
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    bt = jnp.asarray([[2, 5, 7]], jnp.int32)
    start, total = 5, 9
    out = ref.paged_prefill_ref(
        q, kp, vp, bt, jnp.asarray([start], jnp.int32),
        jnp.asarray([total], jnp.int32), jnp.asarray(mb * bs, jnp.int32),
    )
    g = H // KV
    k = kp[bt[0]].transpose(0, 2, 1, 3).reshape(mb * bs, KV, hd)
    v = vp[bt[0]].transpose(0, 2, 1, 3).reshape(mb * bs, KV, hd)
    for t in range(total - start):
        L = start + t + 1
        qq = q[0, t].reshape(KV, g, hd)
        sc = jnp.einsum("kgh,skh->kgs", qq, k[:L]) * hd ** -0.5
        dense = jnp.einsum(
            "kgs,skh->kgh", jax.nn.softmax(sc, axis=-1), v[:L]
        ).reshape(H, hd)
        np.testing.assert_allclose(
            np.asarray(dense), np.asarray(out[0, t]), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# scheduler-level prefix sharing
# ---------------------------------------------------------------------------

def _dense_greedy(cfg, params, prompt, n_new):
    eng = ServeEngine(
        cfg, params, ServeConfig(max_cache_len=64, max_new_tokens=n_new)
    )
    return [int(x) for x in np.asarray(eng.generate(prompt[None, :])[0])]


def test_prefix_sharing_matches_unshared_and_dense(model):
    """Shared-prefix trace (incl. one exact-repeat prompt -> COW): greedy
    tokens are identical to the unshared paged run AND to each request's
    single-request dense decode, while prefill compute and page draws
    shrink."""
    cfg, params = model
    pre = _prompt(99, 8, cfg.vocab_size)
    prompts = [
        jnp.concatenate([pre, _prompt(u, t, cfg.vocab_size)])
        for u, t in enumerate([5, 3, 6])
    ] + [pre]                                   # block-aligned full hit

    runs = {}
    for prefix in (False, True):
        cb = ContinuousBatcher(
            cfg, params, n_slots=2, cache_len=64, paged=True, block_size=4,
            prefix=prefix,
        )
        for u, p in enumerate(prompts):
            cb.submit(Request(uid=u, prompt=p, max_new_tokens=5))
        res = cb.run_until_drained()
        runs[prefix] = (res, cb)

    res_u, cb_u = runs[False]
    res_s, cb_s = runs[True]
    assert res_u == res_s
    for u, p in enumerate(prompts):
        assert res_s[u] == _dense_greedy(cfg, params, p, 5), f"req {u}"
    assert cb_s.prefill_tokens < cb_u.prefill_tokens
    assert cb_s.pcache.pages_allocated < cb_u.pcache.pages_allocated
    assert cb_s.pcache.cow_events >= 1          # the exact-repeat prompt
    assert cb_s.prefix.hits >= 3
    cb_s.pcache.check_invariants(cb_s.prefix.page_refs())
    cb_u.pcache.check_invariants()
    # unshared run retains nothing: every page recycled
    assert cb_u.pcache.n_free == cb_u.pcache.n_blocks - 1


def test_admission_evicts_index_pages_under_pressure(model):
    """Index-retained pages must yield to admission: a second, disjoint
    prompt that needs the whole pool evicts the first prompt's cached
    pages instead of deadlocking."""
    cfg, params = model
    # 8 usable pages; each request needs ceil((16+3)/4) = 5
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=32, paged=True, block_size=4,
        n_blocks=9, prefix=True,
    )
    for u in range(3):
        cb.submit(Request(uid=u, prompt=_prompt(40 + u, 16, cfg.vocab_size),
                          max_new_tokens=4))
    res = cb.run_until_drained()
    assert set(res) == set(range(3))
    assert cb.prefix.evicted_pages > 0
    cb.pcache.check_invariants(cb.prefix.page_refs())


def test_full_hit_at_slot_capacity_pads_to_scratch(model):
    """A full hit on a prompt that exactly fills the slot's block table
    pads its 1-token recompute past the table's capacity: the overflow
    scatter rows must land in the scratch page, not wrap into the last
    (valid) page and corrupt the recomputed token's context."""
    cfg, params = model
    p = _prompt(70, 16, cfg.vocab_size)      # == cache_len: table is full
    outs = {}
    for prefix in (False, True):
        cb = ContinuousBatcher(
            cfg, params, n_slots=1, cache_len=16, paged=True, block_size=4,
            n_blocks=6,  # 4-page table + 1 spare for the COW draw
            prefix=prefix,
        )
        for uid in (0, 1):                   # identical prompts
            cb.submit(Request(uid=uid, prompt=p, max_new_tokens=1))
        outs[prefix] = cb.run_until_drained()
    cb.pcache.check_invariants(cb.prefix.page_refs())
    assert cb.pcache.cow_events == 1         # req 1 took the full-hit path
    assert outs[True] == outs[False]
    # max_new_tokens=1 finishes at prefill: exactly one token, no decode
    assert all(len(v) == 1 for v in outs[True].values())


# ---------------------------------------------------------------------------
# satellite regressions: head-of-line blocking, max_ticks exhaustion
# ---------------------------------------------------------------------------

def test_no_head_of_line_blocking(model):
    """A large request waiting for pages must not starve admissible small
    requests queued behind it (FIFO among admissible)."""
    cfg, params = model
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=32, paged=True, block_size=4,
        n_blocks=9,  # 8 usable pages
    )
    # queue: [small, BIG, small, small] — the big one needs
    # ceil((29+3)/4) = 8 pages (the whole pool), so once the first small
    # is admitted it cannot fit until the pool fully drains
    small = lambda u: Request(uid=u, prompt=_prompt(50 + u, 4, cfg.vocab_size),
                              max_new_tokens=4)
    cb.submit(small(0))
    cb.submit(Request(uid=1, prompt=_prompt(60, 29, cfg.vocab_size),
                      max_new_tokens=4))
    cb.submit(small(2))
    cb.submit(small(3))
    # tick 0: slot 0 takes small 0; the stuck big request at the queue
    # head must NOT stop slot 1 from taking small 2 from behind it
    assert cb.step() == 2
    assert {s.uid for s in cb.slots if s is not None} == {0, 2}
    res = cb.run_until_drained()
    assert set(res) == {0, 1, 2, 3}              # big still completes
    assert all(len(v) == 4 for v in res.values())
    cb.pcache.check_invariants()


def test_run_until_drained_raises_on_tick_exhaustion(model):
    cfg, params = model
    cb = ContinuousBatcher(
        cfg, params, n_slots=1, cache_len=64, paged=True, block_size=4
    )
    cb.submit(Request(uid=0, prompt=_prompt(60, 4, cfg.vocab_size),
                      max_new_tokens=40))
    with pytest.raises(RuntimeError, match="max_ticks=3"):
        cb.run_until_drained(max_ticks=3)
    with pytest.warns(RuntimeWarning, match="max_ticks=4"):
        partial = cb.run_until_drained(max_ticks=4, strict=False)
    assert partial == {}                          # nothing finished yet
    res = cb.run_until_drained()                  # and it can still drain
    assert len(res[0]) == 40
