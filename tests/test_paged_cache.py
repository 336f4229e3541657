"""Paged KV-cache subsystem: pool invariants, kernel/oracle parity, and
paged-vs-dense decode equivalence on ragged continuous batches.

check_invariants is refcount-aware since DESIGN.md §9: exclusively
owned pages are the refcount-1 special case (shared pages and the
prefix index are covered in tests/test_prefix_cache.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import init_lm
from repro.serve import (
    SCRATCH_PAGE,
    ContinuousBatcher,
    PagedKVCache,
    Request,
    ServeConfig,
    ServeEngine,
)

ARCH = "qwen2-1.5b"


@pytest.fixture(scope="module")
def model():
    # fp32 activations: the bf16 smoke model produces near-tie logits
    # whose argmax flips with summation order, which would make greedy
    # token parity across two differently-compiled paths meaningless
    cfg = dataclasses.replace(get_config(ARCH, smoke=True), dtype="float32")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(uid: int, t: int, vocab: int) -> jnp.ndarray:
    return jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(7), uid), (t,), 0, vocab
    ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# pool bookkeeping invariants
# ---------------------------------------------------------------------------

def test_alloc_free_recycle_invariants(model):
    cfg, _ = model
    pc = PagedKVCache(cfg, n_slots=3, max_len=32, block_size=4)
    assert pc.n_blocks == 1 + 3 * 8
    total_free = pc.n_free

    pc.alloc_slot(0, 10)            # 3 pages
    pc.alloc_slot(1, 4)             # 1 page
    pc.check_invariants()
    assert len(pc.owned_blocks(0)) == 3
    assert len(pc.owned_blocks(1)) == 1
    assert pc.n_free == total_free - 4
    assert SCRATCH_PAGE not in pc.owned_blocks(0) + pc.owned_blocks(1)

    blocks0 = pc.owned_blocks(0)
    pc.free_slot(0)
    pc.check_invariants()
    assert pc.n_free == total_free - 1
    assert np.all(pc.block_table[0] == SCRATCH_PAGE)
    assert pc.lengths[0] == 0

    # recycled pages are handed out again
    pc.alloc_slot(2, 12)
    pc.check_invariants()
    assert set(blocks0) & set(pc.owned_blocks(2))
    # exclusively owned pages carry refcount exactly 1
    assert all(pc.refcount(b) == 1 for b in pc.owned_blocks(2))
    assert not any(pc.is_shared(b) for b in pc.owned_blocks(2))


def test_block_table_append_across_boundaries(model):
    cfg, _ = model
    pc = PagedKVCache(cfg, n_slots=2, max_len=16, block_size=4)
    pc.alloc_slot(0, 3)
    pc.lengths[0] = 3
    assert len(pc.owned_blocks(0)) == 1
    pc.append_position(0)           # 4th token still fits page 1
    assert len(pc.owned_blocks(0)) == 1
    pc.append_position(0)           # 5th crosses into a second page
    assert len(pc.owned_blocks(0)) == 2
    assert pc.lengths[0] == 5
    pc.check_invariants()


def test_pool_exhaustion_and_overflow_raise(model):
    cfg, _ = model
    pc = PagedKVCache(cfg, n_slots=2, max_len=8, block_size=4, n_blocks=4)
    pc.alloc_slot(0, 8)             # 2 pages
    pc.alloc_slot(1, 4)             # 3rd page
    with pytest.raises(MemoryError):
        pc.ensure_capacity(1, 8)    # pool (3 usable pages) exhausted
    with pytest.raises(ValueError):
        pc.ensure_capacity(0, 9)    # over per-slot max_len


def test_reservations_gate_admission(model):
    cfg, _ = model
    pc = PagedKVCache(cfg, n_slots=3, max_len=16, block_size=4, n_blocks=9)
    assert pc.reserve_slot(0, 16)          # 4 of 8 usable pages promised
    assert pc.reserve_slot(1, 13)          # 4 more — pool fully promised
    assert not pc.reserve_slot(2, 4)       # no unpromised pages left
    # promised growth is always honored even with 0 unpromised pages
    pc.alloc_slot(0, 4)
    pc.ensure_capacity(0, 16)
    pc.check_invariants()
    pc.free_slot(0)                        # releases pages AND reservation
    assert pc.reserve_slot(2, 16)


# ---------------------------------------------------------------------------
# Pallas kernel vs jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [100, 3])
def test_paged_kernel_matches_oracle(rng, window):
    B, H, KV, hd, bs, nb, mb = 3, 4, 2, 8, 4, 10, 3
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    lengths = jnp.asarray([5, 12, 1], jnp.int32)
    win = jnp.asarray(window, jnp.int32)
    a = ref.paged_attention_ref(q, kp, vp, bt, lengths, win)
    b = paged_decode_attention(q, kp, vp, bt, lengths, win, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_paged_oracle_matches_dense_softmax(rng):
    """The page-gathered ragged attention equals plain softmax attention
    over the first `length` gathered positions (fp32 tolerance)."""
    B, H, KV, hd, bs, nb, mb = 2, 4, 2, 8, 4, 9, 2
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lengths = [6, 3]
    out = ref.paged_attention_ref(
        q, kp, vp, bt, jnp.asarray(lengths, jnp.int32),
        jnp.asarray(mb * bs, jnp.int32),
    )
    g = H // KV
    for bi, L in enumerate(lengths):
        k = kp[bt[bi]].transpose(0, 2, 1, 3).reshape(mb * bs, KV, hd)[:L]
        v = vp[bt[bi]].transpose(0, 2, 1, 3).reshape(mb * bs, KV, hd)[:L]
        qq = q[bi].reshape(KV, g, hd)
        sc = jnp.einsum("kgh,skh->kgs", qq, k) * hd ** -0.5
        dense = jnp.einsum(
            "kgs,skh->kgh", jax.nn.softmax(sc, axis=-1), v
        ).reshape(H, hd)
        np.testing.assert_allclose(
            np.asarray(dense), np.asarray(out[bi]), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# end-to-end: ragged continuous batching parity with dense greedy decode
# ---------------------------------------------------------------------------

def _dense_greedy(cfg, params, prompt, n_new):
    eng = ServeEngine(
        cfg, params, ServeConfig(max_cache_len=32, max_new_tokens=n_new)
    )
    return [int(x) for x in np.asarray(eng.generate(prompt[None, :])[0])]


def test_ragged_batch_matches_single_request_decode(model):
    """Distinct prompt lengths in one batch, slots refilled mid-run:
    every request's tokens equal its single-request greedy decode."""
    cfg, params = model
    lens = [5, 8, 13, 3, 9]
    prompts = [_prompt(u, t, cfg.vocab_size) for u, t in enumerate(lens)]
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=32, paged=True, block_size=4
    )
    for u, p in enumerate(prompts):
        cb.submit(Request(uid=u, prompt=p, max_new_tokens=6))
    res = cb.run_until_drained()
    assert set(res) == set(range(len(lens)))
    for u, p in enumerate(prompts):
        assert res[u] == _dense_greedy(cfg, params, p, 6), f"req {u}"
    # more requests than slots -> slots were refilled mid-run
    assert cb.ticks > 6
    cb.pcache.check_invariants()
    assert cb.pcache.n_free == cb.pcache.n_blocks - 1  # all pages recycled


def test_scheduler_mixed_lengths_drains(model):
    cfg, params = model
    cb = ContinuousBatcher(
        cfg, params, n_slots=3, cache_len=24, paged=True, block_size=4
    )
    for u, t in enumerate([4, 11, 7, 2, 16, 9, 5]):
        cb.submit(Request(uid=u, prompt=_prompt(u, t, cfg.vocab_size),
                          max_new_tokens=3))
    res = cb.run_until_drained()
    assert set(res) == set(range(7))
    assert all(len(v) == 3 for v in res.values())
    # prompts are right-padded to block-size buckets before prefill: the
    # 7 distinct lengths hit only ceil-to-4 buckets {4, 8, 12, 16}
    assert cb._prefill_paged._cache_size() <= 4


def test_scheduler_survives_undersized_pool(model):
    """Admission control: a pool too small to co-run every request must
    serialize them (requests wait in queue), never crash mid-run."""
    cfg, params = model
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=32, paged=True, block_size=4,
        n_blocks=9,  # 8 usable pages: two 16+3-token requests can't co-run
    )
    for u in range(3):
        cb.submit(Request(uid=u, prompt=_prompt(20 + u, 16, cfg.vocab_size),
                          max_new_tokens=4))
    res = cb.run_until_drained()
    assert set(res) == set(range(3))
    assert all(len(v) == 4 for v in res.values())
    cb.pcache.check_invariants()


def test_engine_paged_matches_dense(model):
    """ServeConfig.paged flips the cache; greedy tokens are identical."""
    cfg, params = model
    prompts = jax.random.randint(
        jax.random.PRNGKey(9), (3, 8), 0, cfg.vocab_size
    )
    dense = ServeEngine(
        cfg, params, ServeConfig(max_cache_len=32, max_new_tokens=6)
    ).generate(prompts)
    paged = ServeEngine(
        cfg, params,
        ServeConfig(max_cache_len=32, max_new_tokens=6, paged=True,
                    block_size=4),
    ).generate(prompts)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(paged))


# ---------------------------------------------------------------------------
# EOS early stop (engine paths + per-slot in the batcher)
# ---------------------------------------------------------------------------

def test_engine_eos_stops_early(model):
    """A mid-stream EOS shortens the returned width in BOTH engine paths;
    rows that stop earlier are padded with EOS; eos_token=-1 reproduces
    the full-budget output bit-exactly."""
    cfg, params = model
    prompts = jax.random.randint(
        jax.random.PRNGKey(9), (2, 8), 0, cfg.vocab_size
    )
    n_new = 8
    base = np.asarray(ServeEngine(
        cfg, params, ServeConfig(max_cache_len=32, max_new_tokens=n_new)
    ).generate(prompts))
    # pick the token row 0 emits at step 2 as EOS: row 0 must stop there
    eos = int(base[0, 2])
    stop0 = int(np.flatnonzero(base[0] == eos)[0])
    assert eos not in base[1]  # row 1 runs its full budget
    for paged in (False, True):
        sc = ServeConfig(max_cache_len=32, max_new_tokens=n_new,
                         eos_token=eos, paged=paged, block_size=4)
        eng = ServeEngine(cfg, params, sc)
        # single-row batch: generation returns as soon as the row stops
        solo = np.asarray(eng.generate(prompts[:1]))
        assert solo.shape == (1, stop0 + 1), (paged, solo)
        np.testing.assert_array_equal(solo[0], base[0, : stop0 + 1])
        # two-row batch: row 1 never stops, so the width is the full
        # budget and row 0 is EOS-padded past its stop
        out = np.asarray(eng.generate(prompts))
        assert out.shape == (2, n_new)
        np.testing.assert_array_equal(out[0, : stop0 + 1],
                                      base[0, : stop0 + 1])
        assert (out[0, stop0:] == eos).all()
        np.testing.assert_array_equal(out[1], base[1])


def test_batcher_eos_stops_slot_and_frees_pages(model):
    """Per-slot EOS in the continuous batcher: the stopped request's
    output ends at the EOS (shorter than its budget) and its pages are
    released the same tick — observed mid-run, not just after drain."""
    cfg, params = model
    lens = [5, 8, 13]
    prompts = [_prompt(u, t, cfg.vocab_size) for u, t in enumerate(lens)]
    n_new = 8
    cb0 = ContinuousBatcher(
        cfg, params, n_slots=3, cache_len=32, paged=True, block_size=4
    )
    for u, p in enumerate(prompts):
        cb0.submit(Request(uid=u, prompt=p, max_new_tokens=n_new))
    base = cb0.run_until_drained()
    # the EOS is a token request 0 first emits in DECODE: an EOS emitted
    # at prefill frees slot 0, which the same tick re-seats with the next
    # queued request, so slot 0's pages would not be request 0's
    stop0 = next(i for i in range(1, n_new - 1) if base[0][i] not in base[0][:i])
    eos = base[0][stop0]

    cb = ContinuousBatcher(
        cfg, params, n_slots=3, cache_len=32, paged=True, block_size=4,
        eos_token=eos,
    )
    for u, p in enumerate(prompts):
        cb.submit(Request(uid=u, prompt=p, max_new_tokens=n_new))
    freed_tick = None
    while cb.queue or any(s is not None for s in cb.slots):
        cb.step()
        if 0 in cb.finished and freed_tick is None:
            freed_tick = cb.ticks
            # pages released the tick the EOS was emitted, while the
            # other slots still decode
            assert cb.pcache.owned_blocks(0) == ()
            assert cb.pcache.lengths[0] == 0
            cb.pcache.check_invariants()
    res = cb.finished
    assert res[0] == base[0][: stop0 + 1]
    assert res[0][-1] == eos and len(res[0]) < n_new
    assert freed_tick is not None and freed_tick <= stop0 + 1
    # requests that never emit EOS are untouched
    for u in (1, 2):
        if eos not in base[u]:
            assert res[u] == base[u]
    cb.pcache.check_invariants()
    assert cb.pcache.n_free == cb.pcache.n_blocks - 1


# ---------------------------------------------------------------------------
# scheduler liveness fixes
# ---------------------------------------------------------------------------

def test_run_until_drained_raises_immediately_on_deadlock(model):
    """No active slot + nothing admissible = no future tick can free
    pages: run_until_drained must diagnose that immediately instead of
    spinning all max_ticks and mis-reporting a tick-budget problem."""
    cfg, params = model
    cb = ContinuousBatcher(
        cfg, params, n_slots=1, cache_len=16, paged=True, block_size=4
    )
    pc = cb.pcache
    # an external holder pins most of the pool (the shape a snapshot or
    # index component produces): admission can never succeed
    while pc.n_free > 1:
        pc._ref[pc.free_blocks.popleft()] = 1
    cb.submit(Request(uid=0, prompt=_prompt(0, 8, cfg.vocab_size),
                      max_new_tokens=4))
    with pytest.raises(RuntimeError, match="deadlock at tick 1.*pools:.*g0"):
        cb.run_until_drained(max_ticks=10_000)


def test_prefill_complete_requests_drain_through_one_slot_in_one_tick(model):
    """max_new_tokens=1 requests finish AT prefill and free their pages;
    the scheduler must retry the same slot instead of idling it a full
    tick per request."""
    cfg, params = model
    cb = ContinuousBatcher(
        cfg, params, n_slots=1, cache_len=16, paged=True, block_size=4
    )
    for u in range(3):
        cb.submit(Request(uid=u, prompt=_prompt(u, 5, cfg.vocab_size),
                          max_new_tokens=1))
    cb.step()  # ONE tick
    assert set(cb.finished) == {0, 1, 2}
    assert all(len(v) == 1 for v in cb.finished.values())
    cb.pcache.check_invariants()
    assert cb.pcache.n_free == cb.pcache.n_blocks - 1


def test_layer_pool_direct_mutators_hold_invariants():
    """Every LayerPagePool mutator exercised directly — grow (with
    dead-at-birth blocks), window retirement, shared-page attach, COW
    make_writable — with check_invariants after each step (analysis
    rule RL205 requires exactly this coverage)."""
    from repro.serve.paged_cache import LayerPagePool

    pool = LayerPagePool(
        gid=0, layers=(0,), window=4, n_slots=2, mb=4, n_blocks=9,
        block_size=4, retire=True,
    )
    lengths = np.zeros((2,), np.int64)

    # grow: slot 0 covers 10 tokens -> 3 live blocks drawn
    pool.grow(0, 0, 10)
    lengths[0] = 10
    assert pool.live_pages(0) == 3
    pool.check_invariants(lengths, None)

    # retire: with q_min=9 and window=4 exactly block 0 is dead
    assert pool.retire(0, 9) == 1
    assert int(pool.first_block[0]) == 1
    assert pool.block_table[0, 0] == SCRATCH_PAGE
    pool.check_invariants(lengths, None)

    # grow with dead-at-birth: slot 1's block 0 is already behind the
    # window at q_min=9 — no pool draw, walk starts at block 1
    pool.grow(1, 9, 10)
    lengths[1] = 10
    assert pool._owned[1][0] is None
    assert pool.live_pages(1) == 2
    pool.check_invariants(lengths, None)
    pool.free_slot(1)
    lengths[1] = 0
    pool.check_invariants(lengths, None)

    # attach: slot 1 shares slot 0's live tail (a prefix hit whose
    # window-skipped head is dead at j0)
    shared = [p for p in pool._owned[0] if p is not None]
    pool.attach(1, 1, shared)
    lengths[1] = 10
    assert int(pool.first_block[1]) == 1
    assert all(pool.refcount(p) == 2 for p in shared)
    pool.check_invariants(lengths, None)

    # make_writable: COW of a shared block copies only this group's
    # layer rows and splits the mapping
    class _Cache:
        k_pages = jnp.zeros((1, 9, 4, 1, 2), jnp.float32)
        v_pages = jnp.zeros((1, 9, 4, 1, 2), jnp.float32)

    pool.make_writable(_Cache(), 1, 1)
    assert pool.cow_events == 1
    assert int(pool.block_table[1, 1]) != int(pool.block_table[0, 1])
    assert pool.refcount(int(pool.block_table[0, 1])) == 1
    pool.check_invariants(lengths, None)

    # retain/release round-trip on a live page, then drain everything
    page = int(pool.block_table[0, 1])
    pool.retain(page)
    assert pool.refcount(page) == 2
    pool.release(page)
    pool.check_invariants(lengths, None)
    pool.free_slot(0)
    pool.free_slot(1)
    lengths[:] = 0
    pool.check_invariants(lengths, None)
    assert pool.n_free == pool.n_blocks - 1
