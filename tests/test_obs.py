"""Telemetry subsystem tests (DESIGN.md §13).

Three layers:

  * primitives — registry get-or-create, counter/gauge/histogram
    semantics, deterministic interpolated percentiles, ManualClock,
    the event log's monotone seq + JSONL stream;
  * lifecycle invariants on the facade alone (no model) — tokens_out
    == 1 + decode_events, exact TTFT/TPOT under the fake clock, and
    (hypothesis) bit-identical summaries when a random ragged trace is
    replayed against a fresh telemetry with the same clock;
  * serve-stack integration on the fp32 smoke model — the metrics-OFF
    drain makes ZERO registry mutations and emits bit-identical tokens;
    submitted == finished + active + queued at every tick; per-request
    traced token counts equal the scheduler's outputs; and the deadlock
    diagnostic lands in the event log without changing the raised
    message.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.models import init_lm
from repro.obs import (
    DEFAULT_LATENCY_BUCKETS,
    EventLog,
    ManualClock,
    MetricsRegistry,
    ServeTelemetry,
    exponential_buckets,
    mutation_count,
)
from repro.serve import ContinuousBatcher, Request

ARCH = "qwen2-1.5b"


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_config(ARCH, smoke=True), dtype="float32")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(uid: int, t: int, vocab: int) -> jnp.ndarray:
    return jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(7), uid), (t,), 0, vocab
    ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

def test_counter_monotone_and_labels():
    r = MetricsRegistry(clock=ManualClock())
    c = r.counter("serve_requests_submitted")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert r.counter("serve_requests_submitted") is c  # get-or-create
    with pytest.raises(ValueError):
        c.inc(-1)
    lab = r.counter("kernel_launches", {"kind": "decode"})
    lab.inc()
    assert lab is not c
    assert 'kernel_launches{kind="decode"}' in r.summary()


def test_gauge_tracks_min_max():
    r = MetricsRegistry(clock=ManualClock())
    g = r.gauge("pool_free_pages", {"group": 0})
    assert g.value is None and g.min is None
    for v in (5, 2, 9, 4):
        g.set(v)
    assert (g.value, g.min, g.max) == (4, 2, 9)


def test_metric_kind_conflict_raises():
    r = MetricsRegistry(clock=ManualClock())
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")


def test_histogram_percentiles_deterministic():
    r = MetricsRegistry(clock=ManualClock())
    h = r.histogram("serve_ttft_s")
    assert h.percentile(50) is None  # empty
    values = [0.0003, 0.0012, 0.0013, 0.02, 0.02, 0.7]
    for v in values:
        h.observe(v)
    p50_a, p99_a = h.percentile(50), h.percentile(99)
    h2 = MetricsRegistry(clock=ManualClock()).histogram("serve_ttft_s")
    for v in values:
        h2.observe(v)
    assert (h2.percentile(50), h2.percentile(99)) == (p50_a, p99_a)
    assert h.count == 6 and abs(h.sum - sum(values)) < 1e-12
    # overflow clamps to the last finite bound
    h.observe(1e9)
    assert h.percentile(100) == DEFAULT_LATENCY_BUCKETS[-1]
    with pytest.raises(ValueError):  # conflicting bounds on re-lookup
        r.histogram("serve_ttft_s", bounds=(1.0, 2.0))


def test_exponential_buckets():
    assert exponential_buckets(1.0, 2.0, 3) == (1.0, 2.0, 4.0)
    with pytest.raises(ValueError):
        exponential_buckets(0.0, 2.0, 3)


def test_manual_clock():
    clk = ManualClock(10.0, tick=0.5)
    assert (clk(), clk()) == (10.0, 10.5)
    clk.advance(2.0)
    assert clk() == 13.0
    with pytest.raises(ValueError):
        clk.advance(-1.0)


def test_prometheus_exposition():
    r = MetricsRegistry(clock=ManualClock())
    r.counter("serve_ticks").inc(3)
    r.gauge("pool_occupancy").set(0.5)
    h = r.histogram("serve_ttft_s", bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = r.prometheus()
    assert "# TYPE serve_ticks counter" in text
    assert "serve_ticks 3" in text
    assert 'serve_ttft_s_bucket{le="0.1"} 1' in text
    assert 'serve_ttft_s_bucket{le="+Inf"} 2' in text
    assert "serve_ttft_s_count 2" in text


def test_histogram_top_edge_inclusive():
    """Bucket upper edges are INCLUSIVE: a value exactly equal to the
    top finite bound lands in the last finite bucket, never in
    overflow. Pinned here because the model-error histograms put exact
    predictions (error == 0 == first edge... and saturated errors ==
    1.0 == last edge) right on bucket boundaries."""
    r = MetricsRegistry(clock=ManualClock())
    h = r.histogram("perf_model_error", bounds=(0.1, 1.0))
    h.observe(0.1)  # == an interior edge -> that bucket, not the next
    h.observe(1.0)  # == top finite edge -> last finite bucket
    assert h.counts == [1, 1, 0]
    h.observe(1.0 + 1e-9)  # strictly above -> overflow
    assert h.counts == [1, 1, 1]


def test_histogram_percentile_at_bucket_boundary():
    """Interpolation with all mass at the top edge: p100 returns the
    edge exactly; interior percentiles interpolate inside the final
    finite bucket (lo = previous edge)."""
    r = MetricsRegistry(clock=ManualClock())
    h = r.histogram("x", bounds=(0.1, 1.0))
    for _ in range(4):
        h.observe(1.0)
    assert h.percentile(100) == 1.0
    assert h.percentile(50) == pytest.approx(0.1 + 0.9 * 0.5)
    # overflow observations clamp percentiles to the last finite bound
    h.observe(7.0)
    assert h.percentile(100) == 1.0


def _golden_registry() -> MetricsRegistry:
    r = MetricsRegistry(clock=ManualClock())
    r.counter("serve_ticks", help="scheduler ticks elapsed").inc(3)
    r.counter(
        "serve_recompiles_total",
        {"step": "decode", "plans": 'geo "pow2"\nw\\2x1+4x1'},
        help="XLA compiles by step kind x plan signature",
    ).inc(2)
    r.gauge("pool_occupancy", {"zeta": "z", "alpha": "a"}).set(0.5)
    h = r.histogram("serve_ttft_s", bounds=(0.1, 1.0),
                    help="time to first token (s)")
    for v in (0.05, 1.0, 5.0):
        h.observe(v)
    return r


def test_prometheus_golden_snapshot():
    """Pin the full exposition format against a golden file: HELP/TYPE
    lines, deterministic (name, sorted-label) ordering, and
    exposition-format escaping of backslash/quote/newline in label
    values (plan signatures can contain any of them)."""
    import pathlib

    text = _golden_registry().prometheus()
    golden = (pathlib.Path(__file__).parent
              / "golden" / "prometheus_snapshot.txt").read_text()
    assert text == golden
    # spot-check the load-bearing properties independently of the file
    assert "# HELP serve_ticks scheduler ticks elapsed" in text
    assert "# TYPE serve_ttft_s histogram" in text
    # label keys sort within a line; families sort by name
    assert text.index("pool_occupancy") < text.index(
        "serve_recompiles_total") < text.index("serve_ticks")
    assert 'pool_occupancy{alpha="a",zeta="z"} 0.5' in text
    # escaped label value: \ -> \\, " -> \", newline -> \n
    assert ('serve_recompiles_total{plans="geo \\"pow2\\"\\n'
            'w\\\\2x1+4x1",step="decode"} 2') in text
    assert "\n" + 'serve_ttft_s_bucket{le="1"} 2' + "\n" in text
    # identical registry renders the identical snapshot (determinism)
    assert _golden_registry().prometheus() == text


def test_event_log_stream(tmp_path):
    path = tmp_path / "events.jsonl"
    clk = ManualClock(0.0, tick=1.0)
    with EventLog(path=str(path), clock=clk) as log:
        log.emit("submit", uid=0)
        log.emit("finish", uid=0, tokens_out=3)
    # context-manager exit closes the stream: flushes, appends run_end
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [e["seq"] for e in lines] == [0, 1, 2]
    assert lines[1] == {"seq": 1, "ts": 1.0, "event": "finish",
                        "uid": 0, "tokens_out": 3}
    assert len(log.of("submit")) == 1 and len(log) == 3


def test_event_log_run_end_terminal(tmp_path):
    """close() emits the terminal run_end with the per-type tally of
    everything before it, is idempotent, and seals the log — the
    truncation-detection contract check_metrics.py relies on."""
    path = tmp_path / "events.jsonl"
    log = EventLog(path=str(path), clock=ManualClock(0.0, tick=1.0))
    log.emit("submit", uid=0)
    log.emit("decode", uids=[0])
    log.emit("decode", uids=[0])
    log.emit("finish", uid=0)
    assert not log.closed
    log.close()
    assert log.closed
    log.close()  # idempotent: exactly one run_end
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 5
    end = lines[-1]
    assert end["event"] == "run_end"
    assert end["events"] == 4
    assert end["by_type"] == {"submit": 1, "decode": 2, "finish": 1}
    assert len(log.of("run_end")) == 1
    with pytest.raises(RuntimeError):
        log.emit("submit", uid=1)


def test_truncated_event_stream_detected(tmp_path):
    """check_events fails a stream whose run_end is missing or whose
    tally disagrees with the lines on disk (a crashed/truncated file)."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "check_metrics_mod",
        pathlib.Path(__file__).parent.parent
        / "benchmarks" / "check_metrics.py",
    )
    cm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cm)

    log = EventLog(path=None, clock=ManualClock(0.0, tick=1.0))
    log.emit("submit", uid=0)
    log.emit("decode", uids=[0])
    log.emit("finish", uid=0, tokens_out=2, decode_events=1)
    log.close()
    full = [json.dumps(e) for e in log.events]
    cm.check_events(full)  # intact stream passes
    with pytest.raises(AssertionError, match="truncated"):
        cm.check_events(full[:-1])  # run_end lost
    # run_end present but an interior line lost: tally disagrees
    with pytest.raises(AssertionError, match="truncated"):
        cm.check_events([full[0]] + full[2:])


# ---------------------------------------------------------------------------
# lifecycle facade (no model)
# ---------------------------------------------------------------------------

def _play(tel: ServeTelemetry, trace):
    """Drive the facade through a ragged trace: each entry is
    (prompt_tokens, n_decode_events)."""
    for uid, (pt, _) in enumerate(trace):
        tel.on_submit(uid, pt, 16)
    for uid, (pt, nd) in enumerate(trace):
        tel.on_admit(uid, slot=0, cached_tokens=0)
        tel.on_prefill(uid, pt)
        tel.on_first_token(uid)
        for _ in range(nd):
            tel.on_decode([uid])
        tel.on_finish(uid)
        tel.end_tick(queued=0, active=0)


def test_facade_exact_latency_math():
    # tick=0: repeated reads within one "instant" are equal; advance()
    # models the elapsed time explicitly, so the expectations are exact
    clk = ManualClock(0.0, tick=0.0)
    tel = ServeTelemetry(registry=MetricsRegistry(clock=clk), clock=clk)
    tel.on_submit(0, 8, 4)       # t=0
    clk.advance(1.0)
    tel.on_admit(0, slot=1)      # t=1 -> queue delay 1
    tel.on_prefill(0, 8)
    clk.advance(1.0)
    tel.on_first_token(0)        # t=2 -> ttft 2
    tel.on_decode([0])
    tel.on_decode([0])
    clk.advance(1.0)
    tel.on_finish(0)             # t=3 -> tpot (3-2)/2 = 0.5
    tr = tel.traces[0]
    assert (tr.queue_delay_s, tr.ttft_s, tr.tpot_s) == (1.0, 2.0, 0.5)
    assert tr.tokens_out == 3 and tr.decode_events == 2
    lat = tel.latency_summary()
    assert lat["ttft_s"]["p50"] == 2.0
    assert lat["tpot_s"]["p50"] == 0.5
    assert lat["e2e_s"]["p50"] == 3.0


def test_single_token_request_has_no_tpot():
    clk = ManualClock(0.0, tick=1.0)
    tel = ServeTelemetry(registry=MetricsRegistry(clock=clk), clock=clk)
    tel.on_submit(0, 4, 1)
    tel.on_admit(0, slot=0)
    tel.on_first_token(0)
    tel.on_finish(0)
    tr = tel.traces[0]
    assert tr.tokens_out == 1 and tr.decode_events == 0
    assert tr.tpot_s is None
    assert tel.latency_summary()["tpot_s"]["n"] == 0


@settings(max_examples=25, deadline=None)
@given(
    st.lists(
        st.tuples(st.integers(1, 64), st.integers(0, 12)),
        min_size=1, max_size=12,
    )
)
def test_replayed_trace_is_bit_deterministic(trace):
    """Same ragged trace + same ManualClock => identical run summaries
    (histogram bucket counts, percentiles, event streams included)."""
    summaries = []
    for _ in range(2):
        clk = ManualClock(0.0, tick=0.125)
        tel = ServeTelemetry(
            registry=MetricsRegistry(clock=clk), clock=clk
        )
        _play(tel, trace)
        summaries.append(tel.summary())
        for uid, (pt, nd) in enumerate(trace):
            tr = tel.traces[uid]
            assert tr.tokens_out == 1 + nd == 1 + tr.decode_events
            assert tr.prefill_tokens == pt
    assert summaries[0] == summaries[1]
    assert summaries[0]["requests"]["finished"] == len(trace)


def test_streamed_page_accounting_full_depth_vs_plan():
    from repro.kernels.ops import grouped_streamed_pages

    # plans=None: full-depth walk in every group
    assert grouped_streamed_pages(None, 4, 8, n_groups=3) == [32, 32, 32]
    # per-group plans, None entries degrade to the full walk
    plans = (((2, 2), (8, 2)), None)
    assert grouped_streamed_pages(plans, 4, 8, n_groups=2) == [20, 32]
    # a single bare plan fans out to every group
    assert grouped_streamed_pages(((2, 4),), 4, 8, n_groups=2) == [8, 8]


def test_decode_walk_pages_counts_live_columns():
    """The decode walk copies each slot's pages from the P-page block
    holding its first live column through its last live column — one
    page for an idle slot — plus one page per count-padding row."""
    from repro.kernels.ops import decode_walk_pages

    eff, bs, p = [1, 5, 17, 0], 4, 4          # 1, 2, 5 and 1 columns
    assert decode_walk_pages(eff, [0, 0, 0, 0], bs, p) == 9
    # a windowed slot 2 retired 4 columns: its walk starts at block 1
    assert decode_walk_pages(eff, [0, 0, 4, 0], bs, p) == 5
    # an unaligned start walks from the block holding it
    assert decode_walk_pages(eff, [0, 0, 3, 0], bs, p) == 9
    # a start past an idle slot's single column still walks one page
    assert decode_walk_pages(eff, [6, 0, 0, 0], bs, p) == 9
    # a bucketed plan's 6 rows hold 2 zero-length padding rows
    assert decode_walk_pages(eff, [0] * 4, bs, p, ((2, 2), (8, 4))) == 11


# ---------------------------------------------------------------------------
# serve-stack integration
# ---------------------------------------------------------------------------

def _submit_trace(cb, vocab, lens=(5, 9, 3, 6), new_tokens=3):
    for uid, t in enumerate(lens):
        cb.submit(Request(uid=uid, prompt=_prompt(uid, t, vocab),
                          max_new_tokens=new_tokens))


def test_metrics_off_drain_makes_zero_registry_calls(model):
    """The metrics-OFF contract: telemetry=None means the whole drain
    performs no inc/set/observe anywhere in the process, and the tokens
    are bit-identical to a telemetry-attached drain of the same trace."""
    cfg, params = model

    def drain(tel):
        cb = ContinuousBatcher(
            cfg, params, n_slots=2, cache_len=32, paged=True,
            block_size=4, telemetry=tel,
        )
        _submit_trace(cb, cfg.vocab_size)
        return cb.run_until_drained()

    before = mutation_count()
    off = drain(None)
    assert mutation_count() == before, (
        "uninstrumented drain touched the metrics registry"
    )
    clk = ManualClock(0.0, tick=0.001)
    tel = ServeTelemetry(registry=MetricsRegistry(clock=clk), clock=clk)
    on = drain(tel)
    assert on == off
    assert mutation_count() > before


def test_tick_lifecycle_conservation(model):
    """submitted == finished + active + queued after EVERY tick, read
    entirely off the registry (counters + end-of-tick gauges)."""
    cfg, params = model
    clk = ManualClock(0.0, tick=0.001)
    tel = ServeTelemetry(registry=MetricsRegistry(clock=clk), clock=clk)
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=32, paged=True, block_size=4,
        telemetry=tel,
    )
    _submit_trace(cb, cfg.vocab_size, lens=(5, 9, 3, 6, 4), new_tokens=3)
    r = tel.registry
    n_ticks = 0
    while cb.queue or any(s is not None for s in cb.slots):
        cb.step()
        n_ticks += 1
        assert n_ticks < 100
        submitted = r.counter("serve_requests_submitted").value
        finished = r.counter("serve_requests_finished").value
        active = r.gauge("serve_active_slots").value
        queued = r.gauge("serve_queue_depth").value
        assert submitted == finished + active + queued, (
            submitted, finished, active, queued
        )
        for g in r.find("pool_free_pages"):
            assert g.min >= 0
    assert r.counter("serve_ticks").value == n_ticks


def test_traced_tokens_match_scheduler_outputs(model):
    """Per-request traced token counts equal the scheduler's generated
    lists: tokens_out == len(generated), decode_events == len - 1 (the
    first token comes from prefill). Includes the finish-at-prefill
    path (max_new_tokens=1 => zero decode events)."""
    cfg, params = model
    clk = ManualClock(0.0, tick=0.001)
    tel = ServeTelemetry(registry=MetricsRegistry(clock=clk), clock=clk)
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=32, paged=True, block_size=4,
        telemetry=tel,
    )
    for uid, (t, mnt) in enumerate([(5, 3), (9, 1), (3, 4), (6, 2)]):
        cb.submit(Request(uid=uid, prompt=_prompt(uid, t, cfg.vocab_size),
                          max_new_tokens=mnt))
    results = cb.run_until_drained()
    assert set(results) == set(tel.traces)
    for uid, toks in results.items():
        tr = tel.traces[uid]
        assert tr.tokens_out == len(toks), (uid, tr, toks)
        assert tr.decode_events == len(toks) - 1
        assert tr.finish_ts is not None
    # uid 1: finished AT prefill — no decode interval, so no TPOT sample
    assert tel.traces[1].tpot_s is None
    finish = {e["uid"]: e for e in tel.events.of("finish")}
    assert finish[1]["decode_events"] == 0
    # decode token conservation across the whole drain
    total_decode = sum(len(v) - 1 for v in results.values())
    assert tel.registry.counter("serve_decode_tokens").value == total_decode


def test_prefix_stats_flow_into_gauges(model):
    """Prefix-index hits surface through on_admit's cached-token count
    and the per-tick prefix gauges."""
    cfg, params = model
    clk = ManualClock(0.0, tick=0.001)
    tel = ServeTelemetry(registry=MetricsRegistry(clock=clk), clock=clk)
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=48, paged=True, block_size=4,
        prefix=True, telemetry=tel,
    )
    shared = _prompt(100, 12, cfg.vocab_size)
    for uid in range(3):
        sfx = _prompt(uid, 4, cfg.vocab_size)
        cb.submit(Request(uid=uid, prompt=jnp.concatenate([shared, sfx]),
                          max_new_tokens=2))
    cb.run_until_drained()
    served = cb.prefix.cached_tokens_served
    assert served > 0
    assert tel.registry.counter("serve_prefix_cached_tokens").value == served
    assert tel.registry.gauge(
        "pool_prefix_cached_tokens_served"
    ).value == served
    cached = [e["cached_tokens"] for e in tel.events.of("admit")]
    assert sum(cached) == served


def test_deadlock_emits_structured_event(model):
    """The deadlock diagnostic goes through the event log (one event
    with per-group free counts) while the raised message is unchanged."""
    cfg, params = model
    tel = ServeTelemetry(clock=ManualClock(0.0, tick=0.001))
    cb = ContinuousBatcher(
        cfg, params, n_slots=1, cache_len=16, paged=True, block_size=4,
        telemetry=tel,
    )
    pc = cb.pcache
    while pc.n_free > 1:
        pc._ref[pc.free_blocks.popleft()] = 1
    cb.submit(Request(uid=0, prompt=_prompt(0, 8, cfg.vocab_size),
                      max_new_tokens=4))
    with pytest.raises(RuntimeError, match="deadlock at tick 1.*pools:.*g0"):
        cb.run_until_drained(max_ticks=10_000)
    (ev,) = tel.events.of("deadlock")
    assert ev["tick"] == 1 and ev["queued"] == 1
    assert ev["free_by_group"] == {"0": 1}
    assert "pools:" in ev["diagnostic"]


def test_streamed_bytes_accounted_per_launch(model):
    """Every paged launch lands in the kernel counters, and the per-tick
    series sums to the total."""
    cfg, params = model
    clk = ManualClock(0.0, tick=0.001)
    tel = ServeTelemetry(registry=MetricsRegistry(clock=clk), clock=clk)
    cb = ContinuousBatcher(
        cfg, params, n_slots=2, cache_len=32, paged=True, block_size=4,
        telemetry=tel,
    )
    _submit_trace(cb, cfg.vocab_size)
    cb.run_until_drained()
    total = tel.streamed_bytes_total
    assert total > 0
    assert sum(tel.tick_streamed_bytes) == total
    launches = tel.registry.counter
    n_prefill = launches("kernel_launches", {"kind": "prefill"}).value
    n_decode = launches("kernel_launches", {"kind": "decode"}).value
    assert n_prefill == 4          # one per admitted request
    assert 0 < n_decode <= cb.ticks
    by_kind = (
        launches("kernel_streamed_bytes", {"kind": "prefill"}).value
        + launches("kernel_streamed_bytes", {"kind": "decode"}).value
    )
    assert by_kind == total
    # the decode walk copies live pages only: the rest of every launch's
    # 2 x 8-column extent is skipped, and what it copies costs less than
    # the full walk would
    pc = cb.pcache
    (pool,) = pc.pools
    extent = n_decode * 2 * pc.max_blocks_per_slot
    skipped = launches("kernel_skipped_pages", {"kind": "decode"}).value
    assert 0 < skipped < extent
    decode_bytes = launches("kernel_streamed_bytes", {"kind": "decode"}).value
    assert decode_bytes == (
        (extent - skipped) * len(pool.layers) * pc.page_layer_bytes
    )
