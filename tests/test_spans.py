"""The scheduler's always-on profiler spans (`obs.tracing.SPANS`).

A tiny paged `ContinuousBatcher` with chunked prefill, staggered submits
and one request that finishes at its first token is drained under a
`jax.profiler` trace; the trace is read back with
`jax.profiler.ProfileData`. `--trace-dir` of the serving launcher is
checked the same way.
"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch.serve import parse_args, run
from repro.models import init_lm
from repro.obs import SPANS
from repro.serve import ContinuousBatcher, Request

TICK, PHASES = SPANS[0], SPANS[1:]


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_config("qwen2-1.5b", smoke=True),
                              dtype="float32")
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def _prompt(uid: int, t: int, vocab: int):
    return jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(5), uid), (t,), 0, vocab
    ).astype(jnp.int32)


class _Count:
    """Counts calls of a compiled step, passing them through."""

    def __init__(self, fn):
        self.fn, self.n = fn, 0

    def __call__(self, *a, **k):
        self.n += 1
        return self.fn(*a, **k)


def _drive(cb, uid0: int, vocab: int):
    """Staggered submits: one short request decodes alone for two
    ticks, then a 20-token prompt (three 8-token chunks) and a request
    that ends at its first token join. Returns the tokens by request
    and the ticks, prefill launches and decode launches made."""
    pre = cb._prefill_paged = _Count(cb._prefill_paged)
    dec = cb._decode_paged = _Count(cb._decode_paged)
    cb.submit(Request(uid0, _prompt(0, 6, vocab), 6))
    ticks = 0
    for _ in range(2):
        cb.step()
        ticks += 1
    cb.submit(Request(uid0 + 1, _prompt(1, 20, vocab), 3))
    cb.submit(Request(uid0 + 2, _prompt(2, 5, vocab), 1))
    while cb.queue or any(s is not None for s in cb.slots):
        cb.step()
        ticks += 1
    out = {u - uid0: cb.finished[u] for u in range(uid0, uid0 + 3)}
    return out, ticks, pre.n, dec.n


def _events(trace_dir):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1, files
    data = ProfileData.from_file(files[0])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name in SPANS]


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    cfg, params = model
    cb = ContinuousBatcher(cfg, params, n_slots=2, cache_len=48,
                           paged=True, block_size=4, prefill_chunk=8)
    untraced = _drive(cb, 0, cfg.vocab_size)      # compiles every step
    trace_dir = str(tmp_path_factory.mktemp("spans"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        got = _drive(cb, 100, cfg.vocab_size)
    finally:
        jax.profiler.stop_trace()
    return untraced, got, _events(trace_dir)


def test_every_span_is_written(traced):
    _, _, events = traced
    assert {n for n, *_ in events} == set(SPANS)


def test_phases_nest_in_ticks_and_never_overlap(traced):
    _, _, events = traced
    ticks = [(s, e) for n, s, e, _ in events if n == TICK]
    phases = sorted((s, e, n) for n, s, e, _ in events if n in PHASES)
    for s, e, n in phases:
        assert any(ts <= s and e <= te for ts, te in ticks), n
    for (_, e0, n0), (s1, _, n1) in zip(phases, phases[1:]):
        assert e0 <= s1, (n0, n1)


def test_span_counts_match_the_work(traced):
    (_, _, _, _), (_, ticks, prefills, decodes), events = traced
    count = {n: sum(1 for m, *_ in events if m == n) for n in SPANS}
    assert count["serve/tick"] == ticks
    assert count["serve/token_sync"] == count["serve/decode_launch"] \
        == decodes
    assert count["serve/prefill_launch"] == prefills == 1 + 3 + 1
    assert count["serve/first_token"] == 3
    assert count["serve/admit"] >= 3


def test_span_metadata_carry_request_ids(traced):
    _, _, events = traced
    first = [st for n, _, _, st in events if n == "serve/first_token"]
    assert sorted(st["uid"] for st in first) == [100, 101, 102]
    chunks = sorted((st["start"], st["end"]) for n, _, _, st in events
                    if n == "serve/prefill_launch" and st["uid"] == 101)
    assert chunks == [(0, 8), (8, 16), (16, 20)]


def test_traced_drain_serves_the_same_tokens(traced):
    (tokens, *_), (traced_tokens, *_), _ = traced
    assert traced_tokens == tokens
    assert len(tokens[2]) == 1                  # finished at prefill


def test_serve_launcher_trace_dir(tmp_path):
    out = run(parse_args([
        "--smoke", "--paged", "--requests", "2", "--slots", "2",
        "--new-tokens", "2", "--trace-dir", str(tmp_path),
    ]))
    assert out.tokens == 4
    names = {n for n, *_ in _events(str(tmp_path))}
    assert "serve/tick" in names
