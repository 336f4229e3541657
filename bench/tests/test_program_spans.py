"""The readers of the program's own spans on a small trace recorded on
the CPU (`record_serve_fixture.py`): a tiny paged `ContinuousBatcher`
drained tick by tick, each tick inside a `bench/step` span. On a trace
the program wrote no `serve/*` span into (`cpu_trace.xplane.pb`), every
reader gives None."""

import math
import os

import pytest

from bench import harness, program_spans, trace_reduce

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
#: each new reader and the span it reads
READERS = {
    "token_sync_ms": "serve/token_sync",
    "decode_launch_ms": "serve/decode_launch",
    "prefill_launch_ms": "serve/prefill_launch",
    "first_token_ms": "serve/first_token",
    "admit_ms_per_tick": "serve/admit",
}


class Ctx:
    def __init__(self, fixture):
        ev = trace_reduce.load(os.path.join(FIXTURES, fixture),
                               device_prefix="/host:CPU",
                               op_line="tf_XLAPjRtCpuClient")
        self.red = trace_reduce.reduce(ev)


@pytest.fixture(scope="module")
def ctx():
    return Ctx("cpu_serve_trace.xplane.pb")


def test_the_fixture_holds_the_program_spans(ctx):
    names = [n for n, _, _ in ctx.red.spans]
    steps = names.count("bench/step")
    assert steps > 5 and names.count("serve/tick") == steps
    assert names.count("serve/first_token") == 3
    assert names.count("serve/prefill_launch") == 1 + 3 + 1
    assert ctx.red.busy_s > 0


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_bounded_by_the_spans_it_reads(ctx, metric):
    span = READERS[metric]
    value = harness.load_reader(metric)(ctx)
    lengths = [d / 1e6 for n, _, d in ctx.red.spans if n == span]
    assert lengths
    assert math.isfinite(value) and value >= 0
    if metric == "admit_ms_per_tick":
        ticks = sum(1 for n, _, _ in ctx.red.spans if n == "serve/tick")
        assert value <= sum(lengths) / ticks
    else:
        assert value <= sum(lengths) / len(lengths)


def test_host_time_leaves_out_device_busy_time(ctx):
    host = program_spans.host_ms(ctx, "serve/token_sync")
    spans = [(s, d) for n, s, d in ctx.red.spans if n == "serve/token_sync"]
    for h, (s, d) in zip(host, spans):
        busy = trace_reduce.covered(ctx.red.busy, s, s + d)
        assert h == pytest.approx((d - busy) / 1e6)
        assert 0 <= h <= d / 1e6


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_gives_none_without_program_spans(metric):
    assert harness.load_reader(metric)(Ctx("cpu_trace.xplane.pb")) is None
