"""The trace reduction on a small trace recorded on the CPU
(`record_fixture.py`): three steps of a jitted matrix product inside a
window, 20 ms waits between them."""

import os

import pytest

from bench import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def red():
    ev = trace_reduce.load(FIXTURE, device_prefix="/host:CPU",
                           op_line="tf_XLAPjRtCpuClient")
    return trace_reduce.reduce(ev)


def test_window_busy_and_idle(red):
    assert 0.06 <= red.window_s <= 0.2          # three 20 ms waits
    assert 0 < red.busy_s < 0.5 * red.window_s
    assert red.busy_s == pytest.approx(
        sum(e - s for s, e in red.busy) / 1e9)


def test_op_self_times_name_the_program_ops(red):
    assert any(k.startswith("dot_general") for k in red.op_seconds)
    assert sum(red.op_seconds.values()) == pytest.approx(red.busy_s,
                                                         rel=0.05)


def test_idle_gaps_are_labelled_by_the_host_span(red):
    top = trace_reduce.top(red.idle_by_span, 1)[0]
    assert top[0] in ("bench/wait", "$time sleep")
    assert top[1] >= 0.055
    idle = sum(red.idle_by_span.values())
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
    steps = [s for s in red.spans if s[0] == "bench/step"]
    assert len(steps) == 3


def test_self_times_subtract_nested_events():
    evs = [("outer", 0.0, 100.0), ("inner", 10.0, 30.0),
           ("inner", 50.0, 20.0), ("next", 120.0, 10.0)]
    t = trace_reduce.self_times(evs)
    assert t == pytest.approx({"outer": 50e-9, "inner": 50e-9,
                               "next": 10e-9})


def test_modules_containing_picks_programs_that_ran_a_kernel():
    red = trace_reduce.Reduced(
        busy_s=0, window_s=0, busy=[], op_seconds={},
        ops=[("fusion.1", 1, 2), ("paged_decode_attention.9", 5, 3),
             ("paged_prefill_attention.6", 45, 3)],
        modules=[("jit_fn", 0, 20), ("jit_fn", 40, 20),
                 ("jit_other", 70, 5)],
        spans=[], idle_by_span={})
    dec = trace_reduce.modules_containing(red, trace_reduce.DECODE_KERNEL)
    pre = trace_reduce.modules_containing(red, trace_reduce.PREFILL_KERNEL)
    assert dec == [("jit_fn", 0, 20)] and pre == [("jit_fn", 40, 20)]


def test_op_name_keeps_the_instruction_name():
    text = ("%paged_decode_attention.9 = f32[64,2,6,128]{3,2,1,0} "
            "custom-call(s32[64,160]{1,0} %copy-done.2)")
    assert trace_reduce.op_name(text) == "paged_decode_attention.9"
    assert trace_reduce.op_name("dot_general.1") == "dot_general.1"
