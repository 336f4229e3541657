"""Reduce a profiler trace (`.xplane.pb`) to what the metrics read.

`load` reads the file with `jax.profiler.ProfileData` into plain event
lists: device operations, device program executions and host spans.
`reduce` works on those lists only, so it is checked on a small trace
recorded on the CPU, where the "device" is the CPU client's thread.

Times are nanoseconds on the profiler's clock, which the host spans and
the device events share.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

#: substrings of the device operation names of the program's kernels
DECODE_KERNEL = "paged_decode_attention"
PREFILL_KERNEL = "paged_prefill_attention"


@dataclasses.dataclass
class Events:
    #: device operations, per device plane
    ops: Dict[str, List[Event]]
    #: executions of compiled programs, per device plane
    modules: Dict[str, List[Event]]
    #: host spans on the thread that drove the run
    spans: List[Event]


@dataclasses.dataclass
class Reduced:
    #: seconds in which some operation ran, averaged over devices
    busy_s: float
    window_s: float
    #: merged busy intervals of the first device, [(start, end)]
    busy: List[Tuple[float, float]]
    #: self time of each operation name, seconds, summed over devices
    op_seconds: Dict[str, float]
    #: every operation event of the first device
    ops: List[Event]
    modules: List[Event]
    spans: List[Event]
    #: idle seconds by the innermost host span open at the gap
    idle_by_span: Dict[str, float]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(files)}")
    return files[0]


def op_name(text: str) -> str:
    """An XLA op event's name is its whole HLO instruction on the TPU;
    keep the instruction's own name (`%paged_decode_attention.9 = ...`
    -> `paged_decode_attention.9`)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str, device_prefix: str = "/device:TPU:",
         op_line: str = "XLA Ops", module_line: str = "XLA Modules",
         span_prefix: str = "bench/") -> Events:
    """Device planes are those whose name starts with `device_prefix`;
    lines of such a plane whose name starts with `op_line` hold
    operations, and the line named `module_line` program executions.
    Host spans come from the host thread lines that hold a span whose
    name starts with `span_prefix`. A trace with no such device plane
    (a CPU run) takes the CPU client's threads as the device."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    if not any(p.name.startswith(device_prefix) for p in data.planes):
        # a CPU run (tests): the CPU client's threads are the device
        device_prefix, op_line = "/host:CPU", "tf_XLAPjRtCpuClient"
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name.startswith(op_line):
                    ops.setdefault(plane.name, []).extend(
                        (op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events if e.duration_ns > 0)
                elif line.name == module_line:
                    modules.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                if any(n.startswith(span_prefix) for n, _, _ in evs):
                    spans.extend(evs)
    return Events(ops=ops, modules=modules, spans=spans)


def merge(intervals: Sequence[Tuple[float, float]]):
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of [lo, hi) that merged `intervals` cover."""
    return sum(e - s for s, e in clip(intervals, lo, hi))


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds each name ran, less the time of events nested in it."""
    out: Dict[str, float] = {}
    stack: List[List] = []            # [name, end, child_ns, dur]

    def pop():
        name, _, child, dur = stack.pop()
        out[name] = out.get(name, 0.0) + max(dur - child, 0.0) / 1e9

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            pop()
        if stack:
            stack[-1][2] += d
        stack.append([name, s + d, 0.0, d])
    while stack:
        pop()
    return out


def label_points(spans: Sequence[Event], points: Sequence[float]):
    """The innermost (shortest) span open at each of the sorted
    `points`, or None."""
    order = sorted(spans, key=lambda e: e[1])
    active: List[Event] = []
    out, j = [], 0
    for t in points:
        while j < len(order) and order[j][1] <= t:
            active.append(order[j])
            j += 1
        active = [e for e in active if e[1] + e[2] > t]
        out.append(min(active, key=lambda e: e[2])[0] if active else None)
    return out


def reduce(ev: Events, window_span: str = "bench/window") -> Reduced:
    """Busy and idle time, op self times and idle gaps inside the host
    span named `window_span` (the measured window)."""
    win = [(s, s + d) for n, s, d in ev.spans if n == window_span]
    if len(win) != 1:
        raise RuntimeError(f"expected one {window_span!r} span, "
                           f"found {len(win)}")
    lo, hi = win[0]
    devices = sorted(ev.ops)
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    busy_each, op_seconds = [], {}
    for dev in devices:
        evs = [e for e in ev.ops[dev] if lo <= e[1] < hi]
        busy_each.append(clip(merge((s, s + d) for _, s, d in evs), lo, hi))
        for k, v in self_times(evs).items():
            op_seconds[k] = op_seconds.get(k, 0.0) + v
    busy = busy_each[0]
    busy_s = sum(sum(e - s for s, e in b) for b in busy_each)
    busy_s /= len(devices) * 1e9
    spans = [e for e in ev.spans if e[1] < hi and e[1] + e[2] > lo
             and e[0] != window_span]
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    idle: Dict[str, float] = {}
    labels = label_points(spans, [(a + b) / 2 for a, b in gaps])
    for (a, b), label in zip(gaps, labels):
        label = label or "(no host span)"
        idle[label] = idle.get(label, 0.0) + (b - a) / 1e9
    first = devices[0]
    return Reduced(
        busy_s=busy_s, window_s=(hi - lo) / 1e9,
        busy=busy, op_seconds=op_seconds,
        ops=sorted((e for e in ev.ops[first] if lo <= e[1] < hi),
                   key=lambda e: e[1]),
        modules=sorted((e for e in ev.modules.get(first, [])
                        if lo <= e[1] < hi), key=lambda e: e[1]),
        spans=spans, idle_by_span=idle,
    )


def modules_containing(red: Reduced, op_substr: str) -> List[Event]:
    """Program executions inside which an operation whose name holds
    `op_substr` ran."""
    hits = [s for n, s, _ in red.ops if op_substr in n]
    out, j = [], 0
    for m in red.modules:
        _, s, d = m
        while j < len(hits) and hits[j] < s:
            j += 1
        if j < len(hits) and hits[j] < s + d:
            out.append(m)
    return out


def op_time(red: Reduced, op_substr: str) -> Tuple[float, int]:
    """Seconds and count of operation events whose name holds
    `op_substr` (first device)."""
    evs = [d for n, _, d in red.ops if op_substr in n]
    return sum(evs) / 1e9, len(evs)


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])
            [:n]]
