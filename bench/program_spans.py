"""Host time of the program's own profiler spans in a traced window.

`ContinuousBatcher.step()` writes `serve/tick` and, inside it, one span
per phase of the tick (`serve/admit`, `serve/prefill_launch`,
`serve/first_token`, `serve/decode_launch`, `serve/token_sync`). They
sit on the thread that holds the benchmark's `bench/step` spans, so
`trace_reduce.load` keeps them. A span's host time is its length less
the device busy time inside it, on the profiler's clock: the definition
`host_ms_per_tick` uses for `bench/step`. A program that writes no such
span gives None, never an error.
"""

from __future__ import annotations

from typing import List

from bench import trace_reduce


def host_ms(ctx, name: str) -> List[float]:
    """Host milliseconds of each span named `name` in the window."""
    return [(d - trace_reduce.covered(ctx.red.busy, s, s + d)) / 1e6
            for n, s, d in ctx.red.spans if n == name]


def mean_host_ms(ctx, name: str):
    """Mean host milliseconds of the spans named `name`, or None."""
    ms = host_ms(ctx, name)
    return sum(ms) / len(ms) if ms else None
