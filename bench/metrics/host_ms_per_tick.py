"""Host time per scheduler tick: each `bench/step` span (one
`ContinuousBatcher.step()`) less the device busy time inside it, on the
profiler's clock, averaged over the window's ticks."""

from bench import trace_reduce


def read(ctx):
    steps = [(s, s + d) for n, s, d in ctx.red.spans if n == "bench/step"]
    if not steps:
        return None
    host = [(e - s) - trace_reduce.covered(ctx.red.busy, s, e)
            for s, e in steps]
    return sum(host) / len(host) / 1e6
