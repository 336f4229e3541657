"""Pages reserved for admitted requests but not yet drawn, as a share of
the pool's pages, averaged over ticks (the program's `pool_gauges`)."""


def read(ctx):
    r = ctx.rec.reserved_unused
    return sum(r) / len(r) if r else None
