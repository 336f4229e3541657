"""Output tokens produced inside the window, over its seconds."""


def read(ctx):
    n = sum(sum(1 for x in tr.times if 0.0 <= x <= ctx.seconds)
            for tr in ctx.tracks)
    return n / ctx.seconds
