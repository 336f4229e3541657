"""Mean number of slots a decode step advanced (the program's
`on_decode` hook), over the window's decode steps."""


def read(ctx):
    b = ctx.rec.decode_batch
    return sum(b) / len(b) if b else None
