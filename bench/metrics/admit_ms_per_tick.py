"""Host time of admission per scheduler tick: the host time of every
`serve/admit` span (queue scan, prefix lookup and page reservation for
one free slot; `bench/program_spans.py`) over the count of `serve/tick`
spans in the window."""

from bench import program_spans


def read(ctx):
    ticks = program_spans.host_ms(ctx, "serve/tick")
    if not ticks:
        return None
    return sum(program_spans.host_ms(ctx, "serve/admit")) / len(ticks)
