"""Mean host time of a `serve/token_sync` span: the per-slot token
reads of one decode tick (`int(nxt[i])` for each active slot), with the
finishes and page frees they lead to (`bench/program_spans.py`)."""

from bench import program_spans


def read(ctx):
    return program_spans.mean_host_ms(ctx, "serve/token_sync")
