"""Mean host time of a `serve/decode_launch` span: page preparation for
each active slot, bucket arguments, table snapshots and the dispatch of
the decode program and its argmax (`bench/program_spans.py`)."""

from bench import program_spans


def read(ctx):
    return program_spans.mean_host_ms(ctx, "serve/decode_launch")
