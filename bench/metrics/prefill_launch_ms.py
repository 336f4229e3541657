"""Mean host time of a `serve/prefill_launch` span: one prefill chunk's
page preparation, padding, bucket arguments, table snapshot and the
dispatch of the prefill program (`bench/program_spans.py`)."""

from bench import program_spans


def read(ctx):
    return program_spans.mean_host_ms(ctx, "serve/prefill_launch")
