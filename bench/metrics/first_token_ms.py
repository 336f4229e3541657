"""Mean host time of a `serve/first_token` span: the argmax of a
request's last prefill chunk, the host read of it, and seating or
finishing its slot (`bench/program_spans.py`)."""

from bench import program_spans


def read(ctx):
    return program_spans.mean_host_ms(ctx, "serve/first_token")
