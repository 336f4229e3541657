"""90th percentile of time to first token, from each request's due
time, over every request due in the window (`bench/samples.py`)."""

from bench import samples


def read(ctx):
    return samples.percentile_ms(samples.ttft_s(ctx), 90)
