"""Median, over requests, of each one's mean gap between output tokens
inside the window (`bench/samples.py`)."""

from bench import samples


def read(ctx):
    return samples.percentile_ms(samples.tpot_s(ctx), 50)
