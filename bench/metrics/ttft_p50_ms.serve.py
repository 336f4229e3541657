"""Median time to first token, from each request's due time, over every
request due in the window (`bench/samples.py`). Per layer: with 25–50
requests in a window the median's seed-to-seed spread is over half of
any bound the benchmark may set, so the mean (`ttft_mean_ms`) is the
end-to-end first-token metric."""

from bench import samples


def read(ctx):
    return samples.percentile_ms(samples.ttft_s(ctx), 50)
