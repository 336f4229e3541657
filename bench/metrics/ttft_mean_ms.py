"""Mean time to first token, from each request's due time, over every
request due in the window; one with no first token by the close counts
at its elapsed time then (`bench/samples.py`)."""

import numpy as np

from bench import samples


def read(ctx):
    v = samples.ttft_s(ctx)
    return 1e3 * float(np.mean(v)) if v else None
