"""99th percentile of how late the harness sent each request of the
window: submit time less due time, on the harness's clock."""

import numpy as np


def read(ctx):
    if not ctx.lags_s:
        return None
    return 1e3 * float(np.percentile(ctx.lags_s, 99))
