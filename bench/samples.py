"""Host-clock samples of a run's requests, read by the end-to-end
metrics. Times are seconds from the window's opening; a token's time is
the end of the `step()` that produced it."""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def percentile_ms(values, q: float) -> Optional[float]:
    if not len(values):
        return None
    return 1e3 * float(np.percentile(np.asarray(values, float), q))


def ttft_s(ctx) -> List[float]:
    """First-token time less due time, for every request due in the
    window; one with no first token by the close counts at its elapsed
    time then."""
    return [min(tr.times[0] if tr.times else ctx.seconds, ctx.seconds)
            - tr.spec.due_s for tr in ctx.tracks
            if tr.spec.due_s is not None and tr.spec.due_s >= 0]


def tpot_s(ctx) -> List[float]:
    """Each request's mean gap between its tokens inside the window,
    where those tokens span a quarter second or more (the host clock's
    reach)."""
    out = []
    for tr in ctx.tracks:
        t = [x for x in tr.times if 0.0 <= x <= ctx.seconds]
        if len(t) >= 2 and t[-1] - t[0] >= 0.25:
            out.append((t[-1] - t[0]) / (len(t) - 1))
    return out
