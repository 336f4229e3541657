"""The comparison that decides `correct`.

Once the window has closed and the program's state is freed, a sample
of the sequences the program served, drawn from the seed and holding
the longest, is run once through the plain float32 reference
(`bench/reference/`). The number compared is the widest gap by which
the reference's logit of a served (greedy) token lies below the
reference's best logit at that position. Each cell's limit is in its
file under `bench/cells/`, with the readings it was set from in
PERF.md.
"""

from __future__ import annotations

import importlib
from typing import List, Sequence, Tuple

import numpy as np


def reference(hf: dict):
    return importlib.import_module(f"bench.reference.{hf['reference']}")


def sample(served: Sequence[Tuple[np.ndarray, List[int]]], seed: int,
           n_max: int, tokens: int) -> List[int]:
    """Indices of the sequences to compare: the longest, then others in
    an order drawn from the seed, until `tokens` served tokens or
    `n_max` sequences."""
    if not served:
        return []
    total = [len(p) + len(s) for p, s in served]
    first = int(np.argmax(total))
    rest = [i for i in np.random.default_rng([int(seed), 5]).permutation(
        len(served)) if i != first]
    out, n = [first], len(served[first][1])
    for i in rest:
        if len(out) >= n_max or n >= tokens:
            break
        out.append(int(i))
        n += len(served[i][1])
    return out


def compare(weights, hf, served, seed: int, check: dict, *, pad_to: int,
            control: bool = False):
    """`(readings, control_readings, n)`: the program's readings beside
    their limits, {name: {"value", "limit"}}; with `control`, the
    float8 control's, put in the program's place on the same sequences
    and held to the same limits (else None); and the number of served
    tokens compared."""
    ref = reference(hf)
    idx = sample(served, seed, check["sample_requests"],
                 check["sample_tokens"])
    worst, cworst, n = 0.0, 0.0, 0
    for i in idx:
        prompt, toks = served[i]
        seq = list(np.asarray(prompt)) + list(toks)
        gap, cgap = ref.served_gaps(weights, hf, seq, len(prompt),
                                    len(toks), pad_to=pad_to,
                                    control=control)
        worst = max(worst, float(gap.max()))
        if cgap is not None:
            cworst = max(cworst, float(cgap.max()))
        n += len(toks)
    limit = check["max_logit_gap"]
    out = {"max_logit_gap": {"value": worst if idx else float("inf"),
                             "limit": limit}}
    ctl = None
    if control:
        ctl = {"max_logit_gap": {"value": cworst if idx else float("inf"),
                                 "limit": limit}}
    return out, ctl, n
