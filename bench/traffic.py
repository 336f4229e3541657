"""The one traffic generator: reads a mix from `bench/traffic/<mix>.json`.

A mix is data, with up to two parts:

- `sessions`: conversations whose contexts (`context_len_pool`, one
  length per session) are built before the window; in the window each
  decodes up to `max_new_tokens`, with no arrivals.
- `arrivals`: independent users; requests fall due on a schedule
  whatever the server does. `phases` is a list of `{seconds,
  rate_per_s}` repeated from the start of a `ramp_s` ramp before the
  window to its close: one phase is steady Poisson traffic, an on/off
  pair is bursts. Prompt lengths come from `prompt_len_pool` (quantiles
  of the source's distribution), output lengths from a clipped
  lognormal (`output_len`: median, sigma, min, max). An optional
  `shared_prefix` (`len_pool`, `share`) puts one of a few shared
  prefixes (a system prompt, a document) in front of that share of the
  prompts.

Every seed gets the same work, in another order. Each phase occurrence
is a slice with a fixed number of requests, whose gaps are quantiles of
Exp(1) in an order drawn from `--seed`. The window's (and, apart, the
ramp's) prompt and output lengths are quantiles of their distributions,
dealt to the slices so that each slice gets its share of every part of
the distribution (the seed picks which), and then ordered within the
slice by the seed. So `--seed` draws the order of the gaps, which
request gets which prompt and output length, which requests share a
prefix, the token ids and (in `bench/model.py`) the weights; the load in
each slice, and with it the work of the window, stays the same.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Spec:
    """One generated request."""

    uid: int
    prompt: np.ndarray            # int32 token ids
    max_new_tokens: int
    #: seconds after the window opens that the request falls due
    #: (negative during the ramp); None for a session built in set-up
    due_s: Optional[float] = None


def load_mix(name: str, root: str = BENCH_DIR) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if not set(mix) & {"sessions", "arrivals"}:
        raise ValueError(f"mix {name!r}: neither sessions nor arrivals")
    return mix


def _quantiles(n: int, inv) -> np.ndarray:
    return np.asarray([inv((i + 0.5) / n) for i in range(n)])


def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    z = _quantiles(n, NormalDist().inv_cdf)
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def pool_lengths(n: int, pool) -> np.ndarray:
    """n quantiles of the distribution a pool of lengths stands for."""
    pool = np.sort(np.asarray(pool, int))
    return pool[(np.arange(n) * len(pool)) // n]


def exponential_gaps(n: int) -> np.ndarray:
    """n Poisson inter-arrival gaps: the quantiles of Exp(1)."""
    return _quantiles(n, lambda p: -math.log(1.0 - p))


def slices(phases, ramp_s: float, seconds: float):
    """(start, span, n) of every phase occurrence from -ramp_s to
    `seconds`, cut at 0; n rounds the expected count so that the counts
    add up to the rate over the whole span."""
    out, t, k, expect, given = [], -float(ramp_s), 0, 0.0, 0
    while t < seconds:
        ph = phases[k % len(phases)]
        end = t + float(ph["seconds"])
        for lo, hi in ((t, min(end, 0.0)), (max(t, 0.0), min(end, seconds))):
            if hi > lo:
                expect += ph["rate_per_s"] * (hi - lo)
                n = int(round(expect)) - given
                given += n
                out.append((lo, hi - lo, n))
        t, k = end, k + 1
    return out


def _deal(values: np.ndarray, counts: List[int], rng) -> List[np.ndarray]:
    """Sorted `values` dealt to slices of `counts`: slice j's k-th place
    stands at (k + u) / counts[j] of the distribution (u drawn), and the
    places of every slice, sorted together, take the values in order."""
    places = [((k + rng.random()) / n, j) for j, n in enumerate(counts)
              for k in range(n)]
    order = sorted(range(len(places)), key=lambda i: places[i][0])
    got: List[list] = [[] for _ in counts]
    for v, i in zip(np.sort(values), order):
        got[places[i][1]].append(v)
    return [rng.permutation(np.asarray(g, int)) for g in got]


def prompt_lengths(mix: dict) -> List[int]:
    """Every prompt body length the mix's arrivals can send."""
    return sorted(set(int(t) for t in mix["arrivals"]["prompt_len_pool"]))


def _prefixes(arr: dict, rng, vocab: int) -> List[np.ndarray]:
    sp = arr.get("shared_prefix") or {}
    return [rng.integers(0, vocab, int(n), dtype=np.int32)
            for n in sp.get("len_pool", [])]


def warm_prompts(mix: dict, seed: int, vocab: int) -> List[List[np.ndarray]]:
    """Prompts whose serving, drain after drain, compiles every program
    the mix's arrivals call: each prompt body length, and each again
    behind each shared prefix once the first drain has cached it."""
    if "arrivals" not in mix:
        return []
    rng = np.random.default_rng([int(seed), 11])
    pre = _prefixes(mix["arrivals"], np.random.default_rng([int(seed), 13]),
                    vocab)
    body = [rng.integers(0, vocab, t, dtype=np.int32)
            for t in prompt_lengths(mix)]
    drains = [body + [np.concatenate([p, body[0]]) for p in pre]]
    if pre:
        drains.append([np.concatenate([p, b]) for p in pre for b in body])
    return drains


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Spec]:
    rng = np.random.default_rng([int(seed), 7])
    out: List[Spec] = []
    if "sessions" in mix:
        s = mix["sessions"]
        ctx = rng.permutation(np.asarray(s["context_len_pool"], int))
        out += [Spec(i, rng.integers(0, vocab, int(t), dtype=np.int32),
                     int(s["max_new_tokens"])) for i, t in enumerate(ctx)]
    if "arrivals" in mix:
        a = mix["arrivals"]
        pre = _prefixes(a, np.random.default_rng([int(seed), 13]), vocab)
        share = float((a.get("shared_prefix") or {}).get("share", 0.0))
        o = a["output_len"]
        sl = slices(a["phases"], a.get("ramp_s", 0.0), seconds)
        # the ramp and the window each get their own quantile sets
        for seg in ([x for x in sl if x[0] < 0], [x for x in sl if x[0] >= 0]):
            k_shared = 0
            counts = [n for _, _, n in seg]
            n = sum(counts)
            if n == 0:
                continue
            plen = _deal(pool_lengths(n, a["prompt_len_pool"]), counts, rng)
            olen = _deal(lognormal_lengths(n, o["median"], o["sigma"],
                                           o["min"], o["max"]), counts, rng)
            for j, (start, span, c) in enumerate(seg):
                if c == 0:
                    continue
                g = rng.permutation(exponential_gaps(c))
                due = start + span * (np.cumsum(g) - g) / g.sum()
                shared = set(rng.permutation(c)[:int(round(share * c))])
                for i in range(c):
                    p = rng.integers(0, vocab, int(plen[j][i]),
                                     dtype=np.int32)
                    if i in shared and pre:
                        # prefixes in turn: each is used as often on
                        # every seed
                        p = np.concatenate([pre[k_shared % len(pre)], p])
                        k_shared += 1
                    out.append(Spec(len(out), p, int(olen[j][i]),
                                    float(due[i])))
    return out
