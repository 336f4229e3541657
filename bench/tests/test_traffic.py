"""The generator: the same seed gives the same requests, and every seed
gives the same work in another order, slice by slice."""

import collections
import os

import numpy as np
import pytest

from bench import traffic

BURST = {"arrivals": {
    "phases": [{"seconds": 5, "rate_per_s": 4.0},
               {"seconds": 15, "rate_per_s": 0.5}],
    "ramp_s": 10, "prompt_len_pool": [20, 40, 80, 160],
    "output_len": {"median": 32, "sigma": 0.8, "min": 8, "max": 128},
    "shared_prefix": {"len_pool": [48, 96], "share": 0.5}}}


#: every mix under `bench/traffic/`, and a bursty one with shared prefixes
MIXES = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(traffic.BENCH_DIR, "traffic")) if f.endswith(".json"))
ARRIVAL_MIXES = [m for m in MIXES if "arrivals" in traffic.load_mix(m)]


@pytest.fixture(params=MIXES + ["burst"])
def mix(request):
    if request.param == "burst":
        return BURST
    return traffic.load_mix(request.param)


def _key(specs):
    return [(s.due_s, s.max_new_tokens, s.prompt.tolist()) for s in specs]


def _slice_of(mix, seconds):
    a = mix["arrivals"]
    edges = traffic.slices(a["phases"], a.get("ramp_s", 0), seconds)
    return lambda s: max(i for i, (lo, _, _) in enumerate(edges)
                         if lo <= s.due_s)


def test_same_seed_same_requests(mix):
    a = traffic.generate(mix, 2**33 + 7, 51, 151936)
    b = traffic.generate(mix, 2**33 + 7, 51, 151936)
    assert _key(a) == _key(b)


def test_every_seed_gets_the_same_work(mix):
    a = traffic.generate(mix, 1, 51, 151936)
    b = traffic.generate(mix, 2**31 + 9, 51, 151936)
    assert _key(a) != _key(b)
    assert sorted(s.max_new_tokens for s in a) == \
        sorted(s.max_new_tokens for s in b)
    assert sum(len(s.prompt) for s in a) == sum(len(s.prompt) for s in b)
    if "shared_prefix" not in mix.get("arrivals", {}):
        assert sorted(len(s.prompt) for s in a) == \
            sorted(len(s.prompt) for s in b)
    if "arrivals" not in mix:
        return
    # the seed draws the schedule and the lengths' order ...
    assert [s.due_s for s in a] != [s.due_s for s in b]
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    # ... and every slice keeps its count of requests
    sl = _slice_of(mix, 51)
    assert collections.Counter(map(sl, a)) == collections.Counter(map(sl, b))
    win = [s for s in a if s.due_s >= 0]
    # the window holds its phases' rates times their seconds
    ph, t, expect = mix["arrivals"]["phases"], -mix["arrivals"]["ramp_s"], 0.0
    for k in range(1000):
        p = ph[k % len(ph)]
        lo, hi = max(t, 0.0), min(t + p["seconds"], 51.0)
        expect += p["rate_per_s"] * max(hi - lo, 0.0)
        t += p["seconds"]
    assert abs(len(win) - expect) <= 1
    assert max(s.due_s for s in a) < 51
    assert min(s.due_s for s in a) == pytest.approx(
        -mix["arrivals"].get("ramp_s", 0))


@pytest.mark.parametrize("name", ARRIVAL_MIXES)
def test_each_slice_spans_the_length_distribution(name):
    """Every slice of an open-loop window that holds five requests or
    more gets long and short prompts: no seed piles the longest into one
    slice."""
    mix = traffic.load_mix(name)
    sl = _slice_of(mix, 51)
    pool = sorted(mix["arrivals"]["prompt_len_pool"])
    for seed in (3, 2**32 + 1, 77):
        by = collections.defaultdict(list)
        for s in traffic.generate(mix, seed, 51, 151936):
            by[sl(s)].append(len(s.prompt))
        for lens in by.values():
            if len(lens) >= 5:
                assert min(lens) < pool[len(pool) // 2] < max(lens)


def test_bursts_and_shared_prefixes():
    specs = traffic.generate(BURST, 5, 40, 1000)
    due = np.array([s.due_s for s in specs])
    on = (due + 10) % 20 < 5          # the 5 s bursts, from the ramp's start
    assert on.sum() > 2 * (~on).sum()
    pre = traffic.warm_prompts(BURST, 5, 1000)
    prefixes = [p[:48] for p in pre[0][-2:]]
    shared = [s for s in specs
              if any(np.array_equal(s.prompt[:48], p) for p in prefixes)]
    assert 0.3 < len(shared) / len(specs) < 0.7
    # the second warm drain holds every body length behind every prefix
    assert len(pre[1]) == 2 * len(BURST["arrivals"]["prompt_len_pool"])
