"""Small copies of the benchmark's cells for tests on the CPU.

The configurations and cells are BENCHMARK.json's. Each cell's CPU
settings are in `bench/tests/cells/<workload>.json`: `mix` (top-level
keys of the traffic mix that replace the cell's), `server` (settings
over the cell's), and `max_logit_gap`, the check's limit at this size,
with the readings it was set from in `limit_from`. A cell added with
such a file is tested by every test that walks `CELLS`.
"""

import json
import os
import time

from bench import harness, model

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CONFIGS = [c["name"] for c in BENCHMARK["configs"]]
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
SMOKE_CHECK = dict(sample_requests=16, sample_tokens=600)


def settings(name: str) -> dict:
    with open(os.path.join(harness.BENCH_DIR, "tests", "cells",
                           f"{name}.json")) as f:
        return json.load(f)


def cell(name: str, **hf_over) -> harness.Cell:
    c = harness.load_cell(name)
    s = settings(name)
    c.hf = model.smoke_config(c.hf, **hf_over)
    c.mix = dict(c.mix, **s["mix"])
    c.server = dict(c.server, **s["server"])
    c.check = dict(c.check, max_logit_gap=s["max_logit_gap"], **SMOKE_CHECK)
    return c


def first_cell(open_loop: bool) -> str:
    """The first cell whose mix has open-loop arrivals (or has none)."""
    return next(n for n in CELLS
                if ("arrivals" in harness.load_cell(n).mix) == open_loop)


def _no_cache(root=None):
    """Tests compile in memory only: the checkout's persistent cache is
    for the benchmark's runs on the chip."""
    return None


harness.enable_compile_cache = _no_cache


def run(c: harness.Cell, seed: int, seconds: float = 4.0, *,
        kernel_impl: str = "ref", sabotage=None, trace: bool = False,
        control: bool = False) -> dict:
    return harness.run_cell(c, seed, seconds, trace,
                            t_process=time.perf_counter(),
                            require_tpu=False, kernel_impl=kernel_impl,
                            log=lambda m: None, sabotage=sabotage,
                            control=control)
