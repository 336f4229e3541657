"""The harness finds architectures, configurations, traffic mixes and
per-layer metrics by name from files of their own: a new mix and a new
metric, added as new files beside copies of the existing ones, and a
new architecture with its reference, configuration, cell, mix and CPU
settings, are picked up with no edit to any file that was there."""

import json
import os
import shutil
import subprocess
import sys

import smoke
from bench import harness, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: the open-loop cell the new mix and metrics ride on
TEMPLATE = smoke.first_cell(open_loop=True)

DUMMY_METRIC = '''"""Requests still queued when the window closed (test metric)."""


def read(ctx):
    return float(len(ctx.rec.decode_batch))
'''


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return root, bench


#: a new arrival shape as data alone: 4x bursts for 1 s in every 3 s,
#: and a shared system prompt in front of half of the prompts
BURST_MIX = {"arrivals": {
    "phases": [{"seconds": 1.0, "rate_per_s": 8.0},
               {"seconds": 2.0, "rate_per_s": 2.0}],
    "ramp_s": 1.0, "prompt_len_pool": [3, 9, 20],
    "output_len": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
    "shared_prefix": {"len_pool": [32], "share": 0.5}}}

DUMMY_E2E = '''"""Requests that finished inside the window (test metric)."""


def read(ctx):
    return float(sum(1 for tr in ctx.tracks
                     if len(tr.times) == tr.spec.max_new_tokens))
'''


def test_new_mix_and_metric_files_are_picked_up(tmp_path):
    root, bench = _checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    # new files only
    (root / "bench" / "traffic" / "dummy_burst.json").write_text(
        json.dumps(BURST_MIX))
    (root / "bench" / "metrics" / "dummy_ticks.py").write_text(DUMMY_METRIC)
    (root / "bench" / "metrics" / "dummy_done.py").write_text(DUMMY_E2E)
    cell = json.loads((root / "bench" / "cells" /
                       f"{TEMPLATE}.json").read_text())
    cell["server"]["prefix"] = True
    config = harness.load_cell(TEMPLATE).config
    name = f"{config}.dummy_burst"
    (root / "bench" / "cells" / f"{name}.json").write_text(json.dumps(cell))
    bench["workloads"].append({
        "name": name, "config": config,
        "traffic": "dummy_burst", "chips": 1, "why": "test"})
    bench["end_to_end"].append({
        "name": "dummy_done", "unit": "count", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": [name]})
    bench["per_layer"].append({
        "name": "dummy_ticks", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "serve.scheduler",
        "moves": "dummy_done", "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell(name, root=str(root))
    assert cell.mix == BURST_MIX
    assert [m["name"] for m in cell.per_layer][-1] == "dummy_ticks"
    assert [m["name"] for m in cell.end_to_end][-1] == "dummy_done"
    read = harness.load_reader("dummy_ticks", str(root / "bench"))

    class Ctx:
        rec = harness.Recorder()

    Ctx.rec.decode_batch = [3, 4]
    assert read(Ctx) == 2.0

    # the whole run, at a small size, from the new files
    small = smoke.settings(TEMPLATE)
    cell.hf = smoke.model.smoke_config(cell.hf)
    cell.server = dict(cell.server, **small["server"])
    cell.check = dict(cell.check, max_logit_gap=small["max_logit_gap"],
                      **smoke.SMOKE_CHECK)
    r = smoke.run(cell, 2**31 + 3, seconds=3.0)
    assert r["correct"], r["checks"]
    assert r["metrics"]["dummy_done"]["value"] > 0
    assert r["attempted"] == 12          # 2 s at 2/s, then 1 s at 8/s
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_traced_run_reports_the_new_metric(tmp_path, monkeypatch):
    root, bench = _checkout(tmp_path)
    (root / "bench" / "metrics" / "dummy_ticks.py").write_text(DUMMY_METRIC)
    bench["per_layer"] = [{
        "name": "dummy_ticks", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "serve.scheduler",
        "moves": "output_tok_s"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = smoke.cell(TEMPLATE)
    found = harness.load_cell(TEMPLATE, root=str(root))
    cell.per_layer, cell.bench_dir = found.per_layer, found.bench_dir
    # a CPU run has no published peaks; the test metric reads none
    monkeypatch.setitem(peaks.PEAKS, "cpu", {})
    r = smoke.run(cell, 5, seconds=2.0, trace=True)
    assert r["metrics"]["dummy_ticks"]["value"] > 0
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert r["correct"]


def test_every_entry_of_the_benchmark_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(ROOT, "bench")
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        hf = smoke.model.load_config(c["name"])
        for sub, name in (("arch", hf["model_type"]),
                          ("reference", hf["reference"])):
            assert os.path.isfile(os.path.join(b, sub, name + ".py"))
    for w in bench["workloads"]:
        for sub, name in (("cells", w["name"]), ("traffic", w["traffic"]),
                          ("configs", w["config"]),
                          ("tests/cells", w["name"])):
            assert os.path.isfile(os.path.join(b, sub, name + ".json"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
        moved = e2e[m["moves"]]
        for w in m.get("workloads", []):
            assert "workloads" not in moved or w in moved["workloads"]


#: a second architecture as new files alone, laid out as in a checkout:
#: `swa_example`, a dense decoder without QKV bias, a 24-key window on
#: alternate layers at the smoke size (`bench/arch/swa_example.py`)
NEW_ARCH = os.path.join(HERE, "fixtures", "new_arch")


def test_new_architecture_joins_by_new_files_alone(tmp_path):
    root, bench = _checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    src = os.path.join(NEW_ARCH, "bench")
    for d, _, files in os.walk(src):
        for f in files:
            dst = root / "bench" / os.path.relpath(os.path.join(d, f), src)
            assert not dst.exists(), dst
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(os.path.join(d, f), dst)
    with open(os.path.join(NEW_ARCH, "benchmark_entries.json")) as f:
        entries = json.load(f)
    bench["configs"] += entries["configs"]
    bench["workloads"] += entries["workloads"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    config = entries["configs"][0]["name"]
    cell = entries["workloads"][0]["name"]

    # the checkout's own tests, which take their configurations and
    # cells from its BENCHMARK.json, now cover the new ones: served
    # through `ContinuousBatcher(paged=True)` against the new reference,
    # its float8 control, a broken step, its counts and readers
    tests = [
        f"bench/tests/test_reference.py::"
        f"test_paged_prefill_and_decode_logits_match_reference[{config}]",
        f"bench/tests/test_reference.py::"
        f"test_served_gaps_read_zero_on_the_references_own_tokens[{config}]",
        f"bench/tests/test_shapes.py::"
        f"test_every_configuration_counts_work_in_each_layer[{config}]",
        f"bench/tests/test_faults.py::test_sound_run_is_correct[{cell}]",
        f"bench/tests/test_faults.py::"
        f"test_broken_step_is_not_correct[{cell}-token_altered]",
        f"bench/tests/test_control.py::"
        f"test_float8_control_fails_where_the_program_passes[{cell}]",
        "bench/tests/test_discovery.py::"
        "test_every_entry_of_the_benchmark_has_its_files",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", *tests],
        capture_output=True, text=True, env=env, timeout=900, cwd=root)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert f"{len(tests)} passed" in p.stdout, p.stdout[-2000:]
    assert {p: p.read_bytes() for p in before} == before
