"""Perf-attribution + regression-gate tests (DESIGN.md §14).

Three layers:

  * unit — `predict_streamed_pages` re-derives exactly what
    `make_bucket_plan` + `plan_streamed_pages` compute; plan-signature
    labels; the `plans_enabled` gate; `CompileWatcher` accounting
    against fake executables (incl. the list-wrapped and failing
    `cost_analysis` shapes);
  * integration — a pow2 geometric trace drained on the fp32 smoke
    model through the interpreted Pallas path: the model error is
    EXACTLY zero on every launch (both sides are structural), roofline
    fractions partition the predicted HBM time, and the observed
    compile count equals the bounded set the pow2 plan structure
    predicts — with zero new compiles on an identical second wave;
  * gate — `regress.compare` direction semantics (including the
    required demonstration that an injected 2x streamed-byte
    regression FAILS the gate), the `check_regress` CLI end-to-end
    against a temp results dir, and history append/read round-trips.
"""

import importlib.util
import json
import pathlib

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.tpu_gold import TPU_V5E
from repro.kernels.ops import (
    is_bucket_plan,
    make_bucket_plan,
    plan_streamed_pages,
)
from repro.models import init_lm
from repro.obs import (
    CompileWatcher,
    ManualClock,
    MetricsRegistry,
    ServeTelemetry,
    plan_signature,
    plans_enabled,
    predict_streamed_pages,
)
from repro.obs import perf as perf_mod
from repro.obs import regress
from repro.serve import ContinuousBatcher, Request

ARCH = "qwen2-1.5b"
REPO = pathlib.Path(__file__).parent.parent


def _prompt(uid: int, t: int, vocab: int) -> jnp.ndarray:
    return jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(11), uid), (t,), 0, vocab
    ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# unit: the analytic predictor
# ---------------------------------------------------------------------------

def test_predict_streamed_pages_matches_plan():
    """The predictor must be THE SAME function of the needs vector as
    the dispatch: re-derive the pow2 plan and sum its launch walks.
    Any divergence here would show up as nonzero model error."""
    tw = 16
    for needs in ([1], [1, 2, 4, 8], [3, 3, 3], [16, 16, 16, 16],
                  [1, 15, 7, 2, 9], [5], [2, 2, 2, 2, 2, 2, 2]):
        n = len(needs)
        plan, _ = make_bucket_plan(None, 0, tw, needs=needs)
        assert predict_streamed_pages(needs, n, tw) == \
            plan_streamed_pages(plan, n, tw), needs
        assert predict_streamed_pages(needs, n, tw, bucketed=False) \
            == n * tw


def test_plan_signature_labels():
    assert plan_signature(None) == "single"
    assert plan_signature(((2, 1), (4, 1))) == "2x1+4x1"
    assert plan_signature((((1, 2),), None)) == "1x2|-"
    assert plan_signature((None, ((8, 4),))) == "-|8x4"


def test_plans_enabled_gate():
    """Mirrors the ops.bucket_args gate: strategy 'none' and the oracle
    impl never build plans; 'auto' resolves to the oracle off-TPU."""
    assert plans_enabled("pow2", "pallas_interpret")
    assert not plans_enabled("none", "pallas_interpret")
    assert not plans_enabled("pow2", "ref")
    assert not plans_enabled("pow2", "auto")  # CPU: auto -> ref


def test_rel_err_semantics():
    assert perf_mod._rel_err(0, 0) == 0.0
    assert perf_mod._rel_err(5, 0) == 1.0  # predicted where none measured
    assert perf_mod._rel_err(110, 100) == pytest.approx(0.1)


class _FakeCompiled:
    def __init__(self, cost):
        self._cost = cost

    def cost_analysis(self):
        if isinstance(self._cost, Exception):
            raise self._cost
        return self._cost


def test_compile_watcher_accounting():
    r = MetricsRegistry(clock=ManualClock())
    w = CompileWatcher(r)
    w.on_compile("decode", ((2, 1),), 0.5,
                 _FakeCompiled({"flops": 10.0, "bytes accessed": 20.0}))
    w.on_compile("decode", ((2, 1),), 0.25,
                 _FakeCompiled([{"flops": 5.0}]))  # list-wrapped API
    w.on_compile("prefill", None, 0.1,
                 _FakeCompiled(RuntimeError("backend reports nothing")))
    assert w.total == 3
    assert w.by_step() == {"decode": 2, "prefill": 1}
    assert r.counter("serve_recompiles_total",
                     {"step": "decode", "plans": "2x1"}).value == 2
    assert r.counter("serve_recompiles_total",
                     {"step": "prefill", "plans": "single"}).value == 1
    s = w.summary()
    assert s["compiles"][0]["hlo_bytes"] == 20.0
    assert s["compiles"][0]["memory_s"] == pytest.approx(
        20.0 / w.chip.hbm_bandwidth)
    assert s["compiles"][1]["hlo_flops"] == 5.0
    assert s["compiles"][1]["hlo_bytes"] == 0.0
    assert s["compiles"][2]["hlo_flops"] == 0.0
    assert ("decode", "2x1") in s["distinct_plan_signatures"]
    assert all("raw_plans" not in rec for rec in s["compiles"])
    assert sum(h.count for h in r.find("serve_compile_walltime_s")) == 3


# ---------------------------------------------------------------------------
# integration: pow2 geometric trace through the interpreted Pallas path
# ---------------------------------------------------------------------------

GEO_LENS = (4, 8, 16, 31)  # page needs 1, 2, 4, 8 at block_size 4


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_config(ARCH, smoke=True), dtype="float32")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def geo_drain(model):
    """Drain the geometric trace twice through ONE batcher (shared jit
    cache): wave 1 populates the compile set, wave 2 replays identical
    lengths and must hit it everywhere."""
    from repro.serve.compiled import trace_count

    cfg, params = model
    clk = ManualClock(0.0, tick=0.001)
    tel = ServeTelemetry(registry=MetricsRegistry(clock=clk), clock=clk)
    # cache_len=64 / block_size=4 -> 16-deep tables: every bucket bound
    # min(next_pow2(need), 16) stays a power of two
    cb = ContinuousBatcher(
        cfg, params, n_slots=4, cache_len=64, paged=True, block_size=4,
        kernel_impl="pallas_interpret", bucket_strategy="pow2",
        telemetry=tel,
    )
    traces0 = trace_count()
    for uid, t in enumerate(GEO_LENS):
        cb.submit(Request(uid=uid, prompt=_prompt(uid, t, cfg.vocab_size),
                          max_new_tokens=3))
    cb.run_until_drained()
    compiles_first = tel.compile_watcher().total
    for uid, t in enumerate(GEO_LENS):
        cb.submit(Request(uid=100 + uid,
                          prompt=_prompt(100 + uid, t, cfg.vocab_size),
                          max_new_tokens=3))
    results = cb.run_until_drained()
    return {
        "cb": cb, "tel": tel, "results": results,
        "compiles_first": compiles_first,
        "traces_delta": trace_count() - traces0,
    }


def test_model_error_exactly_zero(geo_drain):
    """The acceptance bar: predicted streamed bytes match measured
    EXACTLY (both derive from the same plan structure) on every
    instrumented launch of the geometric trace."""
    tel = geo_drain["tel"]
    s = tel.perf.summary()
    assert s["model_error_max"] == 0.0
    assert set(s["phases"]) == {"prefill", "decode"}
    for st in s["phases"].values():
        assert st["launches"] > 0
        assert st["model_error_max"] == 0.0
        assert st["predicted_bytes"] == st["measured_bytes"]
        # grade ordering: live floor <= what streamed <= full-depth walk
        assert st["live_bytes"] <= st["measured_bytes"] \
            <= st["full_walk_bytes"]
        assert 0.0 < st["bucketing_savings"] < 1.0
        assert 0.0 < st["walk_efficiency"] <= 1.0
    # every model-error observation landed in the <=0.1% bucket
    hists = tel.registry.find("perf_model_error")
    assert hists
    for h in hists:
        assert h.count > 0 and h.counts[0] == h.count


def test_roofline_fractions_partition_total(geo_drain):
    s = geo_drain["tel"].perf.summary()
    assert s["chip"] == TPU_V5E.name
    phases = s["phases"].values()
    assert sum(st["roofline_fraction"] for st in phases) \
        == pytest.approx(1.0)
    assert s["roofline_total_s"] == pytest.approx(
        sum(st["roofline_s"] for st in phases))
    for st in phases:
        assert st["roofline_s"] == pytest.approx(
            st["measured_bytes"] / TPU_V5E.hbm_bandwidth)


def test_recompile_set_matches_pow2_prediction(geo_drain):
    """PR 4's bounded-recompile-set property as a live metric: the
    compile count equals the number of distinct (step, plan-signature
    [, padded prompt length]) keys the launch log actually exercised —
    the jit cache key is (plans, arg shapes), and only prefill varies
    its token shape."""
    tel, cb = geo_drain["tel"], geo_drain["cb"]
    w = tel.compile_watcher()
    bs = cb.pcache.block_size
    expected = set()
    for phase, plans, _n_rows, eff in tel.perf.launch_log:
        sig = plan_signature(plans)
        if phase == "prefill":
            pad = -(-eff[0] // bs) * bs
            expected.add(("prefill", sig, pad))
        else:
            expected.add(("decode", sig))
    assert w.total == len(expected) > 0
    # every compiled plan draws from the pow2 (bound, count) grid
    for rec in w.compiles:
        raw = rec["raw_plans"]
        if raw is None:
            continue
        group_plans = (raw,) if is_bucket_plan(raw) else raw
        for p in group_plans:
            for bound, count in (p or ()):
                assert bound & (bound - 1) == 0, rec["plans"]
                assert count & (count - 1) == 0, rec["plans"]
    # the registry counters and walltime histograms tell the same story
    ctr = sum(c.value for c in tel.registry.find("serve_recompiles_total"))
    assert ctr == w.total
    wall = sum(h.count for h in tel.registry.find(
        "serve_compile_walltime_s"))
    assert wall == w.total


def test_second_wave_hits_compile_cache(geo_drain):
    """An identical second wave adds ZERO compiles (the bounded set
    saturates), and every jit trace corresponded to exactly one
    compile (the AOT signature cache IS the compile cache)."""
    tel = geo_drain["tel"]
    assert tel.compile_watcher().total == geo_drain["compiles_first"]
    assert geo_drain["traces_delta"] == tel.compile_watcher().total
    # wave 2 actually ran: its uids all finished
    assert all(100 + u in geo_drain["results"] for u in range(4))


def test_compile_records_capture_hlo_costs(geo_drain):
    """Per-compile cost_analysis capture (the analyze_compiled idiom):
    every record carries positive walltime and the executable's bytes
    accessed, plus roofline terms at the device spec."""
    w = geo_drain["tel"].compile_watcher()
    assert w.total > 0
    for rec in w.compiles:
        assert rec["walltime_s"] > 0
        assert rec["hlo_bytes"] > 0
        assert rec["memory_s"] == pytest.approx(
            rec["hlo_bytes"] / TPU_V5E.hbm_bandwidth)
    assert geo_drain["tel"].registry.find("serve_compiled_hlo_bytes")


def test_telemetry_summary_includes_perf_sections(geo_drain):
    s = geo_drain["tel"].summary()
    assert s["perf"]["model_error_max"] == 0.0
    assert s["recompiles"]["total"] == \
        geo_drain["tel"].compile_watcher().total


def test_predict_launch_grades(geo_drain):
    """Direct predictor call against the live pool geometry: the three
    byte grades are ordered, and the applicable grade follows the
    plans_enabled gate (bucketed for the Pallas path, full-depth for
    the oracle)."""
    pc = geo_drain["cb"].pcache
    eff = [5, 9, 17, 32]
    p1 = perf_mod.predict_launch(
        pc, eff, None, 4, strategy="pow2", kernel_impl="pallas_interpret")
    assert p1.live_bytes <= p1.bucketed_bytes <= p1.full_bytes
    assert p1.bytes_total == p1.bucketed_bytes == sum(p1.bytes_by_group)
    assert p1.roofline_s() == pytest.approx(
        p1.bytes_total / TPU_V5E.hbm_bandwidth)
    p2 = perf_mod.predict_launch(
        pc, eff, None, 4, strategy="pow2", kernel_impl="ref")
    assert p2.bytes_total == p2.full_bytes == p1.full_bytes
    # a decode launch's applicable grade is its block walk's copied
    # pages: at least the live ones, within the plan's extent
    p3 = perf_mod.predict_launch(
        pc, eff, None, 4, strategy="pow2", kernel_impl="pallas_interpret",
        phase="decode")
    assert p3.live_bytes <= p3.bytes_total <= p3.bucketed_bytes
    assert p3.pages_by_group == pc.decode_walk_pages(
        eff, tuple(
            make_bucket_plan(None, 0, pc.max_blocks_per_slot, needs=n)[0]
            for n in pc.bucket_needs(eff)
        ))


# ---------------------------------------------------------------------------
# the regression gate
# ---------------------------------------------------------------------------

_BASE = {
    "serve.paged.streamed_bytes_total": 100000.0,
    "serve.paged.decode_tokens": 48.0,
    "kernel.gather_reduction": 0.875,
    "serve.perf.model_error_max": 0.0,
}


def test_compare_identical_passes():
    violations, notes = regress.compare(dict(_BASE), _BASE)
    assert violations == [] and notes == []


def test_compare_fails_on_2x_byte_regression():
    """The ISSUE's required demonstration: doubling the streamed bytes
    must trip the gate."""
    cur = dict(_BASE)
    cur["serve.paged.streamed_bytes_total"] *= 2
    violations, _ = regress.compare(cur, _BASE)
    assert [v.metric for v in violations] == \
        ["serve.paged.streamed_bytes_total"]
    assert violations[0].direction == "high_bad"
    assert "increased" in str(violations[0])


def test_compare_direction_semantics():
    # improvement on a high_bad metric passes, with a note
    cur = dict(_BASE)
    cur["serve.paged.streamed_bytes_total"] = 90000.0
    violations, notes = regress.compare(cur, _BASE)
    assert not violations
    assert any("within band" in n for n in notes)
    # exact metric: ANY drift is a violation
    cur = dict(_BASE)
    cur["serve.paged.decode_tokens"] = 49.0
    violations, _ = regress.compare(cur, _BASE)
    assert [v.metric for v in violations] == ["serve.paged.decode_tokens"]
    # low_bad: a decrease beyond the band fails ...
    cur = dict(_BASE)
    cur["kernel.gather_reduction"] = 0.5
    violations, _ = regress.compare(cur, _BASE)
    assert [v.metric for v in violations] == ["kernel.gather_reduction"]
    # ... a decrease within the 0.01 absolute band passes
    cur["kernel.gather_reduction"] = 0.870
    violations, _ = regress.compare(cur, _BASE)
    assert not violations
    # model error creeping past its absolute band fails
    cur = dict(_BASE)
    cur["serve.perf.model_error_max"] = 0.02
    violations, _ = regress.compare(cur, _BASE)
    assert [v.metric for v in violations] == ["serve.perf.model_error_max"]


def test_compare_missing_and_new_metrics():
    cur = dict(_BASE)
    del cur["serve.paged.decode_tokens"]
    cur["serve.brand_new_metric"] = 7.0
    violations, notes = regress.compare(cur, _BASE)
    assert [v.metric for v in violations] == ["serve.paged.decode_tokens"]
    assert "missing" in violations[0].reason
    assert any("new metric" in n for n in notes)


def test_tolerance_spec_covers_headline_set():
    tol = regress.tolerance_spec()
    assert set(tol) == {k for k, *_ in regress.HEADLINE_SPECS}
    assert all(t["direction"] in ("exact", "high_bad", "low_bad", "both")
               for t in tol.values())
    # exact metrics carry no band; banded metrics carry one
    assert tol["serve.paged.decode_tokens"]["direction"] == "exact"
    assert tol["serve.paged.streamed_bytes_total"]["rel_tol"] == 0.01


def test_pinned_baselines_cover_headline_specs():
    """The checked-in baselines were pinned from a real bench run and
    must cover the full gated set with zero model error."""
    blob = regress.load_baselines(str(REPO / "benchmarks/baselines.json"))
    assert set(blob["metrics"]) == set(regress.tolerance_spec())
    assert blob["metrics"]["serve.perf.model_error_max"] == 0.0
    assert blob["metrics"]["kernel.model_error_max"] == 0.0
    assert blob["metrics"]["prefix.tokens_bit_exact"] == 1.0
    assert blob["tolerances"] == regress.tolerance_spec()


def test_history_roundtrip(tmp_path):
    path = str(tmp_path / "history.jsonl")
    assert regress.read_history(path) == []
    r1 = {"schema": 1, "metrics": {"a": 1.0}, "config_hash": "x"}
    r2 = {"schema": 1, "metrics": {"a": 2.0}, "config_hash": "x"}
    regress.append_history(path, r1)
    regress.append_history(path, r2)
    assert regress.read_history(path) == [r1, r2]


def test_config_hash_stable():
    assert regress.config_hash(["a", "b"]) == regress.config_hash(["a", "b"])
    assert regress.config_hash(["a", "b"]) != regress.config_hash(["a"])
    assert len(regress.config_hash([])) == 12


def _write_serve_results(results_dir, scale_bytes=1.0, decode_tokens=48):
    results_dir.mkdir(exist_ok=True)
    blob = {
        "paged": {
            "decode_tokens": decode_tokens, "prefill_tokens": 72,
            "ticks": 15,
            "streamed_bytes_total": int(162816 * scale_bytes),
            "tok_per_s": 100.0, "wall_s": 0.5,
            "perf": {"model_error_max": 0.0},
            "recompiles": {"total": 4},
        },
        "dense": {"decode_tokens": 48, "tok_per_s": 90.0},
        "prefill_padding_waste": 0.438,
    }
    (results_dir / "serve_bench.json").write_text(json.dumps(blob))


def _load_check_regress():
    spec = importlib.util.spec_from_file_location(
        "check_regress_mod", REPO / "benchmarks" / "check_regress.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_regress_cli_end_to_end(tmp_path):
    """Pin, pass, then demonstrably FAIL on an injected 2x streamed-byte
    regression — with both runs (good and bad) recorded in history."""
    cr = _load_check_regress()
    results = tmp_path / "results"
    baselines = str(tmp_path / "baselines.json")
    history = str(tmp_path / "history.jsonl")
    _write_serve_results(results)
    argv_base = ["--results", str(results), "--baselines", baselines,
                 "--history", history]
    assert cr.main(argv_base + ["--pin"]) == 0
    assert cr.main(argv_base) == 0
    assert len(regress.read_history(history)) == 1
    # inject the regression: the paged drain now streams 2x the bytes
    _write_serve_results(results, scale_bytes=2.0)
    assert cr.main(argv_base) == 1
    assert len(regress.read_history(history)) == 2  # bad runs recorded too
    # an exact-metric change (token parity broken) also fails
    _write_serve_results(results, decode_tokens=47)
    assert cr.main(argv_base + ["--no-append"]) == 1
    assert len(regress.read_history(history)) == 2  # --no-append held
    # an empty results dir fails loudly rather than passing vacuously
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cr.main(["--results", str(empty), "--baselines", baselines,
                    "--no-append"]) == 1


def test_check_regress_missing_baselines(tmp_path):
    cr = _load_check_regress()
    results = tmp_path / "results"
    _write_serve_results(results)
    assert cr.main(["--results", str(results),
                    "--baselines", str(tmp_path / "nope.json"),
                    "--history", str(tmp_path / "h.jsonl")]) == 1
