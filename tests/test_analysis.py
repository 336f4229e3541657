"""repro.analysis: each seeded violation caught by exactly its rule id,
the real repo passes clean, and the gate exits nonzero on new findings.

Fixture layout mirrors the real checks: jaxpr rules get tiny traced
functions, kernel-contract rules get fixture kernel files, repo rules
get a miniature `src/repro` tree under tmp_path."""

import json
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import run_all
from repro.analysis.__main__ import main
from repro.analysis.findings import Finding, diff_findings, load_baseline
from repro.analysis.jaxpr_lint import lint_jaxpr, lint_serve_steps
from repro.analysis.kernel_contracts import check_kernel_file
from repro.analysis.repo_lint import check_repo_conventions


def rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# layer 1: jaxpr rules on seeded traces
# ---------------------------------------------------------------------------

def test_jx001_host_callback_in_hot_path():
    def f(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct((4,), jnp.float32), x
        )

    closed = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    assert rules(lint_jaxpr(closed, "<fixture>")) == {"JX001"}


def test_jx002_float64_creep():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0
        )(jnp.ones((4,), jnp.float32))
    found = lint_jaxpr(closed, "<fixture>")
    assert rules(found) == {"JX002"}
    assert all(f.severity == "error" for f in found)


def test_jx003_whole_pool_materialization():
    pool = jnp.zeros((64, 64), jnp.float32)
    closed = jax.make_jaxpr(lambda p: p * 2.0)(pool)
    found = lint_jaxpr(closed, "<fixture>", pool_nbytes=pool.nbytes)
    assert rules(found) == {"JX003"}
    # the same program is fine when the threshold is above its buffers
    assert lint_jaxpr(
        closed, "<fixture>", pool_nbytes=pool.nbytes + 1
    ) == []


def test_jx003_pool_operand_mapping_and_kernel_internal_suppression():
    """Mapping a pool-sized operand whole into a kernel (no ANY space,
    no blocking) fires per OPERAND — but the pool-sized `mul` INSIDE
    the kernel body is suppressed: refs inside a kernel are the point,
    only out-of-kernel materialization counts."""
    from jax.experimental import pallas as pl

    def tiny(x):
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        return pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True,
        )(x)

    pool = jnp.zeros((64, 64), jnp.float32)
    closed = jax.make_jaxpr(tiny)(pool)
    found = lint_jaxpr(closed, "<fixture>", pool_nbytes=pool.nbytes)
    assert rules(found) == {"JX003"}
    # exactly the two whole-pool mappings (input + output), nothing
    # from the kernel-internal mul
    assert len(found) == 2
    assert all("memory_space" in f.message for f in found)


def test_jx004_switch_branches_vs_layer_groups():
    from jax.experimental import pallas as pl

    def tiny(x):
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        return pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True,
        )(x)

    def f(x):
        return jax.lax.switch(jnp.int32(0), [tiny, tiny, tiny], x)

    closed = jax.make_jaxpr(f)(jnp.ones((4, 4), jnp.float32))
    found = lint_jaxpr(closed, "<fixture>", expected_switch_branches=2)
    assert rules(found) == {"JX004"}
    assert lint_jaxpr(closed, "<fixture>", expected_switch_branches=3) == []


def test_jx005_weak_typed_step_input():
    closed = jax.make_jaxpr(lambda x: x + 1)(1.0)
    found = lint_jaxpr(closed, "<fixture>")
    assert rules(found) == {"JX005"}
    assert found[0].severity == "warning"


# ---------------------------------------------------------------------------
# layer 2: kernel contracts on fixture kernels
# ---------------------------------------------------------------------------

def _kernel_fixture(tmp_path, src):
    p = tmp_path / "fixture_kernel.py"
    p.write_text(textwrap.dedent(src))
    return check_kernel_file(str(p), "fixture_kernel.py")


def test_kc103_missing_dma_wait(tmp_path):
    found = _kernel_fixture(tmp_path, """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def bad_kernel(bt_ref, q_ref, kp_hbm, o_ref, k_buf, sem):
            copy = pltpu.make_async_copy(
                kp_hbm.at[pl.ds(0, 1)], k_buf.at[pl.ds(0, 1)], sem
            )
            copy.start()
            o_ref[...] = q_ref[...]
        """)
    assert rules(found) == {"KC103"}
    assert "never awaited" in found[0].message


def test_kc103_missing_dma_start(tmp_path):
    found = _kernel_fixture(tmp_path, """
        from jax.experimental.pallas import tpu as pltpu

        def bad_kernel(kp_hbm, k_buf, sem, o_ref):
            copy = pltpu.make_async_copy(kp_hbm, k_buf, sem)
            copy.wait()
            o_ref[...] = k_buf[...]
        """)
    assert rules(found) == {"KC103"}
    assert "never started" in found[0].message


def test_kc104_wait_before_start(tmp_path):
    found = _kernel_fixture(tmp_path, """
        from jax.experimental.pallas import tpu as pltpu

        def bad_kernel(kp_hbm, k_buf, sem, o_ref):
            prev = pltpu.make_async_copy(kp_hbm, k_buf, sem)
            prev.wait()
            nxt = pltpu.make_async_copy(kp_hbm, k_buf, sem)
            nxt.start()
            o_ref[...] = k_buf[...]
        """)
    assert rules(found) == {"KC104"}


def test_kc101_whole_pool_vmem_spec(tmp_path):
    found = _kernel_fixture(tmp_path, """
        import functools
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _kernel(bt_ref, q_ref, kp_ref, o_ref):
            o_ref[...] = q_ref[...]

        def run(bt, q, kp):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4,),
                in_specs=[
                    pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                    pl.BlockSpec(memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                scratch_shapes=[],
            )
            kernel = functools.partial(_kernel)
            return pl.pallas_call(
                kernel, grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            )(bt, q, kp)
        """)
    assert rules(found) == {"KC101"}
    assert "in_specs[1]" in found[0].message


def test_kc102_operand_arity_mismatch(tmp_path):
    found = _kernel_fixture(tmp_path, """
        import functools
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _kernel(bt_ref, q_ref, kp_ref, o_ref):
            o_ref[...] = q_ref[...]

        def run(bt, q, kp):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4,),
                in_specs=[
                    pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                    pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                ],
                out_specs=pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                scratch_shapes=[],
            )
            kernel = functools.partial(_kernel)
            return pl.pallas_call(
                kernel, grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            )(bt, q)
        """)
    assert rules(found) == {"KC102"}
    assert "passes 2 operands" in found[0].message


def test_kc102_vararg_kernel_accepts_dual_layout(tmp_path):
    # a `*refs` kernel (the §16 quantized/fp dual-layout bodies) is in
    # contract as long as its NAMED positionals fit the implied count
    found = _kernel_fixture(tmp_path, """
        import functools
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _kernel(bt_ref, st_ref, *refs):
            pass

        def run(bt, st, q, kp):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(4,),
                in_specs=[
                    pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                    pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                ],
                out_specs=pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                scratch_shapes=[],
            )
            kernel = functools.partial(_kernel)
            return pl.pallas_call(
                kernel, grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            )(bt, st, q, kp)
        """)
    assert found == []


def test_kc102_vararg_kernel_named_overshoot(tmp_path):
    # ...but naming MORE positionals than the grid spec can supply
    # still shifts every ref out of slot, vararg or not
    found = _kernel_fixture(tmp_path, """
        import functools
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _kernel(a, b, c, d, e, *refs):
            pass

        def run(bt, q):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4,),
                in_specs=[
                    pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                ],
                out_specs=pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                scratch_shapes=[],
            )
            kernel = functools.partial(_kernel)
            return pl.pallas_call(
                kernel, grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            )(bt, q)
        """)
    assert rules(found) == {"KC102"}
    assert "names 5 positional refs" in found[0].message


def test_kc106_any_operands_without_dma_semaphore(tmp_path):
    found = _kernel_fixture(tmp_path, """
        import functools
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _kernel(bt_ref, q_ref, kp_ref, o_ref, k_buf):
            o_ref[...] = q_ref[...]

        def run(bt, q, kp):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4,),
                in_specs=[
                    pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                    pl.BlockSpec(memory_space=pltpu.ANY),
                ],
                out_specs=pl.BlockSpec((1, 4), lambda i, *_: (i, 0)),
                scratch_shapes=[
                    pltpu.VMEM((1, 4), jnp.float32),
                ],
            )
            kernel = functools.partial(_kernel)
            return pl.pallas_call(
                kernel, grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            )(bt, q, kp)
        """)
    assert rules(found) == {"KC106"}


@pytest.mark.parametrize(
    "walk", ["double_buffered_page_walk", "double_buffered_block_walk"]
)
def test_kc105_read_before_walk(tmp_path, walk):
    found = _kernel_fixture(tmp_path, f"""
        from repro.kernels.paged_common import {walk}

        def bad_kernel(step, n, bt_ref, kp, vp, k_buf, v_buf, sem, o_ref):
            early = k_buf[0]
            cur = {walk}(
                step, n, bt_ref, 4, kp, vp, k_buf, v_buf, sem
            )
            o_ref[...] = early + v_buf[cur]
        """)
    assert rules(found) == {"KC105"}
    assert "k_buf" in found[0].message


def test_real_kernels_pass_contracts():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from repro.analysis.kernel_contracts import check_kernel_contracts

    assert check_kernel_contracts(root) == []


# ---------------------------------------------------------------------------
# layer 3: repo conventions on a miniature src/repro tree
# ---------------------------------------------------------------------------

def _mini_repo(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / "src" / "repro" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    (tmp_path / "tests").mkdir(exist_ok=True)
    return str(tmp_path)


def test_rl201_rl203_rl204_seeded_engine(tmp_path):
    root = _mini_repo(tmp_path, {
        "serve/engine.py": """
            import time
            import jax

            class Engine:
                def __init__(self, telemetry):
                    self.telemetry = telemetry
                    self._decode = jax.jit(lambda x: x)

                def step(self):
                    self.telemetry.on_decode([1])
                    return time.time()
            """,
    })
    found = check_repo_conventions(root)
    by_rule = {f.rule: f for f in found}
    assert rules(found) == {"RL201", "RL203", "RL204"}
    assert "jax.jit" in by_rule["RL201"].message
    assert "on_decode" in by_rule["RL203"].message
    assert "time.time" in by_rule["RL204"].message


def test_rl203_accepts_the_guard_idioms(tmp_path):
    root = _mini_repo(tmp_path, {
        "serve/engine.py": """
            class Engine:
                def __init__(self, telemetry):
                    self.telemetry = telemetry
                    self.annotate = (
                        telemetry is not None and telemetry.profile
                    )
                    self.watcher = (
                        None if telemetry is None
                        else telemetry.compile_watcher()
                    )

                def step(self):
                    tel = self.telemetry
                    if tel is None:
                        return
                    tel.on_decode([1])

                def tick(self):
                    if self.telemetry is not None:
                        self.telemetry.end_tick(0, 0)
            """,
    })
    assert check_repo_conventions(root) == []


def test_rl202_impl_compare_outside_ops(tmp_path):
    root = _mini_repo(tmp_path, {
        "kernels/foo.py": """
            def pick(impl):
                if impl == "pallas":
                    return 1
                return 0
            """,
    })
    found = check_repo_conventions(root)
    assert rules(found) == {"RL202"}


def test_rl202_allowed_inside_ops(tmp_path):
    root = _mini_repo(tmp_path, {
        "kernels/ops.py": """
            import jax

            def resolve_impl(impl):
                if impl == "pallas":
                    return "native"
                return (
                    "native" if jax.default_backend() == "tpu" else "ref"
                )
            """,
    })
    assert check_repo_conventions(root) == []


def test_rl205_uncovered_mutator(tmp_path):
    root = _mini_repo(tmp_path, {
        "serve/paged_cache.py": """
            class LayerPagePool:
                def grow(self, slot, q_min, n_tokens):
                    self._owned[slot] = n_tokens

                def live_pages(self, slot):
                    return self._owned.get(slot, 0)

                def check_invariants(self, lengths, external):
                    pass
            """,
    })
    found = check_repo_conventions(root)
    assert rules(found) == {"RL205"}
    assert "grow" in found[0].message
    # a test file calling BOTH the mutator and check_invariants clears it
    (tmp_path / "tests" / "test_pool.py").write_text(textwrap.dedent("""
        def test_grow():
            pool.grow(0, 0, 4)
            pool.check_invariants([4], None)
        """))
    assert check_repo_conventions(root) == []


def test_rl206_dequant_outside_kernels(tmp_path):
    # dequantization escaping the kernels' page fold (§16): both the
    # import and the use are findings — the serve/models layers only
    # get the opaque `requantize_page_update` append primitive
    root = _mini_repo(tmp_path, {
        "serve/cache.py": """
            from repro.kernels.paged_common import dequantize_pages

            def peek(codes, scales):
                return dequantize_pages(codes, scales)
            """,
    })
    found = check_repo_conventions(root)
    assert rules(found) == {"RL206"}
    assert all("dequantize_pages" in f.message for f in found)


def test_rl206_allows_kernels_and_requantize(tmp_path):
    root = _mini_repo(tmp_path, {
        "kernels/paged_common.py": """
            def dequantize_pages(codes, scales):
                return codes * scales

            def load_kv_page(k_buf, v_buf, cur):
                return k_buf[cur], v_buf[cur]
            """,
        "models/attention.py": """
            from repro.kernels.paged_common import requantize_page_update

            def append(codes, scales, fn):
                return requantize_page_update(codes, scales, fn)
            """,
    })
    assert check_repo_conventions(root) == []


# ---------------------------------------------------------------------------
# the gate: baseline ratchet + nonzero exit on NEW findings
# ---------------------------------------------------------------------------

def test_gate_exit_codes_and_baseline_ratchet(tmp_path, capsys):
    root = _mini_repo(tmp_path, {
        "kernels/foo.py": """
            def pick(impl):
                if impl == "pallas":
                    return 1
            """,
    })
    json_path = str(tmp_path / "results" / "findings.json")
    base_path = str(tmp_path / "analysis" / "baseline.json")
    argv = ["--root", root, "--layers", "repo", "--json", json_path,
            "--baseline", base_path]

    # new violation, empty baseline -> gate fails
    assert main(argv + ["--gate"]) == 1
    blob = json.load(open(json_path))
    assert blob["counts"] == {
        "total": 1, "new": 1, "stale_baseline": 0,
        "by_rule": {"RL202": 1}, "by_severity": {"error": 1},
    }

    # baseline the finding -> gate passes (known debt)
    (tmp_path / "analysis").mkdir(exist_ok=True)
    with open(base_path, "w") as fh:
        json.dump({"findings": blob["findings"]}, fh)
    assert main(argv + ["--gate"]) == 0

    # a SECOND violation on top of the baselined one -> fails again
    p = tmp_path / "src" / "repro" / "kernels" / "foo.py"
    p.write_text(p.read_text() + textwrap.dedent("""
        def pick2(kernel_impl):
            return kernel_impl == "ref"
        """))
    assert main(argv + ["--gate"]) == 1
    blob = json.load(open(json_path))
    assert blob["counts"]["total"] == 2 and blob["counts"]["new"] == 1

    # fixing the baselined violation reports the entry as stale
    p.write_text(textwrap.dedent("""
        def pick(impl):
            return impl
        """))
    assert main(argv + ["--gate"]) == 0
    blob = json.load(open(json_path))
    assert blob["counts"]["stale_baseline"] == 1
    capsys.readouterr()


def test_finding_key_ignores_line_numbers():
    a = Finding("RL201", "f.py", 10, "error", "m")
    b = Finding("RL201", "f.py", 99, "error", "m")
    new, stale = diff_findings([a], [b.key])
    assert new == [] and stale == []


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(str(tmp_path / "nope.json")) == []


# ---------------------------------------------------------------------------
# the real repo is clean
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_clean_repo_all_layers():
    """The committed state passes every layer with ZERO findings — the
    committed analysis/baseline.json is empty and must stay empty."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = run_all(root)
    assert findings == [], "\n".join(str(f) for f in findings)
    baseline = load_baseline(os.path.join(root, "analysis", "baseline.json"))
    assert baseline == []


def test_serve_steps_trace_clean():
    """Layer 1 on the real compiled decode+prefill steps: no host
    callbacks, no f64, pools never materialized, dispatch switch matches
    the layer-group partition."""
    assert lint_serve_steps() == []
