"""Multi-device behaviour via subprocesses (8 forced host devices).

The parent test process keeps its single real CPU device; each case spawns
``python -c`` with XLA_FLAGS so jax initializes with 8 devices there.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout: int = 300) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    script = textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_reduction_schedules_match_psum():
    """linear ring / binary-hopping / rs-ag all equal the native psum
    (the paper's reduction networks, Table IV, as mesh collectives)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core.reduction import SCHEDULES, make_sharded_allreduce
        mesh = make_mesh((8,), ("x",))
        x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4) + 1
        ref = None
        for name in ("tree", "linear", "binary-hopping", "rs-ag"):
            f = make_sharded_allreduce(mesh, "x", name)
            y = np.asarray(f(x))
            if ref is None: ref = y
            np.testing.assert_allclose(y, ref, rtol=1e-6)
        print("ALL_EQUAL")
    """)
    assert "ALL_EQUAL" in out


def test_reduce_to_zero_binary_hopping():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.core.reduction import reduce_to_zero_binary_hopping
        mesh = make_mesh((8,), ("x",))
        x = (jnp.arange(8, dtype=jnp.float32) + 1).reshape(8, 1)
        f = jax.jit(jax.shard_map(
            lambda s: reduce_to_zero_binary_hopping(s, "x"),
            mesh=mesh, in_specs=P("x"), out_specs=P("x")))
        y = np.asarray(f(x))
        assert y[0, 0] == 36.0, y  # sum(1..8) lands on device 0
        print("WEST_OK")
    """)
    assert "WEST_OK" in out


def test_compressed_gradient_allreduce():
    """int8 error-feedback psum: close to exact mean, residual captures
    the quantization error (error feedback property)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import compressed_psum, init_residual
        mesh = make_mesh((8,), ("pod",))
        g = jnp.linspace(-1, 1, 8 * 64, dtype=jnp.float32).reshape(8, 64)
        grads = {"w": g}
        res = init_residual({"w": g})
        def f(grads, res):
            return compressed_psum(grads, res, "pod")
        fj = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=({"w": P("pod")}, {"w": P("pod")}),
            out_specs=({"w": P("pod")}, {"w": P("pod")})))
        (out, new_res) = fj(grads, res)
        exact = np.mean(np.asarray(g), axis=0)
        got = np.asarray(out["w"])[0]
        err = np.max(np.abs(got - exact)) / (np.max(np.abs(exact)) + 1e-9)
        assert err < 0.05, err
        # error feedback: residual equals the local quantization error
        assert float(np.max(np.abs(np.asarray(new_res["w"])))) > 0
        print("COMPRESS_OK", err)
    """)
    assert "COMPRESS_OK" in out


def test_data_parallel_train_step_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import init_lm
        from repro.optim import AdamWConfig, adamw_init
        from repro.train.step import make_train_step
        cfg = get_config("qwen2-1.5b", smoke=True)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size),
            "targets": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab_size),
        }
        step = make_train_step(cfg, AdamWConfig(lr=1e-3))
        p_ref, _, m_ref = jax.jit(step)(params, opt, batch)
        mesh = make_mesh((8,), ("data",))
        bsh = {k: NamedSharding(mesh, P("data")) for k in batch}
        psh = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
        batch_sharded = jax.device_put(batch, bsh["tokens"])
        p_dp, _, m_dp = jax.jit(step, in_shardings=(psh, None, bsh))(params, opt, batch)
        assert abs(float(m_ref["loss"]) - float(m_dp["loss"])) < 1e-3
        for a, b in zip(jax.tree_util.tree_leaves(p_ref), jax.tree_util.tree_leaves(p_dp)):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                       rtol=2e-2, atol=2e-4)
        print("DP_MATCH", float(m_ref["loss"]))
    """)
    assert "DP_MATCH" in out


def test_tensor_parallel_forward_matches():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.dist.sharding import sharding_rules
        from repro.launch.specs import param_shardings
        from repro.models import init_lm, forward
        cfg = get_config("granite-20b", smoke=True)  # MQA + plain MLP
        params = init_lm(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        ref, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, toks)
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh, sharding_rules(mesh):
            psh = param_shardings(jax.eval_shape(lambda: params), mesh)
            f = jax.jit(lambda p, t: forward(p, t, cfg)[0], in_shardings=(psh, None))
            tp = f(params, toks)
        np.testing.assert_allclose(np.asarray(ref, np.float32), np.asarray(tp, np.float32),
                                   rtol=3e-2, atol=3e-2)
        print("TP_MATCH")
    """)
    assert "TP_MATCH" in out


def test_elastic_checkpoint_restore_across_meshes():
    """Save sharded on 8 devices, restore onto 4 and onto 1 (elasticity)."""
    out = run_py("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import CheckpointManager
        mesh8 = make_mesh((8,), ("data",))
        w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        sharded = jax.device_put(w, NamedSharding(mesh8, P("data")))
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, {"w": sharded})
            mesh4 = make_mesh((4, 2), ("data", "model"))
            sh4 = {"w": NamedSharding(mesh4, P("model", "data"))}
            r4, _ = mgr.restore({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}, shardings=sh4)
            np.testing.assert_array_equal(np.asarray(r4["w"]), np.asarray(w))
            r1, _ = mgr.restore({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)})
            np.testing.assert_array_equal(np.asarray(r1["w"]), np.asarray(w))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


@pytest.mark.slow
def test_dryrun_single_cell_512_devices():
    """The real multi-pod dry-run path: one cell on the 2x16x16 mesh."""
    out = run_py("""
        from repro.launch.dryrun import run_cell
        r = run_cell("qwen2-1.5b", "decode_32k", multi_pod=True)
        assert r["status"] == "ok", r
        assert r["chips"] == 512
        rf = r["roofline"]
        assert rf["collective_s"] >= 0 and rf["memory_s"] > 0
        print("DRYRUN_OK", rf["bound"])
    """, devices=512, timeout=420)
    assert "DRYRUN_OK" in out
