"""Entry points: the serving launcher's `run`, the compile-cache
placement, and `chip_smoke.py`'s refusal to run without a TPU."""

import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import serve
from repro.launch.compile_cache import DEFAULT_CACHE_DIR, REPO_ROOT, \
    enable_compilation_cache

SMOKE = os.path.join(REPO_ROOT, "chip_smoke.py")


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    return env


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    """No TPU: a non-zero exit naming the platform, and no result line —
    from the repository, and from a directory holding only the script."""
    script = SMOKE
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    out = subprocess.run(
        [sys.executable, script], env=_cpu_env(), cwd=os.path.dirname(script),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compilation_cache() == DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
        assert DEFAULT_CACHE_DIR == os.path.join(REPO_ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_respects_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper leaves JAX on that
    directory and compiled entries land there."""
    cache = tmp_path / "cache"
    body = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compilation_cache
        print(enable_compilation_cache())
        print(jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones((64,))).block_until_ready()
    """)
    out = subprocess.run(
        [sys.executable, "-c", body],
        env=_cpu_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                     JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                     JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0"),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())


def test_serve_run_smoke_warmup_compiles_nothing_while_serving():
    """`run` is the whole launch: after a warm-up drain of the same
    requests, the timed drain serves every token with no compile."""
    args = serve.parse_args([
        "--smoke", "--paged", "--requests", "4", "--slots", "2",
        "--prompt-len", "40", "--new-tokens", "3", "--warmup",
    ])
    out = serve.run(args)
    assert out.tokens == 4 * 3
    assert sorted(out.results) == [0, 1, 2, 3]
    assert out.compiles_setup > 0 and out.compiles_served == 0
    assert out.served_s > 0 and out.setup_s > 0
    lens = [int(r.prompt.shape[0]) for r in serve.make_requests(args, out.cfg)]
    assert len(set(lens)) > 1 and max(lens) <= 40
