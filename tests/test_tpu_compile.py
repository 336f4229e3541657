"""The main path's kernels compile for a described TPU v5e at
qwen2-1.5b's published widths (12 query heads, 2 KV heads, head dim 128,
16-token pages; MLP projections 1536 <-> 8960).

Nothing runs: each test lowers one kernel entry point with shapes placed
on a described chip and compiles it with the TPU compiler, which refuses
what Mosaic cannot lower (unaligned slices, unsupported casts and
reshapes, VMEM overflow) exactly as it would on the chip. The topology
is described inside a fixture, never at import, so every test worker
collects the same tests and only the one running this file loads the
TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitplane_gemv import bitplane_gemv
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_prefill import paged_prefill_attention

B, H, KV, HD, BS = 8, 12, 2, 128, 16     # qwen2-1.5b decode batch and heads
MB = 40                                  # table width: 640-token slots
NB = B * MB + 1                          # pool pages, scratch page 0
T = 512                                  # the widest smoke prefill
D_MODEL, D_FF = 1536, 8960


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """`shape(dims, dtype)` -> a ShapeDtypeStruct on the described chip.
    The persistent compilation cache is off meanwhile: what a compile
    for an absent chip writes cannot be read back."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _pools(shape, kv_dtype):
    """(K/V page pools, their per-page scales — int8 pools only)."""
    dt = jnp.bfloat16 if kv_dtype == "bf16" else jnp.int8
    pools = [shape((NB, KV, BS, HD), dt)] * 2
    scales = [shape((NB, KV), jnp.float32)] * 2 if kv_dtype == "int8" else []
    return pools, scales


def _scale_kwargs(scales):
    return dict(k_scales=scales[0], v_scales=scales[1]) if scales else {}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles(shape, kv_dtype):
    def step(q, kp, vp, bt, lengths, window, *scales):
        return paged_decode_attention(
            q, kp, vp, bt, lengths, window, **_scale_kwargs(scales)
        )

    i32 = jnp.int32
    pools, scales = _pools(shape, kv_dtype)
    text = _compiled_text(
        step, shape((B, H, HD), jnp.bfloat16), *pools,
        shape((B, MB), i32), shape((B,), i32), shape((1,), i32), *scales,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_prefill_compiles(shape, kv_dtype):
    def step(q, kp, vp, bt, start, total, window, *scales):
        return paged_prefill_attention(
            q, kp, vp, bt, start, total, window, **_scale_kwargs(scales)
        )

    i32 = jnp.int32
    pools, scales = _pools(shape, kv_dtype)
    text = _compiled_text(
        step, shape((1, T, H, HD), jnp.bfloat16), *pools,
        shape((1, MB), i32), shape((1,), i32), shape((1,), i32),
        shape((1,), i32), *scales,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,m", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_bitplane_gemv_compiles(shape, k, m):
    def gemv(x_r, planes):
        return bitplane_gemv(x_r, planes, n_bits=8, group=1)

    text = _compiled_text(
        gemv, shape((8, B, k // 8), jnp.bfloat16),
        shape((8, k // 8, m), jnp.uint8),
    )
    assert "tpu_custom_call" in text
