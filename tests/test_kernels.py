"""Pallas kernels vs ref.py oracles: shape/dtype/precision sweeps
(interpret=True executes the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.bitplane_gemm import bitplane_gemm
from repro.kernels.bitplane_gemv import bitplane_gemv
from repro.kernels.pack import pack_bitplanes


def _quant_pack(rng, k, m, n_bits, group):
    w = jnp.asarray(rng.normal(size=(k, m)), jnp.float32)
    w_q, scale = ref.quantize_ref(w, n_bits)
    planes = ref.pack_ref(w_q, n_bits, group)
    return w, w_q, scale, planes


@pytest.mark.parametrize("n_bits", [2, 3, 4, 8])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_pack_unpack_roundtrip(rng, n_bits, group):
    _, w_q, _, planes = _quant_pack(rng, 64, 32, n_bits, group)
    assert jnp.array_equal(ref.unpack_ref(planes, n_bits, group), w_q)


@pytest.mark.parametrize("n_bits,group", [(8, 1), (4, 2), (8, 4), (2, 1)])
def test_pack_kernel_matches_ref(rng, n_bits, group):
    _, w_q, _, planes = _quant_pack(rng, 64, 128, n_bits, group)
    u = (w_q + 2 ** (n_bits - 1)).astype(jnp.uint8)
    dpb = 8 // group
    u_r = u.reshape(64 // dpb, dpb, 128).transpose(1, 0, 2)
    got = pack_bitplanes(u_r, n_bits=n_bits, group=group,
                         block_k8=8, block_m=64, interpret=True)
    assert jnp.array_equal(got, planes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_bits,group", [(8, 1), (4, 1), (4, 2), (8, 2), (8, 4), (3, 1)])
@pytest.mark.parametrize("B,K,M,blocks", [
    (2, 64, 128, (64, 4)),
    (8, 128, 64, (64, 4)),
    # the default preferred tiles (256, 128) divide neither M=384 nor, at
    # group 1, K8=192: the kernel fits them (M tile 128, K8 whole)
    (2, 1536, 384, (256, 128)),
])
def test_gemv_kernel_matches_oracle(rng, dtype, n_bits, group, B, K, M,
                                    blocks):
    _, w_q, scale, planes = _quant_pack(rng, K, M, n_bits, group)
    x = jnp.asarray(rng.normal(size=(B, K)), dtype)
    y_ref = ref.bitplane_matmul_ref(x, planes, scale, n_bits, group)
    x_r = ref.prepare_x_ref(x, group)
    raw = bitplane_gemv(x_r, planes, n_bits=n_bits, group=group,
                        block_m=blocks[0], block_k8=blocks[1],
                        interpret=True)
    off = float(2 ** (n_bits - 1))
    y = (raw - off * jnp.sum(x.astype(jnp.float32), -1, keepdims=True)) * scale[None]
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    denom = float(jnp.max(jnp.abs(y_ref.astype(jnp.float32)))) + 1e-6
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - y_ref.astype(jnp.float32))))
    assert err / denom < tol, (dtype, n_bits, group, err / denom)


def test_gemm_kernel_matches_gemv(rng):
    n_bits, group = 8, 1
    _, w_q, scale, planes = _quant_pack(rng, 128, 128, n_bits, group)
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
    x_r = ref.prepare_x_ref(x, group)
    a = bitplane_gemv(x_r, planes, n_bits=n_bits, group=group,
                      block_m=64, block_k8=8, interpret=True)
    b = bitplane_gemm(x_r, planes, n_bits=n_bits, group=group,
                      block_b=8, block_m=64, block_k8=8, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_planewise_oracle_matches_direct(rng):
    """The kernel-order contraction (ref #2) equals unpack-then-matmul."""
    for n_bits, group in [(8, 1), (4, 2)]:
        _, _, scale, planes = _quant_pack(rng, 64, 32, n_bits, group)
        x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
        a = ref.bitplane_matmul_ref(x, planes, scale, n_bits, group)
        b = ref.bitplane_matmul_planewise_ref(x, planes, scale, n_bits, group)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@settings(max_examples=10)
@given(
    n_bits=st.sampled_from([2, 4, 8]),
    group=st.sampled_from([1, 2]),
    seed=st.integers(0, 2**31),
)
def test_quantized_matmul_error_bound(n_bits, group, seed):
    """Property: dequantized matmul error <= per-column quantization step
    (symmetric quantization error bound)."""
    rng = np.random.default_rng(seed)
    k, m = 32, 16
    w = jnp.asarray(rng.normal(size=(k, m)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, k)), jnp.float32)
    planes, scale = ops.quantize_and_pack(w, n_bits, group, impl="ref")
    y = ops.bitplane_matmul(x, planes, scale, n_bits=n_bits, group=group, impl="ref")
    # bound: |x @ (W - Wq*s)| <= sum_k |x_k| * s/2 per column
    bound = jnp.sum(jnp.abs(x), axis=1, keepdims=True) * (scale[None, :] / 2) + 1e-4
    assert bool(jnp.all(jnp.abs(y - x @ w) <= bound * 1.01))


def test_packed_bytes_amplification():
    """HBM bytes scale with n_bits: the paper's bandwidth argument."""
    b8 = ops.packed_bytes(4096, 4096, 8)
    b4 = ops.packed_bytes(4096, 4096, 4)
    b2 = ops.packed_bytes(4096, 4096, 2)
    assert b8 / b4 == pytest.approx(2.0, rel=0.01)
    assert b8 / b2 == pytest.approx(4.0, rel=0.01)
    # vs bf16 dense: 16/n_bits amplification
    dense = 4096 * 4096 * 2
    assert dense / b8 == pytest.approx(2.0, rel=0.01)
