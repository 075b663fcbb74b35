"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in kernels/ref.py, executed with interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
# property tests skip individually when hypothesis is absent; the
# plain oracle tests in this file still run (see _hypothesis_compat)
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.newton_schulz import (fused_matmul, ns_iteration_fused,
                                         ns_iteration_pallas)
from repro.kernels.ops import natural_compress, natural_decompress, \
    newton_schulz, newton_schulz_batched


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 384),
                                   (128, 256, 128), (384, 384, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_matmul_matches_ref(m, k, n, dtype, key):
    k1, k2, k3 = jax.random.split(key, 3)
    a = jax.random.normal(k1, (m, k), dtype)
    b = jax.random.normal(k2, (k, n), dtype)
    c = jax.random.normal(k3, (m, n), dtype)
    got = fused_matmul(a, b, c=c, alpha=0.7, beta=1.3,
                       out_dtype=jnp.float32, interpret=True)
    want = ref.fused_matmul_ref(a, b, c, alpha=0.7, beta=1.3)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol * 10)


def test_fused_matmul_no_c(key):
    a = jax.random.normal(key, (128, 128))
    b = jax.random.normal(jax.random.fold_in(key, 1), (128, 128))
    got = fused_matmul(a, b, interpret=True, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.fused_matmul_ref(a, b, None)),
                               rtol=1e-5)


def test_ns_iteration_matches_ref(key):
    x = jax.random.normal(key, (128, 256), jnp.float32) * 0.05
    got = ns_iteration_pallas(x, ref.NS_COEFFS, interpret=True)
    want = ref.ns_iteration_ref(x, ref.NS_COEFFS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("shape", [(64, 48), (48, 64), (200, 120),
                                   (128, 128), (13, 77)])
def test_newton_schulz_pallas_vs_oracle(shape, key):
    """Pallas path (zero-padded to 128 blocks) == jnp oracle, any shape."""
    g = jax.random.normal(key, shape, jnp.float32)
    got = newton_schulz(g, steps=5, use_pallas=True, interpret=True)
    want = ref.newton_schulz_ref(g, steps=5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("bsz,m,n", [(2, 128, 256), (3, 256, 128),
                                     (1, 384, 384), (2, 256, 640)])
def test_ns_iteration_fused_matches_batched_ref(bsz, m, n, key):
    """ONE fused pallas_call (gram + poly + update in VMEM, symmetric
    gram tiles skipped) == the batched jnp iteration, multi-tile m
    included (exercises the triangular accumulate + mirror)."""
    x = jax.random.normal(key, (bsz, m, n), jnp.float32) * 0.05
    got = ns_iteration_fused(x, ref.NS_COEFFS, interpret=True)
    want = ref.ns_iteration_batched_ref(x, ref.NS_COEFFS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_ns_iteration_fused_bf16(key):
    x = (jax.random.normal(key, (2, 128, 128)) * 0.05).astype(jnp.bfloat16)
    got = ns_iteration_fused(x, ref.NS_COEFFS, interpret=True)
    want = ref.ns_iteration_batched_ref(x, ref.NS_COEFFS)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("bsz,m,n", [(3, 96, 160), (2, 200, 120),
                                     (1, 128, 128), (4, 13, 77)])
def test_newton_schulz_batched_pallas_vs_oracle(bsz, m, n, key):
    """Batched Pallas path (zero-padded to 128 blocks, fused iteration)
    == batched jnp oracle, any slice shape."""
    g = jax.random.normal(key, (bsz, m, n), jnp.float32)
    got = newton_schulz_batched(g, steps=5, use_pallas=True, interpret=True)
    want = ref.newton_schulz_batched_ref(g, steps=5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_newton_schulz_batched_unfused_fallback(key):
    """fused=False (the VMEM-infeasible fallback: vmapped three-call
    chain) computes the same batched result."""
    g = jax.random.normal(key, (2, 96, 160), jnp.float32)
    got = newton_schulz_batched(g, steps=3, use_pallas=True, interpret=True,
                                fused=False)
    want = ref.newton_schulz_batched_ref(g, steps=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_newton_schulz_fused_equals_chain(key):
    """The fused iteration and the three-call chain are the same
    algorithm: 2-D entry point, both pallas variants vs each other."""
    g = jax.random.normal(key, (100, 60), jnp.float32)
    a = newton_schulz(g, steps=3, use_pallas=True, interpret=True,
                      fused=True)
    b = newton_schulz(g, steps=3, use_pallas=True, interpret=True,
                      fused=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_newton_schulz_orthogonalises(key):
    g = jax.random.normal(key, (96, 160), jnp.float32)
    z = newton_schulz(g, steps=9, use_pallas=True, interpret=True)
    s = jnp.linalg.svd(z.astype(jnp.float32), compute_uv=False)
    # quintic NS keeps singular values in a band around 1, not exactly 1
    assert float(jnp.max(s)) < 1.3 and float(jnp.min(s)) > 0.6


@given(n=st.integers(1, 2000), seed=st.integers(0, 2 ** 16),
       scale=st.sampled_from([1e-3, 1.0, 1e3]))
@settings(max_examples=12, deadline=None)
def test_natural_roundtrip_property(n, seed, scale):
    """Hypothesis sweep: natural compress/decompress keeps relative error
    <= 1/3 for arbitrary lengths (incl. non-multiple-of-8)."""
    x = (jax.random.normal(jax.random.key(seed), (n,)) * scale
         ).astype(jnp.bfloat16)
    code, signs = natural_compress(x, use_pallas=False)
    xh = np.asarray(natural_decompress(code, signs, (n,), jnp.float32))
    xb = np.asarray(x, np.float32)
    nz = np.abs(xb) > 0
    rel = np.abs(xh[nz] - xb[nz]) / np.abs(xb[nz])
    assert rel.max() <= 1 / 3 + 1e-2 if nz.any() else True
    assert (xh[~nz] == 0).all()


@pytest.mark.parametrize("rows,cols", [(256, 128), (512, 256), (256, 384)])
def test_natural_pallas_kernel_matches_ref(rows, cols, key):
    from repro.kernels.natural_pack import natural_encode
    x = (jax.random.normal(key, (rows, cols)) *
         jnp.exp(jax.random.normal(jax.random.fold_in(key, 1),
                                   (rows, cols)) * 4)).astype(jnp.bfloat16)
    code_k, sign_k = natural_encode(x, block_rows=256, interpret=True)
    code_r, sign_r = ref.natural_compress_ref(x.reshape(-1))
    np.testing.assert_array_equal(np.asarray(code_k).reshape(-1),
                                  np.asarray(code_r))
    np.testing.assert_array_equal(np.asarray(sign_k).reshape(-1),
                                  np.asarray(sign_r))


def test_natural_pallas_end_to_end(key):
    """ops.natural_compress with the Pallas path (interpret) == ref path."""
    x = jax.random.normal(key, (1000,)).astype(jnp.bfloat16)
    c1, s1 = natural_compress(x, use_pallas=True, interpret=True)
    c2, s2 = natural_compress(x, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


@given(n8=st.integers(1, 1500), seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_bitpack_sign_kernels_match_refs(n8, seed):
    """Pallas 1-bit pack/unpack (interpret) == jnp refs, byte-for-byte,
    for arbitrary multiple-of-8 lengths (the Natural sign-plane path)."""
    from repro.kernels import bitpack as bp
    bits = jax.random.bernoulli(
        jax.random.key(seed), 0.5, (8 * n8,)).astype(jnp.uint8)
    ref_p = bp.pack_bits_ref(bits)
    ker_p = bp.pack_bits(bits, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref_p), np.asarray(ker_p))
    ker_u = bp.unpack_bits(ref_p, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ker_u))


@pytest.mark.parametrize("width,hi", [(2, 1 << 16), (3, 1 << 24),
                                      (4, 1 << 24)])
@pytest.mark.parametrize("k", [1, 7, 128, 1000])
def test_bitpack_narrow_kernels_match_refs(width, hi, k, key):
    """Pallas narrow int encode/decode (interpret) == jnp refs and
    round-trip exactly for every supported byte width."""
    from repro.kernels import bitpack as bp
    idx = jax.random.randint(key, (k,), 0, hi, jnp.int32)
    ref_e = bp.narrow_encode_ref(idx, width)
    ker_e = bp.narrow_encode(idx, width, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref_e), np.asarray(ker_e))
    ker_d = bp.narrow_decode(ref_e, width, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ker_d))


def test_natural_compress_pallas_signs_roundtrip(key):
    """natural_compress with the full Pallas path (encode kernel + sign
    bitpack kernel) stays bit-identical to the jnp path end-to-end."""
    x = jax.random.normal(key, (777,)).astype(jnp.bfloat16)
    c1, s1 = natural_compress(x, use_pallas=True, interpret=True)
    c2, s2 = natural_compress(x, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    x1 = natural_decompress(c1, s1, (777,), use_pallas=True, interpret=True)
    x2 = natural_decompress(c2, s2, (777,), use_pallas=False)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))


def test_ns_zero_padding_exactness(key):
    """Zero padding is exact for NS: padded result sliced back equals the
    unpadded oracle (the ops.py wrapper invariant)."""
    g = jax.random.normal(key, (100, 60), jnp.float32)
    got = newton_schulz(g, steps=3, use_pallas=True, interpret=True,
                        block=128)
    want = ref.newton_schulz_ref(g, steps=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)
