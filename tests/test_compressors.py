"""Contractive-compressor properties (paper Def. 1, §D)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
# property tests skip individually when hypothesis is absent; the
# plain oracle tests in this file still run (see _hypothesis_compat)
from _hypothesis_compat import given, settings, st

from repro.core import compressors as C
from repro.core.norms import norm


def _contract_ratio(comp, key, x, kind="frobenius", trials=4):
    """E||C(x)-x||^2 / ||x||^2 (should be <= 1 - alpha)."""
    state = comp.init(key, x.shape, x.dtype)
    tot = 0.0
    for i in range(trials):
        payload, state = comp.compress(state, x)
        xh = comp.decompress(payload, x.shape, jnp.float32)
        tot += float(norm(xh - x.astype(jnp.float32), kind)) ** 2
    return tot / trials / float(norm(x, kind)) ** 2


@pytest.mark.parametrize("name", sorted(C.REGISTRY))
def test_registry_roundtrip_shapes(name, key):
    comp = C.get_compressor(name, use_pallas=False)
    shape = (24, 16)
    x = jax.random.normal(key, shape, jnp.float32)
    state = comp.init(key, shape, jnp.dtype(jnp.bfloat16))
    payload, state = comp.compress(state, x.astype(jnp.bfloat16))
    xh = comp.decompress(payload, shape, jnp.float32)
    assert xh.shape == shape and xh.dtype == jnp.float32
    assert comp.payload_bytes(shape, jnp.bfloat16) > 0


def test_topk_contractive_euclidean(key):
    x = jax.random.normal(key, (32, 16))
    for frac in (0.05, 0.1, 0.2, 0.5):
        r = _contract_ratio(C.TopK(frac, use_pallas=False), key, x)
        assert r <= 1.0 - frac * 0.5  # top-k beats random-k = 1 - frac


def test_topk_exact_on_sparse(key):
    x = jnp.zeros((10, 10)).at[3, 4].set(5.0).at[7, 1].set(-2.0)
    comp = C.TopK(0.02, use_pallas=False)  # k = 2
    payload, _ = comp.compress(comp.init(key, x.shape, x.dtype), x)
    xh = comp.decompress(payload, x.shape, jnp.float32)
    np.testing.assert_allclose(np.asarray(xh), np.asarray(x))


def test_topksvd_alpha_matches_formula(key):
    """alpha = 1 - (sum_{i>K} s_i^p / sum s_i^p)^{2/p} for Schatten-p."""
    x = jax.random.normal(key, (12, 9))
    s = jnp.linalg.svd(x, compute_uv=False)
    for K in (1, 3, 5):
        comp = C.TopKSVD(rank=K)
        payload, _ = comp.compress({}, x)
        xh = comp.decompress(payload, x.shape, jnp.float32)
        # spectral: residual = s_{K+1}
        np.testing.assert_allclose(float(norm(xh - x, "spectral")),
                                   float(s[K]), rtol=1e-4)
        # nuclear: residual = sum_{i>K} s_i
        np.testing.assert_allclose(float(norm(xh - x, "nuclear")),
                                   float(jnp.sum(s[K:])), rtol=1e-4)
        # frobenius
        np.testing.assert_allclose(
            float(norm(xh - x, "frobenius")),
            float(jnp.sqrt(jnp.sum(s[K:] ** 2))), rtol=1e-4)


def test_column_topk_contractive_mixed_norm(key):
    x = jax.random.normal(key, (16, 20))
    comp = C.ColumnTopK(0.25)
    payload, _ = comp.compress({}, x)
    xh = comp.decompress(payload, x.shape, jnp.float32)
    # kept columns exact, residual only on dropped ones
    kept = np.asarray(payload["indices"])
    np.testing.assert_allclose(np.asarray(xh)[:, kept],
                               np.asarray(x)[:, kept], rtol=1e-6)
    r = _contract_ratio(comp, key, x, kind="col_l2_dual")
    assert r < 1.0


def test_natural_relative_error_bound(key):
    """|C(x) - x| <= |x| / 3 elementwise => alpha >= 8/9 (§D / Horvath)."""
    x = jax.random.normal(key, (64, 64)) * jnp.exp(
        jax.random.normal(jax.random.fold_in(key, 1), (64, 64)) * 3)
    comp = C.Natural(use_pallas=False)
    payload, _ = comp.compress({}, x.astype(jnp.bfloat16))
    xh = np.asarray(comp.decompress(payload, x.shape, jnp.float32))
    xb = np.asarray(x.astype(jnp.bfloat16), np.float32)
    rel = np.abs(xh - xb) / np.maximum(np.abs(xb), 1e-30)
    assert rel.max() <= 1 / 3 + 1e-2
    assert _contract_ratio(comp, key, x.astype(jnp.bfloat16)) <= 1 / 9 + 0.01


def test_natural_preserves_powers_of_two(key):
    x = jnp.array([1.0, 2.0, -4.0, 0.5, -0.25, 0.0, 1024.0])
    comp = C.Natural(use_pallas=False)
    payload, _ = comp.compress({}, x.astype(jnp.bfloat16))
    xh = comp.decompress(payload, x.shape, jnp.float32)
    np.testing.assert_allclose(np.asarray(xh), np.asarray(x))


def test_dropout_damping_alpha(key):
    x = jax.random.normal(key, (16, 16))
    # damping: deterministic ratio (1-gamma)^2
    for g in (0.5, 1.0, 1.5):
        r = _contract_ratio(C.Damping(g), key, x, trials=1)
        np.testing.assert_allclose(r, (1 - g) ** 2, rtol=1e-5, atol=1e-7)
    # dropout: E ratio = 1 - p
    comp = C.RandomDropout(0.7)
    r = _contract_ratio(comp, key, x, trials=64)
    assert abs(r - 0.3) < 0.15


def test_rankk_approximately_contractive(key):
    """PowerSGD-style RankK with NS orthonormalisation + warm start:
    contractive in expectation after warm-up (Remark 11)."""
    comp = C.RankK(fraction=0.25, use_pallas=False)
    x = jax.random.normal(key, (32, 24))
    state = comp.init(key, x.shape, jnp.float32)
    # warm-start: iterate on the same matrix; ratio should drop well < 1
    for _ in range(3):
        payload, state = comp.compress(state, x)
    xh = comp.decompress(payload, x.shape, jnp.float32)
    s = jnp.linalg.svd(x, compute_uv=False)
    best = float(jnp.sum(s[comp.rank_for(x.shape):] ** 2) / jnp.sum(s ** 2))
    ratio = float(norm(xh - x, "frobenius") ** 2 / norm(x, "frobenius") ** 2)
    assert ratio < 1.0
    assert ratio < 2.5 * best + 0.2  # near the optimal rank-K residual


def test_with_natural_combo_bytes(key):
    """TopK+Natural / RankK+Natural payloads: float planes shrink to
    9 bits/value; indices stay int32 (paper Table 2 accounting)."""
    shape = (64, 48)
    top = C.WithNatural(C.TopK(0.1, use_pallas=False), use_pallas=False)
    k = top.inner.k_for(shape)
    assert top.payload_bytes(shape, jnp.bfloat16) == k * 4 + k + (k + 7) // 8
    rk = C.WithNatural(
        C.RankK(fraction=0.1, use_pallas=False), use_pallas=False)
    r = rk.inner.rank_for(shape)
    nn = (64 + 48) * r
    assert rk.payload_bytes(shape, jnp.bfloat16) == nn + (nn + 7) // 8
    # roundtrip
    x = jax.random.normal(key, shape)
    st_ = rk.init(key, shape, jnp.dtype(jnp.bfloat16))
    payload, st_ = rk.compress(st_, x.astype(jnp.bfloat16))
    xh = rk.decompress(payload, shape, jnp.float32)
    assert xh.shape == shape
    assert not bool(jnp.any(jnp.isnan(xh)))


@given(frac=st.sampled_from([0.05, 0.1, 0.25]),
       m=st.integers(4, 40), n=st.integers(4, 40),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_topk_contraction_property(frac, m, n, seed):
    """Hypothesis: Def. 1 holds for TopK with alpha = fraction for any
    shape/seed (classical result: top-k >= random-k)."""
    x = jax.random.normal(jax.random.key(seed), (m, n))
    comp = C.TopK(frac, use_pallas=False)
    payload, _ = comp.compress({}, x)
    xh = comp.decompress(payload, (m, n), jnp.float32)
    lhs = float(jnp.sum((xh - x) ** 2))
    rhs = (1 - comp.k_for((m, n)) / (m * n)) * float(jnp.sum(x ** 2))
    assert lhs <= rhs + 1e-5


def test_empirical_alpha_helper(key):
    x = jax.random.normal(key, (16, 16))
    a = C.empirical_alpha(C.TopK(0.25, use_pallas=False), key, x)
    assert 0.25 <= a <= 1.0
