"""Error-feedback algebra + the paper's motivating divergence example.

The EF-necessity experiment (§2 "Error Feedback", Beznosikov et al.
Example 1): naive biased compression of gradients diverges on an average
of quadratics, while the EF21 mechanism converges.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compressors import TopK, get_compressor
from repro.core.error_feedback import apply_payload, ef_compress_step


def test_ef_state_bit_consistency(key):
    """Sender and receiver estimates stay identical (the EF21 invariant)."""
    comp = TopK(0.2, use_pallas=False)
    target = jax.random.normal(key, (12, 12))
    est_send = jnp.zeros((12, 12))
    est_recv = jnp.zeros((12, 12))
    state = comp.init(key, target.shape, jnp.float32)
    for i in range(5):
        payload, state, est_send = ef_compress_step(comp, state, est_send,
                                                    target, jnp.float32)
        est_recv = apply_payload(comp, payload, est_recv)
        np.testing.assert_array_equal(np.asarray(est_send),
                                      np.asarray(est_recv))


def test_ef_estimate_converges_to_fixed_target(key):
    """Repeated EF rounds on a fixed target: ||G - T|| -> 0 geometrically
    (contraction factor sqrt(1 - alpha))."""
    comp = TopK(0.25, use_pallas=False)
    target = jax.random.normal(key, (20, 20))
    est = jnp.zeros_like(target)
    state = comp.init(key, target.shape, jnp.float32)
    errs = []
    for i in range(30):
        _, state, est = ef_compress_step(comp, state, est, target,
                                         jnp.float32)
        errs.append(float(jnp.linalg.norm(est - target)))
    assert errs[-1] < 1e-3 * errs[0]


def _quadratic_problem():
    """Average of 3 strongly convex quadratics with conflicting gradients
    (the divergence construction of Beznosikov et al. 2020, Example 1)."""
    a = jnp.array([[-3.0, 2.0, 2.0], [2.0, -3.0, 2.0], [2.0, 2.0, -3.0]])

    # f_j(x) = 0.5 x^T (I + e_j e_j^T) x + <a_j, x>; grads differ strongly
    def grad_j(x, j):
        return x + jnp.eye(3)[j] * x[j] + a[j]

    return grad_j


def test_biased_compression_without_ef_fails(key):
    """Top1-compressed gradient descent (no EF) stalls/diverges on the
    quadratic example while EF21 converges to the optimum."""
    grad_j = _quadratic_problem()
    comp = TopK(0.34, use_pallas=False)  # top-1 of 3
    lr = 0.1

    def naive(x0, steps=300):
        x = x0
        for _ in range(steps):
            g = jnp.mean(jnp.stack([
                comp.decompress(comp.compress({}, grad_j(x, j))[0],
                                (3,), jnp.float32) for j in range(3)]), 0)
            x = x - lr * g
        return x

    def ef21(x0, steps=300):
        x = x0
        G = [jnp.zeros(3)] * 3
        for _ in range(steps):
            for j in range(3):
                _, _, G[j] = ef_compress_step(comp, {}, G[j], grad_j(x, j),
                                              jnp.float32)
            x = x - lr * jnp.mean(jnp.stack(G), 0)
        return x

    x0 = jnp.array([1.0, 0.7, -0.3])
    # optimum: grad f(x*) = 0 for f = mean f_j
    def full_grad(x):
        return jnp.mean(jnp.stack([grad_j(x, j) for j in range(3)]), 0)

    x_naive = naive(x0)
    x_ef = ef21(x0)
    gn_naive = float(jnp.linalg.norm(full_grad(x_naive)))
    gn_ef = float(jnp.linalg.norm(full_grad(x_ef)))
    assert gn_ef < 1e-3, f"EF21 should converge, got grad norm {gn_ef}"
    assert gn_naive > 10 * gn_ef, \
        f"naive compression should stall: {gn_naive} vs {gn_ef}"


@pytest.mark.parametrize("name", ["identity", "natural", "top10",
                                  "top10+natural", "rank10",
                                  "rank10+natural"])
def test_apply_payload_matches_sender_estimate(name, key):
    """§2 invariant: the receiver's ``apply_payload(comp, payload, E)``
    must be *bit-identical* to the ``new_estimate`` the sender computed in
    ``ef_compress_step`` — the whole point of transmitting C(T - E) is
    that both sides advance E by the exact same decompressed message."""
    comp = get_compressor(name, use_pallas=False)
    shape = (24, 16)
    target = jax.random.normal(key, shape, jnp.float32)
    est_send = jnp.zeros(shape, jnp.float32)
    est_recv = jnp.zeros(shape, jnp.float32)
    state = comp.init(key, shape, jnp.dtype(jnp.bfloat16))
    for i in range(4):
        payload, state, est_send = ef_compress_step(comp, state, est_send,
                                                    target)
        est_recv = apply_payload(comp, payload, est_recv)
        np.testing.assert_array_equal(np.asarray(est_send),
                                      np.asarray(est_recv))


@pytest.mark.parametrize("name", ["top10", "rank10", "natural"])
def test_apply_payload_matches_sender_on_stacked_leaf(name, key):
    """Same invariant on a stacked leaf [L, m, n]: both sides vmapped over
    the stack dim, exactly as LayerPlan drives the optimizer phases."""
    L, shape = 3, (12, 8)
    target = jax.random.normal(key, (L,) + shape, jnp.float32)
    comp = get_compressor(name, use_pallas=False)
    keys = jax.random.split(key, L)
    state = jax.vmap(
        lambda k: comp.init(k, shape, jnp.dtype(jnp.bfloat16)))(keys)
    est_send = jnp.zeros((L,) + shape, jnp.float32)
    est_recv = jnp.zeros((L,) + shape, jnp.float32)

    def send(cs, e, t):
        return ef_compress_step(comp, cs, e, t)

    def recv(pl, e):
        return apply_payload(comp, pl, e)

    for i in range(3):
        payload, state, est_send = jax.vmap(send)(state, est_send, target)
        est_recv = jax.vmap(recv)(payload, est_recv)
        np.testing.assert_array_equal(np.asarray(est_send),
                                      np.asarray(est_recv))


def test_rank_fallback_is_deterministic_and_wrapped():
    """The documented resolve rule: rank-type compressors on non-2D
    slices fall back to TopK(0.25), preserving a requested Natural
    wrapper — never silently switching compression family by name."""
    from repro.core import compressors as C
    from repro.dist.layerwise import resolve_compressor

    assert isinstance(resolve_compressor("rank10", (128,), False), C.TopK)
    fb = resolve_compressor("rank10+natural", (128,), False)
    assert isinstance(fb, C.WithNatural) and isinstance(fb.inner, C.TopK)
    # 2-D slices keep exactly what was asked for
    assert isinstance(resolve_compressor("rank10", (64, 32), False), C.RankK)
    # non-rank compressors pass through on any shape
    assert isinstance(resolve_compressor("top10", (128,), False), C.TopK)
    assert isinstance(resolve_compressor("natural", (128,), False), C.Natural)


def test_identity_compressor_ef_is_exact(key):
    comp = get_compressor("identity", use_pallas=False)
    target = jax.random.normal(key, (8, 8))
    est = jnp.zeros_like(target)
    _, _, est = ef_compress_step(comp, {}, est, target, jnp.float32)
    np.testing.assert_allclose(np.asarray(est), np.asarray(target),
                               rtol=1e-6)
