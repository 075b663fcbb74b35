"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each Pallas kernel is validated
against these references over shape/dtype sweeps in tests/test_kernels.py
(interpret=True on CPU), and they double as the CPU execution path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Jordan et al. (2024) quintic Newton-Schulz coefficients.
NS_COEFFS = (3.4445, -4.7750, 2.0315)


def fused_matmul_ref(a: jax.Array, b: jax.Array, c: jax.Array | None,
                     alpha: float = 1.0, beta: float = 1.0) -> jax.Array:
    """out = alpha * c + beta * (a @ b), f32 accumulation, output dtype f32."""
    out = beta * jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    if c is not None:
        out = out + alpha * c.astype(jnp.float32)
    return out


def ns_iteration_ref(x: jax.Array, coeffs=NS_COEFFS) -> jax.Array:
    """One quintic Newton-Schulz iteration: X' = aX + (bA + cA^2) X, A = XX^T."""
    a, b, c = coeffs
    xf = x.astype(jnp.float32)
    gram = xf @ xf.T
    poly = b * gram + c * (gram @ gram)
    return (a * xf + poly @ xf).astype(x.dtype)


def newton_schulz_ref(g: jax.Array, steps: int = 5, coeffs=NS_COEFFS,
                      eps: float = 1e-7) -> jax.Array:
    """Approximate UV^T of the SVD of g (orthogonalisation), jnp oracle.

    Operates on the transposed matrix when rows > cols so the gram matrix
    is built on the small side, matching the Muon reference implementation.
    """
    if g.ndim != 2:
        raise ValueError("newton_schulz_ref expects a 2-D matrix")
    transpose = g.shape[0] > g.shape[1]
    x = g.T if transpose else g
    x = x / (jnp.linalg.norm(x.astype(jnp.float32)) + eps).astype(x.dtype)
    for _ in range(steps):
        x = ns_iteration_ref(x, coeffs)
    return x.T if transpose else x


def ns_iteration_batched_ref(x: jax.Array, coeffs=NS_COEFFS) -> jax.Array:
    """Batched quintic NS iteration over a [B, m, n] slice stack.

    Native batched matmuls — traces to exactly the dot_generals that
    ``jax.vmap(ns_iteration_ref)`` produces, so it stays bit-identical to
    the per-slice oracle (asserted in tests/test_ns_bucketing.py).
    """
    a, b, c = coeffs
    xf = x.astype(jnp.float32)
    gram = xf @ jnp.swapaxes(xf, -1, -2)
    poly = b * gram + c * (gram @ gram)
    return (a * xf + poly @ xf).astype(x.dtype)


def newton_schulz_batched_ref(g: jax.Array, steps: int = 5,
                              coeffs=NS_COEFFS,
                              eps: float = 1e-7,
                              hook=None) -> jax.Array:
    """Batched orthogonalisation oracle over [B, m, n] slice stacks.

    No transpose handling: the bucketing layer (repro.dist.bucketing)
    canonicalises every slice to m <= n before stacking. Per-slice f32
    Frobenius normalisation matches ``newton_schulz_ref`` bit-for-bit.

    ``hook``, when given, is a value-identity applied to the iterate
    after normalisation and after every iteration — the sharding layer
    (kernels/ops.py) threads ``with_sharding_constraint`` through the
    chain with it, so the partitioner keeps the stack sharded instead of
    replicating the whole chain. ``hook=None`` leaves the oracle
    untouched.
    """
    if g.ndim != 3:
        raise ValueError("newton_schulz_batched_ref expects [B, m, n]")
    nrm = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)),
                           axis=(-2, -1), keepdims=True))
    x = g / (nrm + eps).astype(g.dtype)
    if hook is not None:
        x = hook(x)
    for _ in range(steps):
        x = ns_iteration_batched_ref(x, coeffs)
        if hook is not None:
            x = hook(x)
    return x


def natural_compress_ref(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Deterministic natural compression of bf16 values: round to the
    nearest power of two. Returns (exp_code uint8, sign uint8 in {0,1}).

    bf16 layout: 1 sign | 8 exponent | 7 mantissa. Rounding to the nearest
    power of two increments the exponent when the mantissa >= 0.5 (top
    mantissa bit set). Relative error <= 1/3 => contractive with
    alpha = 1 - 1/9 = 8/9 in any elementwise norm.
    Zero maps to code 0; inf/nan clamp to code 254.
    """
    xb = x.astype(jnp.bfloat16)
    bits = jax.lax.bitcast_convert_type(xb, jnp.uint16)
    sign = (bits >> 15).astype(jnp.uint8)
    exp = ((bits >> 7) & 0xFF).astype(jnp.uint16)
    mant_hi = (bits >> 6) & 0x1
    exp_rounded = jnp.minimum(exp + mant_hi, 254).astype(jnp.uint8)
    is_zero = (bits & 0x7FFF) == 0
    code = jnp.where(is_zero, jnp.uint8(0), exp_rounded)
    return code, sign


def natural_decompress_ref(code: jax.Array, sign: jax.Array) -> jax.Array:
    """Inverse of natural_compress_ref -> bf16 powers of two."""
    bits = (sign.astype(jnp.uint16) << 15) | (code.astype(jnp.uint16) << 7)
    bits = jnp.where(code == 0, sign.astype(jnp.uint16) << 15, bits)
    return jax.lax.bitcast_convert_type(bits.astype(jnp.uint16), jnp.bfloat16)


def magnitude_key32(x: jax.Array) -> jax.Array:
    """int32 key of |x| for a 16- or 32-bit float ``x``: non-negative
    IEEE floats order as their bit patterns, so the keys order as the
    magnitudes, -0 and +0 alike. A 16-bit pattern fills the high half,
    which is the key of its value widened to f32 for bf16.

    The key is read from the bits of ``x`` itself: XLA may keep a value
    it rounded to bf16 at f32 where only float arithmetic follows (and
    on the TPU it did, in the compiled step), which would rank entries
    that tie in bf16 apart, unlike the kernel that reads the bf16
    array."""
    if x.dtype.itemsize == 2:
        bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)
        return (bits & 0x7FFF) << 16
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32) & 0x7FFFFFFF
    raise TypeError(f"no magnitude key for {x.dtype}")


TIE_BLOCK = 1024   # entries per tie count of ``topk_threshold``


def topk_threshold(flat: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """The keep rule of ``lax.top_k(abs(flat), k)``'s set, without a sort:
    (t, c) such that entry i is kept iff key_i > t, or key_i == t and
    i < c (``magnitude_key32`` keys; ties go to the lower index, as in
    ``lax.top_k``). Exactly k entries are kept.

    t is the k-th largest key, built bit by bit from the top: one
    compare-and-count pass per mantissa and exponent bit of the dtype
    (15 for bf16, 31 for f32). c is one past the r-th entry equal to t,
    r = k - #(key > t): a count of ties per ``TIE_BLOCK`` entries, a
    cumulative sum over those counts, and a cumulative sum inside the
    one block where the r-th tie falls."""
    n = flat.shape[0]
    low = 32 - 8 * flat.dtype.itemsize       # key bits below are zero

    def bit_pass(j, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - j)
        cnt = jnp.sum((magnitude_key32(flat) >= cand).astype(jnp.int32))
        return jnp.where(cnt >= k, cand, t)

    t = jax.lax.fori_loop(0, 31 - low, bit_pass, jnp.int32(0))
    key = magnitude_key32(flat)
    r = k - jnp.sum((key > t).astype(jnp.int32))
    nb = -(-n // TIE_BLOCK)
    tie = jnp.pad((key == t).astype(jnp.int32), (0, nb * TIE_BLOCK - n))
    cum = jnp.cumsum(jnp.sum(tie.reshape(nb, TIE_BLOCK), axis=1))
    j = jnp.sum((cum < r).astype(jnp.int32))
    before = jnp.where(j > 0, cum[jnp.maximum(j - 1, 0)], 0)
    seg = jax.lax.dynamic_slice(tie, (j * TIE_BLOCK,), (TIE_BLOCK,))
    pos = jnp.sum((before + jnp.cumsum(seg) < r).astype(jnp.int32))
    return t, j * TIE_BLOCK + pos + 1


def topk_keep_mask(flat: jax.Array, t: jax.Array, c: jax.Array):
    """The entries ``topk_threshold``'s (t, c) keep."""
    key = magnitude_key32(flat)
    idx = jnp.arange(flat.shape[0], dtype=jnp.int32)
    return (key > t) | ((key == t) & (idx < c))


def topk_select_ref(flat: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """The k largest-magnitude entries of ``flat`` as (values, int32
    indices) in ascending index order: the same set as ``lax.top_k``,
    ties to the lower index."""
    t, c = topk_threshold(flat, k)
    idx = jnp.nonzero(topk_keep_mask(flat, t, c), size=k,
                      fill_value=0)[0].astype(jnp.int32)
    return flat[idx], idx
