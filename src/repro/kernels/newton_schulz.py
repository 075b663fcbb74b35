"""Pallas TPU kernels for Newton-Schulz orthogonalisation (Muon's hot spot).

Two generations of kernels:

  * ``fused_matmul``: ``out = alpha * C + beta * (A @ B)`` — the original
    blocked-matmul workhorse. One NS iteration is three chained calls:
        gram = X @ X^T                       (fused_matmul(X, X^T))
        poly = b*gram + c*(gram @ gram)      (fused_matmul(gram, gram, C=gram, alpha=b, beta=c))
        X'   = a*X + poly @ X                (fused_matmul(poly, X, C=X, alpha=a))
    The ``gram``/``poly`` intermediates round-trip through HBM between the
    three pallas_calls.

  * ``ns_iteration_fused``: ONE pallas_call per NS iteration over a whole
    ``[B, m, n]`` stack of independent slices (DESIGN.md §7). The gram and
    the quintic polynomial live in ``[m, m]`` f32 VMEM scratch for the
    entire iteration — they never touch HBM — and the batch is a parallel
    grid dimension, so a shape bucket of identically-shaped layers is one
    dispatch chain of ``ns_steps`` kernels instead of ``3 * ns_steps``
    kernels *per layer*.

Design notes (TPU adaptation):
  * blocks default to (128, 128, 128): MXU-aligned on all three matmul dims;
    the K-dim is the innermost ("arbitrary") grid axis so the output block
    revisits stay in VMEM between K steps.
  * accumulation always f32 in a VMEM scratch buffer, cast to the output
    dtype on the final K step (bf16-safe for 5 chained iterations).
  * shapes are padded to block multiples by the ops.py wrapper; zero padding
    is exact for NS (padded rows/cols stay exactly zero through the
    polynomial), verified in tests.
  * the fused kernel accumulates only the upper-triangular tiles of the
    symmetric gram ``X X^T`` and mirrors the lower triangle once per
    iteration — T(T+1)/2 instead of T^2 tile matmuls on the gram phase.
  * the fused kernel needs ``2 * 4 * m^2`` bytes of VMEM scratch;
    ``fused_ns_feasible`` gates it, the ops.py wrapper falls back to the
    three-call chain for slices whose gram does not fit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_matmul_kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, nk: int,
                         alpha: float, beta: float, has_c: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _finish():
        acc = beta * acc_ref[...]
        if has_c:
            acc = acc + alpha * c_ref[...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)


def fused_matmul(a: jax.Array, b: jax.Array, c: jax.Array | None = None,
                 alpha: float = 1.0, beta: float = 1.0,
                 block_m: int = 128, block_n: int = 128, block_k: int = 128,
                 out_dtype=None, interpret: bool = False) -> jax.Array:
    """``alpha * c + beta * (a @ b)`` with blocked VMEM tiling.

    Requires m % block_m == n % block_n == k % block_k == 0 (ops.py pads).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, \
        (a.shape, b.shape, block_m, block_n, block_k)
    out_dtype = out_dtype or a.dtype
    nk = k // block_k
    has_c = c is not None
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
    ]
    operands = [a, b]
    if has_c:
        in_specs.append(pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)))
        operands.append(c)
    else:
        # dummy scalar-shaped operand so the kernel signature is fixed
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)))
        operands.append(jnp.zeros((1, 1), dtype=out_dtype))
    kernel = functools.partial(_fused_matmul_kernel, nk=nk, alpha=alpha,
                               beta=beta, has_c=has_c)
    return pl.pallas_call(
        kernel,
        name="fused_matmul",
        grid=(m // block_m, n // block_n, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


def ns_iteration_pallas(x: jax.Array, coeffs, *, block: int = 128,
                        interpret: bool = False) -> jax.Array:
    """One quintic NS iteration via three fused_matmul calls.

    x: [m, n] with both dims multiples of ``block`` (pad upstream).
    """
    a, b, c = coeffs
    xt = x.T
    gram = fused_matmul(x, xt, block_m=block, block_n=block, block_k=block,
                        out_dtype=jnp.float32, interpret=interpret)
    poly = fused_matmul(gram, gram, c=gram, alpha=b, beta=c,
                        block_m=block, block_n=block, block_k=block,
                        out_dtype=jnp.float32, interpret=interpret)
    out = fused_matmul(poly, x, c=x, alpha=a, beta=1.0,
                       block_m=block, block_n=block, block_k=block,
                       out_dtype=x.dtype, interpret=interpret)
    return out


# ------------------------------------------------------- fused NS iteration

# VMEM budget for the fused kernel (of ~16 MB/core): 2 f32 [m, m] scratch
# buffers + double-buffered in/out [m, block_n] tiles must fit.
_FUSED_VMEM_BUDGET = 12 * 1024 * 1024

_CONTRACT_LAST = (((1,), (1,)), ((), ()))   # A @ B^T on [p, k] x [q, k]


def fused_ns_vmem_bytes(m: int, block_n: int, itemsize: int) -> int:
    """VMEM bytes the fused iteration kernel needs for ``[*, m, n]``
    slices: gram + poly scratch (f32) plus double-buffered X/X' tiles."""
    scratch = 2 * 4 * m * m
    tiles = 2 * 2 * m * block_n * max(itemsize, 4)
    return scratch + tiles


def fused_ns_feasible(m: int, block_n: int = 128, itemsize: int = 4) -> bool:
    """Whether the whole [m, m] gram fits the fused kernel's VMEM budget
    (the ops.py wrapper falls back to the three-call chain otherwise)."""
    return fused_ns_vmem_bytes(m, block_n, itemsize) <= _FUSED_VMEM_BUDGET


def _ns_fused_kernel(x_ref, o_ref, gram_ref, poly_ref, *, nj: int, nmt: int,
                     block_m: int, a: float, b: float, c: float):
    """One grid step of the fused iteration. Grid: (batch, phase, j).

    phase 0 sweeps the n-tiles of X accumulating the upper-triangular
    tiles of gram = X X^T in VMEM scratch; on the last n-tile it mirrors
    the lower triangle and evaluates poly = b*gram + c*gram^2 into the
    second scratch. phase 1 sweeps the n-tiles again emitting
    X' = a*X + poly @ X. gram/poly never leave VMEM.
    """
    ph = pl.program_id(1)
    j = pl.program_id(2)
    x = x_ref[0]                               # [m, block_n]

    @pl.when((ph == 0) & (j == 0))
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)

    @pl.when(ph == 0)
    def _gram():
        # upper-triangular tile accumulation: gram is symmetric, so the
        # T(T-1)/2 sub-diagonal tile matmuls are redundant and skipped.
        for mi in range(nmt):
            ri = slice(mi * block_m, (mi + 1) * block_m)
            xi = x[ri, :]
            for mj in range(mi, nmt):
                rj = slice(mj * block_m, (mj + 1) * block_m)
                gram_ref[ri, rj] += jax.lax.dot_general(
                    xi, x[rj, :], _CONTRACT_LAST,
                    preferred_element_type=jnp.float32)
        # the out block is flushed each j-step either way; write the aX
        # term so phase-0 flushes are deterministic (phase 1 overwrites).
        o_ref[0] = (a * x.astype(jnp.float32)).astype(o_ref.dtype)

    @pl.when((ph == 0) & (j == nj - 1))
    def _poly():
        for mi in range(nmt):
            ri = slice(mi * block_m, (mi + 1) * block_m)
            for mj in range(mi + 1, nmt):
                rj = slice(mj * block_m, (mj + 1) * block_m)
                gram_ref[rj, ri] = gram_ref[ri, rj].T
        g = gram_ref[...]
        poly_ref[...] = b * g + c * jnp.dot(
            g, g, preferred_element_type=jnp.float32)

    @pl.when(ph == 1)
    def _update():
        xf = x.astype(jnp.float32)
        o_ref[0] = (a * xf + jnp.dot(
            poly_ref[...], xf,
            preferred_element_type=jnp.float32)).astype(o_ref.dtype)


def ns_iteration_fused(x: jax.Array, coeffs, *, block_m: int = 128,
                       block_n: int = 128,
                       interpret: bool = False) -> jax.Array:
    """One quintic NS iteration for a ``[B, m, n]`` stack in ONE pallas_call.

    m % block_m == n % block_n == 0 (pad upstream); gram/poly stay in VMEM
    (caller gates on ``fused_ns_feasible(m, block_n)``). The batch is a
    parallel grid dim; phases and n-tiles are sequential, so the scratch
    accumulator is re-initialised per batch element.
    """
    bsz, m, n = x.shape
    if m % block_m or n % block_n:
        raise ValueError(
            f"ns_iteration_fused needs block-aligned slices, got {x.shape} "
            f"for blocks ({block_m}, {block_n}) — pad upstream (ops.py)")
    a, b, c = coeffs
    nj = n // block_n
    kernel = functools.partial(
        _ns_fused_kernel, nj=nj, nmt=m // block_m, block_m=block_m,
        a=float(a), b=float(b), c=float(c))
    spec = pl.BlockSpec((1, m, block_n), lambda bi, ph, j: (bi, 0, j))
    return pl.pallas_call(
        kernel,
        name="ns_iteration_fused",
        grid=(bsz, 2, nj),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, m), jnp.float32),
                        pltpu.VMEM((m, m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x)
