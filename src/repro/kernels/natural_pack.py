"""Pallas TPU kernel for Natural compression encode (Horvath et al. 2022).

Rounds bf16 values to the nearest power of two and emits the (exponent
code, sign) pair per element as uint8 planes — pure VPU bit manipulation,
elementwise-tiled in VMEM. The 8:1 sign bit-packing (which makes the wire
payload 9 bits/value) is a cheap reshape+dot done in ops.py after the
kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _natural_encode_kernel(x_ref, code_ref, sign_ref):
    # Round to bf16 (the wire format is defined on bf16), widen exactly to
    # f32 and do the bit work in int32: the TPU vector unit has no 16-bit
    # shifts. bf16 sign/exponent/top-mantissa bits 15/14..7/6 are f32 bits
    # 31/30..23/22, and the low 16 f32 bits are zero, so the codes are the
    # same as ref.natural_compress_ref's.
    xf = x_ref[...].astype(jnp.bfloat16).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(xf, jnp.int32)
    sign = (bits >> 31) & 0x1
    exp = (bits >> 23) & 0xFF
    mant_hi = (bits >> 22) & 0x1
    exp_rounded = jnp.minimum(exp + mant_hi, 254)
    is_zero = (bits & 0x7FFFFFFF) == 0
    code_ref[...] = jnp.where(is_zero, 0, exp_rounded).astype(jnp.uint8)
    sign_ref[...] = sign.astype(jnp.uint8)


def natural_encode(x: jax.Array, *, block_rows: int = 256,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Encode a [r, 128*k] bf16/f32 array -> (uint8 codes, uint8 signs).

    Rows must be a multiple of block_rows (ops.py pads/reshapes 1-D inputs).
    """
    r, cols = x.shape
    assert r % block_rows == 0, (x.shape, block_rows)
    grid = (r // block_rows,)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    return pl.pallas_call(
        _natural_encode_kernel,
        name="natural_encode",
        grid=grid,
        in_specs=[spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((r, cols), jnp.uint8),
                   jax.ShapeDtypeStruct((r, cols), jnp.uint8)),
        interpret=interpret,
    )(x)
