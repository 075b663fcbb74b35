"""Pallas TPU kernels for the wire bit-packing primitives (repro.wire).

Three bit-exact pack/unpack pairs, each with a pure-jnp reference that is
both the CPU execution path and the interpret-mode oracle:

  * ``pack_bits`` / ``unpack_bits`` — 1-bit plane packing (Natural sign
    planes): 8 consecutive {0,1} bytes -> one byte, LSB first.  Exactly
    the layout ops.py has always used, so Natural payloads stay
    bit-identical across backends.
  * ``narrow_encode`` / ``narrow_decode`` — width-byte integer encoding
    for TopK/ColumnTopK indices whose domain fits in 2 (uint16) or
    3 (uint24) bytes.  Plane-major little-endian layout: all low bytes,
    then the next plane(s) — each plane is a contiguous lane-aligned
    array, which keeps the TPU kernels pure VPU shift/mask ops.

Each takes ``use_pallas`` as a resolved bool (``ops.resolve_use_pallas``):
the kernel or its reference, never a choice of its own.

Kernel notes (TPU adaptation):
  * the 1-bit kernels are lane-dim reductions/expansions by 8; both are
    expressed as one [1024, 128]-tiled matmul against a constant
    selector matrix built from iota (bit values <= 255 and power-of-two
    weights are exactly representable, and the dot runs with HIGHEST
    precision, so the arithmetic is exact). uint8 is widened through
    int32 on both sides of the float math: Mosaic has no direct
    uint8 <-> float32 cast.
  * the narrow kernels never touch the MXU: plane-major layout makes
    encode a shifted mask per grid step and decode a shift-accumulate
    over the plane grid dimension (int32 VPU ops; exact by
    construction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_BITS_IN = _LANES * 8  # input lanes per packed 128-lane output tile


# ---------------------------------------------------------------- jnp refs

def pack_bits_ref(bits01: jax.Array) -> jax.Array:
    """[8k] uint8 of {0,1} -> [k] uint8 bit-packed (LSB first)."""
    b = bits01.reshape(-1, 8).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(b * weights[None, :], axis=1, dtype=jnp.uint8)


def unpack_bits_ref(packed: jax.Array) -> jax.Array:
    """[k] uint8 -> [8k] uint8 of {0,1} (inverse of pack_bits_ref)."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return ((packed[:, None] >> shifts[None, :]) & 1).reshape(-1)


def narrow_encode_ref(idx: jax.Array, width: int) -> jax.Array:
    """int32 [k] in [0, 2^(8*width)) -> uint8 [width*k], plane-major
    little-endian (plane i holds byte i of every element)."""
    shifts = jnp.arange(width, dtype=jnp.int32)[:, None] * 8
    return ((idx[None, :] >> shifts) & 0xFF).astype(jnp.uint8).reshape(-1)


def narrow_decode_ref(b: jax.Array, width: int) -> jax.Array:
    """uint8 [width*k] plane-major -> int32 [k]."""
    planes = b.reshape(width, -1).astype(jnp.int32)
    shifts = jnp.arange(width, dtype=jnp.int32)[:, None] * 8
    return jnp.sum(planes << shifts, axis=0, dtype=jnp.int32)


# --------------------------------------------------------- 1-bit kernels

def _pack_bits_kernel(b_ref, o_ref):
    # [bm, 1024] {0,1} -> [bm, 128]: one dot against the selector matrix
    # W[l, t] = (l // 8 == t) * 2^(l % 8).  All values are integers
    # <= 255 with power-of-two weights, so the HIGHEST-precision dot is
    # exact.
    l = jax.lax.broadcasted_iota(jnp.int32, (_BITS_IN, _LANES), 0)
    t = jax.lax.broadcasted_iota(jnp.int32, (_BITS_IN, _LANES), 1)
    w = jnp.where(l // 8 == t, jnp.exp2((l % 8).astype(jnp.float32)), 0.0)
    acc = jnp.dot(b_ref[...].astype(jnp.int32).astype(jnp.float32), w,
                  preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
    o_ref[...] = acc.astype(jnp.int32).astype(jnp.uint8)


def _unpack_bits_kernel(p_ref, o_ref):
    # [bm, 128] bytes -> [bm, 1024] bits: replicate each byte over its 8
    # bit lanes (dot with a 0/1 selector), then extract bit (l % 8) with
    # int32 shift/mask (bytes <= 255 are exact in f32).
    t = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _BITS_IN), 0)
    l = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _BITS_IN), 1)
    rep = jnp.dot(p_ref[...].astype(jnp.int32).astype(jnp.float32),
                  jnp.where(l // 8 == t, 1.0, 0.0),
                  preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
    shift = jax.lax.broadcasted_iota(jnp.int32, (1, _BITS_IN), 1) % 8
    bit = (rep.astype(jnp.int32) >> shift) & 1
    o_ref[...] = bit.astype(jnp.uint8)


def _rows_2d(flat: jax.Array, lanes: int,
             max_block: int = 256) -> tuple[jax.Array, int]:
    """Zero-pad a flat array to [rows, lanes] with rows % block == 0."""
    n = flat.shape[0]
    pad = (-n) % lanes
    x = jnp.pad(flat, (0, pad)).reshape(-1, lanes)
    rows = x.shape[0]
    block = rows if rows < max_block else max_block
    rpad = (-rows) % block
    if rpad:
        x = jnp.pad(x, ((0, rpad), (0, 0)))
    return x, block


def pack_bits(bits01: jax.Array, *, use_pallas: bool,
              interpret: bool = False) -> jax.Array:
    """[8k] uint8 of {0,1} -> [k] uint8, LSB first (bit-exact pair with
    ``unpack_bits``; layout identical to the historical ops.py packer)."""
    n = bits01.shape[0]
    assert n % 8 == 0, n
    if not use_pallas:
        return pack_bits_ref(bits01)
    x, block = _rows_2d(bits01, _BITS_IN)
    out = pl.pallas_call(
        _pack_bits_kernel,
        name="pack_bits",
        grid=(x.shape[0] // block,),
        in_specs=[pl.BlockSpec((block, _BITS_IN), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], _LANES), jnp.uint8),
        interpret=interpret,
    )(x)
    return out.reshape(-1)[:n // 8]


def unpack_bits(packed: jax.Array, *, use_pallas: bool,
                interpret: bool = False) -> jax.Array:
    """[k] uint8 -> [8k] uint8 of {0,1} (inverse of ``pack_bits``)."""
    k = packed.shape[0]
    if not use_pallas:
        return unpack_bits_ref(packed)
    x, block = _rows_2d(packed, _LANES)
    out = pl.pallas_call(
        _unpack_bits_kernel,
        name="unpack_bits",
        grid=(x.shape[0] // block,),
        in_specs=[pl.BlockSpec((block, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, _BITS_IN), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], _BITS_IN), jnp.uint8),
        interpret=interpret,
    )(x)
    return out.reshape(-1)[:8 * k]


# -------------------------------------------------------- narrow kernels

def _narrow_encode_kernel(i_ref, o_ref):
    # grid (planes, row blocks); plane j emits byte j of every element.
    j = pl.program_id(0)
    o_ref[...] = ((i_ref[...] >> (8 * j)) & 0xFF).astype(jnp.uint8)


def _narrow_decode_kernel(p_ref, o_ref, *, width: int):
    # grid (row blocks, planes); accumulate plane j << 8j into int32 out.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += p_ref[0].astype(jnp.int32) << (8 * j)


def narrow_width(domain: int) -> int:
    """Smallest byte width in {2, 3, 4} that indexes [0, domain)."""
    if domain <= 1 << 16:
        return 2
    if domain <= 1 << 24:
        return 3
    return 4


def narrow_encode(idx: jax.Array, width: int,
                  *, use_pallas: bool,
                  interpret: bool = False) -> jax.Array:
    """int32 [k] -> uint8 [width*k], plane-major little-endian.

    Values must lie in [0, 2^(8*width)); bit-exact pair with
    ``narrow_decode``. width == 4 round-trips any non-negative int32."""
    k = idx.shape[0]
    if not use_pallas:
        return narrow_encode_ref(idx, width)
    x, block = _rows_2d(idx, _LANES)
    rows = x.shape[0]
    out = pl.pallas_call(
        _narrow_encode_kernel,
        name="narrow_encode",
        grid=(width, rows // block),
        in_specs=[pl.BlockSpec((1, block, _LANES), lambda j, i: (0, i, 0))],
        out_specs=pl.BlockSpec((1, block, _LANES), lambda j, i: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((width, rows, _LANES), jnp.uint8),
        interpret=interpret,
    )(x[None])
    # plane-major: [width, rows*lanes] -> drop per-plane padding -> flat
    return out.reshape(width, -1)[:, :k].reshape(-1)


def narrow_decode(b: jax.Array, width: int,
                  *, use_pallas: bool,
                  interpret: bool = False) -> jax.Array:
    """uint8 [width*k] plane-major -> int32 [k]."""
    assert b.shape[0] % width == 0, (b.shape, width)
    k = b.shape[0] // width
    if not use_pallas:
        return narrow_decode_ref(b, width)
    pad = (-k) % _LANES
    planes = jnp.pad(b.reshape(width, k), ((0, 0), (0, pad)))
    planes = planes.reshape(width, -1, _LANES)
    rows = planes.shape[1]
    block = rows if rows < 256 else 256
    rpad = (-rows) % block
    if rpad:
        planes = jnp.pad(planes, ((0, 0), (0, rpad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_narrow_decode_kernel, width=width),
        name="narrow_decode",
        grid=(planes.shape[1] // block, width),
        in_specs=[pl.BlockSpec((1, block, _LANES), lambda i, j: (j, i, 0))],
        out_specs=pl.BlockSpec((block, _LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((planes.shape[1], _LANES), jnp.int32),
        interpret=interpret,
    )(planes)
    return out.reshape(-1)[:k]
