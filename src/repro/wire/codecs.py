"""Per-payload-leaf wire codecs (DESIGN.md §6).

A *codec* is a bit-exact ``pack(array) -> uint8[nbytes]`` /
``unpack(bytes) -> array`` pair for one fixed-shape payload leaf. Two
cover every compressor in the registry:

  RawCodec        any array, byte-for-byte (bitcast).  bf16 value blobs,
                  Natural uint8 code planes, the already-bit-packed
                  Natural sign bitmaps, f32 lossless (Identity) diffs.
  NarrowIntCodec  int32 index arrays whose domain fits 2 (uint16) or
                  3 (uint24) bytes — TopK/ColumnTopK indices.  Width 4
                  degrades gracefully to raw little-endian int32.

The 9-bit Natural wire format falls out of composition: the uint8
exponent-code plane (RawCodec, 8 bits/value) and the 1-bit-packed sign
bitmap (RawCodec over the ``kernels.bitpack``-packed plane, 1 bit/value)
are laid out back-to-back in the same buffer region by the WireLayout.

Codec selection (``leaf_codecs``) is static: it reads the resolved
compressor and the abstract payload structure, never array values, so a
``WireLayout`` is built once per LayerPlan and reused by every traced
step. The narrow codecs run the Pallas kernels in ``kernels/bitpack.py``
or their bit-identical jnp references, as the plan's resolved
``use_pallas`` says (the kernels on a TPU, the references elsewhere).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import compressors as C
from repro.core.compressors import _nelem
from repro.kernels.bitpack import narrow_decode, narrow_encode, narrow_width


def _to_bytes(x: jax.Array) -> jax.Array:
    """Flatten any fixed-shape array to its uint8 byte view."""
    if x.dtype == jnp.uint8:
        return x.reshape(-1)
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint8).reshape(-1)
    return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)


def _from_bytes(b: jax.Array, shape: tuple[int, ...], dtype) -> jax.Array:
    """Inverse of ``_to_bytes`` (bit-exact)."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.uint8:
        return b.reshape(shape)
    if dtype == jnp.bool_:
        return b.reshape(shape).astype(jnp.bool_)
    it = dtype.itemsize
    return jax.lax.bitcast_convert_type(
        b.reshape(tuple(shape) + (it,)), dtype)


@dataclass(frozen=True)
class RawCodec:
    """Byte-for-byte bitcast of one payload leaf."""
    shape: tuple[int, ...]
    dtype: str                      # dtype name (keeps the dataclass hashable)

    @property
    def nbytes(self) -> int:
        return _nelem(self.shape) * jnp.dtype(self.dtype).itemsize

    @property
    def cid(self) -> str:
        return f"raw:{self.dtype}"

    def pack(self, x: jax.Array) -> jax.Array:
        assert tuple(x.shape) == tuple(self.shape), (x.shape, self.shape)
        return _to_bytes(x)

    def unpack(self, b: jax.Array) -> jax.Array:
        return _from_bytes(b, self.shape, self.dtype)


@dataclass(frozen=True)
class NarrowIntCodec:
    """int32 indices in [0, 2^(8*width)) as width-byte planes."""
    shape: tuple[int, ...]
    width: int                      # 2 (uint16) or 3 (uint24); 4 = raw
    use_pallas: bool                # encode/decode kernels or references

    @property
    def nbytes(self) -> int:
        return _nelem(self.shape) * self.width

    @property
    def cid(self) -> str:
        return f"u{8 * self.width}"

    def pack(self, x: jax.Array) -> jax.Array:
        assert tuple(x.shape) == tuple(self.shape), (x.shape, self.shape)
        return narrow_encode(x.astype(jnp.int32).reshape(-1), self.width,
                             use_pallas=self.use_pallas)

    def unpack(self, b: jax.Array) -> jax.Array:
        return narrow_decode(b, self.width,
                             use_pallas=self.use_pallas).reshape(self.shape)


def index_domains(comp: Any, slice_shape: tuple[int, ...]) -> dict[str, int]:
    """Payload-leaf name -> index domain size, for leaves that hold
    positions rather than values (eligible for narrow encoding)."""
    inner = comp.inner if isinstance(comp, C.WithNatural) else comp
    if isinstance(inner, C.TopK):
        return {"indices": _nelem(slice_shape)}
    if isinstance(inner, C.ColumnTopK):
        return {"indices": int(slice_shape[-1])}
    return {}


def leaf_codecs(comp: Any, slice_shape: tuple[int, ...],
                payload_struct: Any,
                use_pallas: bool) -> tuple[tuple, Any]:
    """(codecs, treedef) for one resolved compressor's per-slice payload.

    ``payload_struct`` is the abstract (ShapeDtypeStruct) payload of one
    slice; codecs are returned in payload-flatten order.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(payload_struct)
    domains = index_domains(comp, slice_shape)
    codecs = []
    for path, leaf in flat:
        name = getattr(path[-1], "key", "") if path else ""
        if name in domains and jnp.issubdtype(leaf.dtype, jnp.integer):
            width = narrow_width(domains[name])
            if width < 4:
                codecs.append(NarrowIntCodec(tuple(leaf.shape), width,
                                             use_pallas))
                continue
        codecs.append(RawCodec(tuple(leaf.shape), jnp.dtype(leaf.dtype).name))
    return tuple(codecs), treedef
