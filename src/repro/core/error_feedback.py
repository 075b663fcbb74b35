"""EF21 (w2s) and EF21-P (s2w) error-feedback algebra (§2, §A.2).

Both mechanisms share one primitive: maintain an estimate E of a target T,
transmit the compressed difference C(T - E), and advance E by the *exact
decompressed* message, so sender and receiver stay bit-identical:

    payload = C(T - E);   E' = E + decompress(payload)

EF21   : E = G_j (worker gradient estimator), T = M_j (momentum).
EF21-P : E = W   (worker model estimate),     T = X^{k+1} (server iterate).

The wire dtype is bf16: the cast is *inside* C, so the quantisation error
is part of the compression error the feedback loop corrects.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.obs.trace import sub_span


def ef_compress_step(comp, comp_state: Any, estimate: jax.Array,
                     target: jax.Array,
                     wire_dtype=jnp.bfloat16) -> tuple[Any, Any, jax.Array]:
    """One error-feedback round on a single tensor.

    Returns (payload, new_comp_state, new_estimate) with
    new_estimate = estimate + decompress(payload) in f32.
    """
    diff = (target.astype(jnp.float32) - estimate.astype(jnp.float32))
    # Lossless compressors (the paper's "ID" and subclasses) carry the
    # exact f32 difference — capability flag, not a type-name check, so
    # Identity subclasses stay lossless and WithNatural(Identity) does
    # not (the Natural wrapper quantises).
    if getattr(comp, "lossless_wire", False):
        wire_dtype = jnp.float32
    payload, comp_state = comp.compress(comp_state, diff.astype(wire_dtype))
    with sub_span("ef.update"):
        delta = comp.decompress(payload, diff.shape, jnp.float32)
        new_estimate = (estimate.astype(jnp.float32)
                        + delta).astype(estimate.dtype)
    return payload, comp_state, new_estimate


def apply_payload(comp, payload, estimate: jax.Array) -> jax.Array:
    """Receiver side: E' = E + decompress(payload)."""
    delta = comp.decompress(payload, estimate.shape, jnp.float32)
    return (estimate.astype(jnp.float32) + delta).astype(estimate.dtype)
