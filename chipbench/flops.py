"""Operations and bytes that the algorithm requires, from shapes.

The counts are of the work the mathematics asks for, the same whatever
implements it: a kernel that pads, recomputes or takes more passes is
slower against them, never credited for it.
"""
from __future__ import annotations

import math


def matmul_flops(m: int, k: int, n: int) -> float:
    """An [m, k] @ [k, n] product: one multiply and one add per term."""
    return 2.0 * m * k * n


def attention_flops(seq: int, width: int) -> float:
    """Forward operations of one token's attention in one layer: its
    scores against ``seq`` keys and the weighted sum of ``seq`` values,
    over ``width`` = heads x head size."""
    return 2.0 * matmul_flops(1, width, seq)


def ns_flops(m: int, n: int) -> float:
    """One quintic Newton-Schulz iteration on an [m, n] slice, m <= n:
    the gram A = X X^T and its square are symmetric, so each needs only
    its upper triangle (m^2 n and m^3), and the product with X is
    2 m^2 n. A kernel that forms the full gram (4 m^2 n + 2 m^3) does
    more than this, never less, so its share stays under 100%."""
    m, n = min(m, n), max(m, n)
    return 3.0 * m * m * n + 1.0 * m ** 3


def ns_step_flops(specs: dict, ns_steps: int) -> float:
    """Newton-Schulz operations of one optimizer step: every slice of
    every spectral leaf, ``ns_steps`` iterations each."""
    total = 0.0
    for s in specs.values():
        if s["kind"] == "spectral":
            *stack, fi, fo = s["shape"]
            total += math.prod(stack) * ns_flops(fi, fo)
    return ns_steps * total


def ns_step_bytes(specs: dict, ns_steps: int) -> float:
    """Least HBM traffic of the same: each iteration reads and writes
    every f32 slice once."""
    total = 0
    for s in specs.values():
        if s["kind"] == "spectral":
            total += math.prod(s["shape"]) * 4 * 2
    return float(ns_steps * total)


def topk_count(fraction: float, n: int) -> int:
    """Entries a top-``fraction`` compressor keeps of ``n``."""
    return max(1, math.ceil(fraction * n))


def natural_encode_bytes(specs: dict, fraction: float,
                         n_workers: int) -> float:
    """HBM bytes of one step's Natural encodes: every worker encodes the
    kept bf16 values of each compressed slice (2 bytes read) into an
    exponent code and a sign (1 byte written each). Vectors are sent
    uncompressed and are not encoded."""
    values = 0
    for s in specs.values():
        if s["kind"] == "vector":
            continue
        stack = s["shape"][:s["stack"]]
        slice_n = math.prod(s["shape"][s["stack"]:])
        values += math.prod(stack) * topk_count(fraction, slice_n)
    return float(n_workers * values * (2 + 1 + 1))


def roofline_s(flop: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_flop = flop / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flop, "flops") if t_flop >= t_bytes else (t_bytes, "bytes")
