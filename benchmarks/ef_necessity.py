"""EF-necessity ablation (paper §2 / Beznosikov et al. Example 1):
biased compression *without* error feedback stalls or diverges; the EF21
mechanism converges. Run on the 3-quadratic construction and on a tiny LM.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.compressors import TopK
from repro.core.error_feedback import ef_compress_step


def run(fast: bool = False):
    a = jnp.array([[-3.0, 2.0, 2.0], [2.0, -3.0, 2.0], [2.0, 2.0, -3.0]])

    def grad_j(x, j):
        return x + jnp.eye(3)[j] * x[j] + a[j]

    def full_grad(x):
        return jnp.mean(jnp.stack([grad_j(x, j) for j in range(3)]), 0)

    comp = TopK(0.34, use_pallas=False)
    lr, steps = 0.1, 100 if fast else 400
    x0 = jnp.array([1.0, 0.7, -0.3])

    x = x0
    for _ in range(steps):
        g = jnp.mean(jnp.stack([
            comp.decompress(comp.compress({}, grad_j(x, j))[0], (3,),
                            jnp.float32) for j in range(3)]), 0)
        x = x - lr * g
    naive_gn = float(jnp.linalg.norm(full_grad(x)))

    x = x0
    G = [jnp.zeros(3)] * 3
    for _ in range(steps):
        for j in range(3):
            _, _, G[j] = ef_compress_step(comp, {}, G[j], grad_j(x, j),
                                          jnp.float32)
        x = x - lr * jnp.mean(jnp.stack(G), 0)
    ef_gn = float(jnp.linalg.norm(full_grad(x)))

    x = x0
    for _ in range(steps):
        x = x - lr * full_grad(x)
    exact_gn = float(jnp.linalg.norm(full_grad(x)))

    return [{"bench": "ef_necessity", "method": "top1_no_ef",
             "grad_norm": naive_gn, "converged": naive_gn < 1e-2},
            {"bench": "ef_necessity", "method": "top1_ef21",
             "grad_norm": ef_gn, "converged": ef_gn < 1e-2},
            {"bench": "ef_necessity", "method": "exact_gd",
             "grad_norm": exact_gn, "converged": exact_gn < 1e-2}]
