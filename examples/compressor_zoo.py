"""Tour of the non-Euclidean compressor zoo (paper §D): empirical
contraction factors alpha w.r.t. different norms, wire cost, and the
"LMO as compressor" view (§D.1: the nuclear-norm sharp operator IS a
Rank-1 compressor).

    PYTHONPATH=src python examples/compressor_zoo.py
"""
import jax
import jax.numpy as jnp

from repro.core.compressors import (ColumnTopK, Natural, RandomDropout,
                                    RankK, TopK, TopKSVD, WithNatural,
                                    empirical_alpha)
from repro.core.lmo import sharp
from repro.core.norms import norm

key = jax.random.key(0)
x = jax.random.normal(key, (64, 48))

print(f"{'compressor':22s} {'norm':10s} {'alpha_emp':>9s} {'bytes':>8s}")
for comp, kind in [
        (TopK(0.1, use_pallas=False), "frobenius"),
        (TopKSVD(rank=4), "spectral"),
        (TopKSVD(rank=4), "nuclear"),
        (TopKSVD(rank=4), "frobenius"),
        (ColumnTopK(0.25), "col_l2_dual"),
        (Natural(use_pallas=False), "frobenius"),
        (Natural(use_pallas=False), "linf"),
        (RandomDropout(0.6), "frobenius"),
        (RankK(fraction=0.15, use_pallas=False), "frobenius"),
        (WithNatural(TopK(0.15, use_pallas=False), use_pallas=False), "frobenius")]:
    a = empirical_alpha(comp, key, x, n_trials=4, norm_kind=kind)
    b = comp.payload_bytes(x.shape, jnp.bfloat16)
    print(f"{comp.name:22s} {kind:10s} {a:9.3f} {b:8d}")

# §D.1: the sharp operator of the nuclear norm is a Rank-1 compressor
gs = sharp(x, "nuclear")
res = float(norm(x - (-gs), "frobenius") / norm(x, "frobenius"))
print(f"\nnuclear-norm sharp operator as compressor: rank={int(jnp.linalg.matrix_rank(-gs))} "
      f"frobenius residual {res:.3f} (alpha ~ 1/rank(X))")
