"""Contractive compressors — Euclidean and non-Euclidean (Def. 1, §D).

Functional API (jit-safe, fixed payload shapes):

    comp = TopK(fraction=0.1, use_pallas=False)
    state = comp.init(key, shape, dtype)          # sketches / PRNG, may be {}
    payload, state = comp.compress(state, x)      # payload: pytree of small arrays
    x_hat = comp.decompress(payload, shape, dtype)
    comp.payload_bytes(shape, dtype)              # analytic wire bytes / message

The *payload* is exactly what crosses the slow link in the distributed
step (all-gathered over the worker axis), so its size is what shows up in
the dry-run HLO collective accounting.

Included compressors and the norm w.r.t. which they are contractive:
  Identity        alpha = 1            (any norm)
  TopK            Euclidean            (classical; B_2)
  RankK           spectral/Frobenius   (PowerSGD-style subspace iteration with
                                        Newton-Schulz orthonormalisation;
                                        approximately contractive, Remark 11)
  TopKSVD         any Schatten norm    (exact truncated SVD; §D Def. 10)
  ColumnTopK      mixed l_{p,q}        (§D Def. 13, p=2)
  Natural         elementwise, 8/9     (round to nearest power of two)
  RandomDropout   any norm, alpha=p    (§D Def. 9)
  Damping         any norm             (§D Def. 8; theoretical curiosity)
  WithNatural(C)  composes Natural onto the float leaves of C's payload
                  (the paper's TopK+Natural / RankK+Natural combos)
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from repro.kernels import (natural_compress, natural_decompress,
                           newton_schulz, topk_select)
from repro.obs.trace import sub_span

Payload = Any
State = Any


def _nelem(shape) -> int:
    return int(math.prod(shape)) if shape else 1


def _itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


@dataclass(frozen=True)
class Identity:
    """True identity (the paper's "ID").

    ``lossless_wire`` is the capability flag the EF algebra and the wire
    layout read (instead of sniffing type names, which breaks for
    subclasses): True means the payload must carry the *exact* f32
    difference — no wire-dtype quantisation — so EF21 with this
    compressor recovers uncompressed Gluon bit-for-bit. Inherited by
    subclasses; False (the default on every lossy compressor) keeps the
    wire cast inside C where the feedback loop corrects it.
    """
    name: str = "identity"
    lossless_wire: ClassVar[bool] = True

    def init(self, key, shape, dtype) -> State:
        return {}

    def compress(self, state, x):
        return x, state

    def decompress(self, payload, shape, dtype):
        return payload.astype(dtype)

    def payload_bytes(self, shape, dtype) -> int:
        return _nelem(shape) * _itemsize(dtype)


@dataclass(frozen=True)
class Damping:
    """C(x) = gamma * x; contractive with alpha = 1-(1-gamma)^2 (§D Def. 8)."""
    gamma: float = 0.5

    @property
    def name(self):
        return f"damping{self.gamma}"

    def init(self, key, shape, dtype) -> State:
        return {}

    def compress(self, state, x):
        return (self.gamma * x.astype(jnp.float32)).astype(x.dtype), state

    def decompress(self, payload, shape, dtype):
        return payload.astype(dtype)

    def payload_bytes(self, shape, dtype) -> int:
        return _nelem(shape) * _itemsize(dtype)


@dataclass(frozen=True)
class RandomDropout:
    """C(x) = x w.p. p else 0; contractive with alpha = p (§D Def. 9)."""
    p: float = 0.5

    @property
    def name(self):
        return f"dropout{self.p}"

    def init(self, key, shape, dtype) -> State:
        return {"key": key}

    def compress(self, state, x):
        key, sub = jax.random.split(state["key"])
        keep = jax.random.bernoulli(sub, self.p)
        payload = {"keep": keep, "x": jnp.where(keep, x, jnp.zeros_like(x))}
        return payload, {"key": key}

    def decompress(self, payload, shape, dtype):
        return payload["x"].astype(dtype)

    def payload_bytes(self, shape, dtype) -> int:
        # expected wire cost: full message w.p. p, 1 bit otherwise
        return int(self.p * _nelem(shape) * _itemsize(dtype)) + 1


@dataclass(frozen=True)
class TopK:
    """Keep the k = ceil(fraction * n) largest-magnitude entries.

    The payload holds them in ascending index order: ``lax.top_k``'s set
    (ties to the lower index), selected without a sort by an exact
    magnitude threshold (``kernels.ops.topk_select``)."""
    fraction: float = 0.1
    # selection kernel or reference, resolved by the caller
    use_pallas: bool = field(kw_only=True)

    @property
    def name(self):
        return f"top{int(self.fraction * 100)}%"

    def k_for(self, shape) -> int:
        return max(1, int(math.ceil(self.fraction * _nelem(shape))))

    def init(self, key, shape, dtype) -> State:
        return {}

    def compress(self, state, x):
        with sub_span("ef.select"):
            vals, idx = topk_select(x.reshape(-1), self.k_for(x.shape),
                                    use_pallas=self.use_pallas)
        return {"values": vals, "indices": idx}, state

    def decompress(self, payload, shape, dtype):
        flat = jnp.zeros((_nelem(shape),), dtype=payload["values"].dtype)
        flat = flat.at[payload["indices"]].set(payload["values"])
        return flat.reshape(shape).astype(dtype)

    def payload_bytes(self, shape, dtype) -> int:
        k = self.k_for(shape)
        return k * (_itemsize(dtype) + 4)


@dataclass(frozen=True)
class ColumnTopK:
    """Keep the K columns with largest l2 norm (§D Def. 13, p=2).

    Contractive w.r.t. the mixed l_{2,q} norms (and Frobenius)."""
    fraction: float = 0.1

    @property
    def name(self):
        return f"coltop{int(self.fraction * 100)}%"

    def k_for(self, shape) -> int:
        return max(1, int(math.ceil(self.fraction * shape[-1])))

    def init(self, key, shape, dtype) -> State:
        return {}

    def compress(self, state, x):
        assert x.ndim == 2, "ColumnTopK expects a matrix"
        k = self.k_for(x.shape)
        colnorm = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=0)
        _, idx = jax.lax.top_k(colnorm, k)
        idx = idx.astype(jnp.int32)
        return {"cols": x[:, idx], "indices": idx}, state

    def decompress(self, payload, shape, dtype):
        out = jnp.zeros(shape, dtype=payload["cols"].dtype)
        out = out.at[:, payload["indices"]].set(payload["cols"])
        return out.astype(dtype)

    def payload_bytes(self, shape, dtype) -> int:
        k = self.k_for(shape)
        return k * shape[0] * _itemsize(dtype) + 4 * k


@dataclass(frozen=True)
class RankK:
    """PowerSGD-style rank-K compression with Newton-Schulz
    orthonormalisation and warm-started sketches (TPU-native RankK).

    compress(x [m, n]):  P = x @ Q;  P <- ns_orth(P);  Qn = x^T @ P
    payload (P, Qn); decompress = P @ Qn^T. State keeps Q = Qn (warm start),
    so the subspace tracks the error-feedback residual across steps.
    Approximately contractive w.r.t. Frobenius/spectral norms (Remark 11).
    """
    fraction: float | None = None   # rank = ceil(fraction * min(m, n)) ...
    rank: int | None = None         # ... or a fixed rank
    # Newton-Schulz kernel or reference, resolved by the caller
    use_pallas: bool = field(kw_only=True)

    @property
    def name(self):
        if self.rank is not None:
            return f"rank{self.rank}"
        return f"rank{int(self.fraction * 100)}%"

    def rank_for(self, shape) -> int:
        r_max = min(shape[-2], shape[-1])
        if self.rank is not None:
            return min(self.rank, r_max)
        return max(1, min(r_max, int(math.ceil(self.fraction * r_max))))

    def init(self, key, shape, dtype) -> State:
        assert len(shape) == 2, "RankK expects a matrix"
        r = self.rank_for(shape)
        q = jax.random.normal(key, (shape[1], r), dtype=jnp.float32)
        q = q / (jnp.linalg.norm(q, axis=0, keepdims=True) + 1e-12)
        return {"q": q.astype(dtype)}

    def compress(self, state, x):
        q = state["q"].astype(jnp.float32)
        xf = x.astype(jnp.float32)
        p = xf @ q
        p = newton_schulz(p, steps=5,                 # orthonormal-ish cols
                          use_pallas=self.use_pallas)
        qn = xf.T @ p
        payload = {"p": p.astype(x.dtype), "q": qn.astype(x.dtype)}
        return payload, {"q": qn.astype(state["q"].dtype)}

    def decompress(self, payload, shape, dtype):
        out = payload["p"].astype(jnp.float32) @ payload["q"].astype(jnp.float32).T
        return out.astype(dtype)

    def payload_bytes(self, shape, dtype) -> int:
        r = self.rank_for(shape)
        return (shape[0] + shape[1]) * r * _itemsize(dtype)


@dataclass(frozen=True)
class TopKSVD:
    """Exact truncated SVD (§D Def. 10) — contractive for all Schatten
    norms. Reference implementation (CPU/tests; SVD is TPU-hostile)."""
    rank: int = 1

    @property
    def name(self):
        return f"svd{self.rank}"

    def init(self, key, shape, dtype) -> State:
        return {}

    def compress(self, state, x):
        u, s, vt = jnp.linalg.svd(x.astype(jnp.float32), full_matrices=False)
        r = min(self.rank, s.shape[0])
        payload = {"us": u[:, :r] * s[None, :r], "vt": vt[:r, :]}
        return payload, state

    def decompress(self, payload, shape, dtype):
        return (payload["us"] @ payload["vt"]).astype(dtype)

    def payload_bytes(self, shape, dtype) -> int:
        r = self.rank
        return (shape[0] + shape[1]) * r * _itemsize(dtype)


@dataclass(frozen=True)
class Natural:
    """Round to nearest power of two; 9 bits/value on the wire.

    Elementwise relative error <= 1/3 => contractive with alpha = 8/9
    w.r.t. every absolute norm (Euclidean, l_inf, l1, Frobenius...)."""
    name: str = "natural"
    # encode/bit-pack kernels or references, resolved by the caller
    use_pallas: bool = field(kw_only=True)

    def init(self, key, shape, dtype) -> State:
        return {}

    def compress(self, state, x):
        codes, signs = natural_compress(x, use_pallas=self.use_pallas)
        return {"codes": codes, "signs": signs}, state

    def decompress(self, payload, shape, dtype):
        return natural_decompress(payload["codes"], payload["signs"],
                                  shape, dtype, use_pallas=self.use_pallas)

    def payload_bytes(self, shape, dtype) -> int:
        n = _nelem(shape)
        return n + (n + 7) // 8  # 9 bits / value


@dataclass(frozen=True)
class WithNatural:
    """Compose Natural onto the float leaves of an inner compressor's
    payload (the paper's TopK+Natural and RankK+Natural combos).

    jit-safe: the float-leaf shapes are reconstructed statically from the
    original array shape, so payloads stay fixed-shape pytrees of arrays.

    ``WithNatural(Identity)`` is supported end-to-end (compress,
    decompress and payload_bytes agree): the inner payload IS the array,
    so it Natural-compresses the whole message — semantically Natural,
    kept for composition symmetry. Quantisation makes the wrapper lossy
    regardless of the inner compressor (``lossless_wire = False``).
    """
    inner: Any
    # encode/bit-pack kernels or references, resolved by the caller
    use_pallas: bool = field(kw_only=True)
    lossless_wire: ClassVar[bool] = False

    @property
    def name(self):
        return f"{self.inner.name}+natural"

    def init(self, key, shape, dtype) -> State:
        return self.inner.init(key, shape, dtype)

    def _float_leaf_shapes(self, shape) -> dict[str, tuple[int, ...]]:
        """Float leaves of a dict payload (Identity's bare-array payload
        is handled directly in compress/decompress, consistent with the
        Identity branch of payload_bytes)."""
        if isinstance(self.inner, TopK):
            return {"values": (self.inner.k_for(shape),)}
        if isinstance(self.inner, RankK):
            r = self.inner.rank_for(shape)
            return {"p": (shape[0], r), "q": (shape[1], r)}
        if isinstance(self.inner, TopKSVD):
            r = self.inner.rank
            return {"us": (shape[0], r), "vt": (r, shape[1])}
        raise TypeError(f"WithNatural does not support {type(self.inner)}")

    def compress(self, state, x):
        payload, state = self.inner.compress(state, x)
        if isinstance(self.inner, Identity):
            codes, signs = natural_compress(payload,
                                            use_pallas=self.use_pallas)
            return {"codes": codes, "signs": signs}, state
        out = dict(payload)
        for name in self._float_leaf_shapes(x.shape):
            codes, signs = natural_compress(payload[name],
                                            use_pallas=self.use_pallas)
            out[name + "_codes"] = codes
            out[name + "_signs"] = signs
            del out[name]
        return out, state

    def decompress(self, payload, shape, dtype):
        if isinstance(self.inner, Identity):
            return self.inner.decompress(natural_decompress(
                payload["codes"], payload["signs"], shape, jnp.bfloat16,
                use_pallas=self.use_pallas), shape, dtype)
        inner_payload = dict(payload)
        for name, lshape in self._float_leaf_shapes(shape).items():
            inner_payload[name] = natural_decompress(
                payload[name + "_codes"], payload[name + "_signs"],
                lshape, jnp.bfloat16, use_pallas=self.use_pallas)
            del inner_payload[name + "_codes"]
            del inner_payload[name + "_signs"]
        return self.inner.decompress(inner_payload, shape, dtype)

    def payload_bytes(self, shape, dtype) -> int:
        inner_b = self.inner.payload_bytes(shape, dtype)
        # float portion shrinks to 9/ (8*itemsize); int indices unchanged.
        # Recompute precisely per inner type:
        if isinstance(self.inner, TopK):
            k = self.inner.k_for(shape)
            return k * 4 + k + (k + 7) // 8
        if isinstance(self.inner, (RankK, TopKSVD)):
            r = self.inner.rank_for(shape) if isinstance(self.inner, RankK) else self.inner.rank
            n = (shape[0] + shape[1]) * r
            return n + (n + 7) // 8
        if isinstance(self.inner, Identity):
            n = _nelem(shape)
            return n + (n + 7) // 8
        return inner_b  # fallback: no extra savings accounted

    # expose for RankK state compat
    def rank_for(self, shape):
        return self.inner.rank_for(shape)


def empirical_alpha(comp, key, x, n_trials: int = 8, norm_kind: str = "frobenius") -> float:
    """Estimate the contractivity parameter alpha = 1 - E||C(x)-x||^2/||x||^2."""
    from .norms import norm as _norm
    state = comp.init(key, x.shape, x.dtype)
    num = 0.0
    for i in range(n_trials):
        payload, state = comp.compress(state, x)
        xh = comp.decompress(payload, x.shape, jnp.float32)
        num += float(_norm(xh - x.astype(jnp.float32), norm_kind) ** 2)
    den = float(_norm(x, norm_kind) ** 2)
    return 1.0 - num / (n_trials * den)


# name -> factory(use_pallas): the kernel choice is handed to every
# compressor that has kernels (Natural, TopK, RankK and their WithNatural)
REGISTRY = {
    "identity": lambda up: Identity(),
    "natural": lambda up: Natural(use_pallas=up),
    "identity+natural": lambda up: WithNatural(Identity(), use_pallas=up),
    "top5": lambda up: TopK(0.05, use_pallas=up),
    "top10": lambda up: TopK(0.10, use_pallas=up),
    "top15": lambda up: TopK(0.15, use_pallas=up),
    "top20": lambda up: TopK(0.20, use_pallas=up),
    "top10+natural": lambda up: WithNatural(
        TopK(0.10, use_pallas=up), use_pallas=up),
    "top15+natural": lambda up: WithNatural(
        TopK(0.15, use_pallas=up), use_pallas=up),
    "rank5": lambda up: RankK(fraction=0.05, use_pallas=up),
    "rank10": lambda up: RankK(fraction=0.10, use_pallas=up),
    "rank15": lambda up: RankK(fraction=0.15, use_pallas=up),
    "rank20": lambda up: RankK(fraction=0.20, use_pallas=up),
    "rank10+natural": lambda up: WithNatural(
        RankK(fraction=0.10, use_pallas=up), use_pallas=up),
    "rank15+natural": lambda up: WithNatural(
        RankK(fraction=0.15, use_pallas=up), use_pallas=up),
}


def get_compressor(name: str, use_pallas: bool):
    """The registered compressor ``name`` with its kernel choice (a
    resolved bool: the Pallas kernels or the jnp references)."""
    if name not in REGISTRY:
        raise KeyError(f"unknown compressor '{name}'; have {sorted(REGISTRY)}")
    return REGISTRY[name](use_pallas)
