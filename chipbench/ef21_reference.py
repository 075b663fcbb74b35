"""Plain reference of EF21-Muon training steps, and its variants.

Follows the paper's Algorithm 3 as the configuration and the traffic
file state it, in straightforward ``jax.numpy`` and float32 at the
highest matmul precision, and imports nothing of the program:

    g_j   = grad f_j(X)                            per worker j
    M_j  <- (1 - beta) M_j + beta g_j
    R_j   = C(M_j - G_j);  G_j <- G_j + R_j        (EF21, per slice)
    G    <- G + mean_j R_j                         (server)
    X    <- X + t_i LMO_i(G)                       (per leaf i)

``X`` is held in the model's dtype and every update is rounded to it, as
the configuration states. Compressors: ``identity`` (the exact float32
difference), ``top<p>`` (the p% largest magnitudes of the bf16
difference, per layer slice) and ``+natural`` (each sent value rounded
to the nearest power of two). Vectors are sent uncompressed. LMOs:
spectral leaves take -NS(G) (quintic Newton-Schulz, five iterations on
the Frobenius-normalised slice, oriented rows <= columns) scaled by
sqrt(max(1, fan_out / fan_in)); the others take -sign(G).

Variants stand in for the program to give the readings the limits are
set from (``calibrate.py``, ``tests/chipbench``):

- ``lowp``: the control, one precision step below what the
  configuration states: every matrix product of the model takes its
  operands rounded to fp8 (e4m3, scaled per tensor), Newton-Schulz's to
  bfloat16;
- ``half_batch``: each worker's loss is the mean over the first half of
  its tokens only;
- ``altered``: one entry of the first spectral leaf of the produced
  ``X`` is off by 1.
"""
from __future__ import annotations

import math
import re
from functools import partial

import jax
import jax.numpy as jnp

VARIANTS = ("exact", "lowp", "half_batch", "altered")
NS_COEFFS = (3.4445, -4.7750, 2.0315)
NS_EPS = 1e-7


def fp8_round(a: jax.Array) -> jax.Array:
    """Round to fp8 e4m3 with a per-tensor scale, gradient passed
    straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 240.0
    q = jax.lax.reduce_precision(a / s, exponent_bits=4,
                                 mantissa_bits=3) * s
    return a + jax.lax.stop_gradient(q - a)


def bf16_round(a: jax.Array) -> jax.Array:
    return a.astype(jnp.bfloat16).astype(jnp.float32)


# --------------------------------------------------------- compressors


def natural_round(v: jax.Array) -> jax.Array:
    """Each value to the nearest power of two in linear distance: up
    when its mantissa is 1.5 or more, with 0 kept."""
    fr, ex = jnp.frexp(jnp.abs(v))          # |v| = fr 2^ex, fr in [.5, 1)
    p = jnp.ldexp(jnp.ones_like(fr), jnp.where(fr >= 0.75, ex, ex - 1))
    return jnp.where(v == 0, 0.0, jnp.sign(v) * p)


def parse_compressor(name: str) -> tuple[float | None, bool]:
    """(top fraction or None for all, natural rounding?)"""
    m = re.fullmatch(r"(identity|top(\d+)|natural)(\+natural)?", name)
    if m is None:
        raise ValueError(f"the reference has no compressor {name!r}")
    frac = int(m.group(2)) / 100 if m.group(2) else None
    natural = m.group(1) == "natural" or m.group(3) is not None
    if m.group(1) == "identity" and not natural:
        return None, False
    return frac, natural


def compress_slice(diff: jax.Array, frac, natural: bool) -> jax.Array:
    """The decompressed message of one slice's lossy compressor (f32):
    the bf16 difference, its top ``frac`` magnitudes kept, rounded to
    powers of two with ``natural``."""
    v = diff.astype(jnp.bfloat16).reshape(-1)
    if frac is not None:
        k = max(1, math.ceil(frac * v.shape[0]))
        _, idx = jax.lax.top_k(jnp.abs(v), k)
        kept = v[idx].astype(jnp.float32)
        if natural:
            kept = natural_round(kept)
        out = jnp.zeros(v.shape, jnp.float32).at[idx].set(kept)
    else:
        out = v.astype(jnp.float32)
        if natural:
            out = natural_round(out)
    return out.reshape(diff.shape)


def message(diff: jax.Array, spec: dict, w2s: str) -> jax.Array:
    """R = C(diff) for one leaf: per layer slice, vectors exact."""
    if spec["kind"] == "vector" or w2s == "identity":
        return diff
    frac, natural = parse_compressor(w2s)
    fn = partial(compress_slice, frac=frac, natural=natural)
    for _ in range(spec["stack"]):
        fn = jax.vmap(fn)
    return fn(diff)


# ---------------------------------------------------------------- LMO


def newton_schulz(g: jax.Array, quant, steps: int = 5) -> jax.Array:
    """Approximate U V^T of a [m, n] slice."""
    transpose = g.shape[0] > g.shape[1]
    x = g.T if transpose else g
    x = x / (jnp.sqrt(jnp.sum(jnp.square(x))) + NS_EPS)
    a, b, c = NS_COEFFS
    for _ in range(steps):
        gram = jnp.matmul(quant(x), quant(x.T))
        poly = b * gram + c * jnp.matmul(quant(gram), quant(gram))
        x = a * x + jnp.matmul(quant(poly), quant(x))
    return x.T if transpose else x


def lmo_update(x: jax.Array, g: jax.Array, spec: dict, t: float,
               quant, ns_steps: int) -> jax.Array:
    """X + t_i LMO(G), rounded to X's dtype."""
    if spec["kind"] == "spectral":
        fi, fo = spec["shape"][-2:]
        scale = math.sqrt(max(1.0, fo / fi))
        fn = partial(newton_schulz, quant=quant, steps=ns_steps)
        for _ in range(spec["stack"]):
            fn = jax.vmap(fn)
        d = -fn(g)
    else:
        scale = 1.0
        d = -jnp.sign(g)
    return (x.astype(jnp.float32) + t * scale * d).astype(x.dtype)


# --------------------------------------------------------------- steps


def _norms(tree: dict, lead: int) -> dict:
    """Per-leaf Frobenius norms over all but ``lead`` leading axes."""
    return {p: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                axis=tuple(range(lead, a.ndim))))
            for p, a in tree.items()}


def half_mask(b: int, s: int) -> jax.Array:
    """Keep the first half of a worker's B x S tokens."""
    keep = jnp.arange(b * s) < (b * s) // 2
    return keep.reshape(b, s).astype(jnp.float32)


def run(family, arch: dict, traffic: dict, params0: dict, batches: list,
        n_steps: int, variant: str = "exact", ns_steps: int = 5) -> dict:
    """``n_steps`` EF21-Muon steps from ``params0`` on ``batches[0:n]``
    (each {"tokens", "labels"} [W, B, S]). Returns the readout the
    comparison takes: per-step losses; after step 1 the per-worker
    gradient (M_j / beta), the workers' estimates G_j and the server's
    G, per leaf; after the last step |X - X0| per leaf; and the
    aggregate gap |G - mean_j G_j| / |G| after every step."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    specs = family.leaf_specs(arch)
    flat = family.flatten
    n_w, beta, t = traffic["n_workers"], traffic["beta"], traffic["radius"]
    w2s = traffic["w2s"]
    if traffic.get("s2w", "identity") != "identity":
        raise ValueError("the reference has no compressed s2w leg")
    lowp = variant == "lowp"
    mquant = fp8_round if lowp else None
    nquant = bf16_round if lowp else (lambda a: a)

    def grad_fn(x, batch):
        with jax.default_matmul_precision("highest"):
            xf = jax.tree.map(lambda a: a.astype(jnp.float32), x)
            b, s = batch["tokens"].shape
            mask = half_mask(b, s) if variant == "half_batch" else None
            return jax.value_and_grad(family.loss)(xf, batch, arch,
                                                   quant=mquant, mask=mask)

    grad_fn = jax.jit(grad_fn)

    @partial(jax.jit, donate_argnums=(0, 1))
    def worker_update(m, g, grads):
        m = {p: (1 - beta) * m[p] + beta * grads[p] for p in m}
        r = {p: message(m[p] - g[p], specs[p], w2s) for p in m}
        return m, {p: g[p] + r[p] for p in g}, r

    @partial(jax.jit, donate_argnums=(0, 1))
    def server_update(x, gs, rsum):
        gs = {p: gs[p] + rsum[p] / n_w for p in gs}
        with jax.default_matmul_precision("highest"):
            x = {p: lmo_update(x[p], gs[p], specs[p], t, nquant, ns_steps)
                 for p in x}
        return x, gs

    @jax.jit
    def agg_gap(gs, gws):
        num = sum(jnp.sum(jnp.square(gs[p] - sum(g[p] for g in gws) / n_w))
                  for p in gs)
        den = sum(jnp.sum(jnp.square(gs[p])) for p in gs)
        return jnp.sqrt(num / den)

    x0 = flat(params0)
    x = {p: jnp.copy(a) for p, a in x0.items()}   # donated below
    zeros = lambda: {p: jnp.zeros(a.shape, jnp.float32) for p, a in x.items()}
    gs = zeros()
    m_w = [zeros() for _ in range(n_w)]
    g_w = [zeros() for _ in range(n_w)]
    out = {"loss": [], "agg": []}
    for i in range(n_steps):
        losses, rsum = [], None
        for j in range(n_w):
            batch = jax.tree.map(lambda a: a[j], batches[i])
            loss, grads = grad_fn(_nest_like(params0, x), batch)
            m_w[j], g_w[j], r = worker_update(m_w[j], g_w[j], flat(grads))
            rsum = r if rsum is None else jax.tree.map(jnp.add, rsum, r)
            del grads, r
            losses.append(loss)
        x, gs = server_update(x, gs, rsum)
        del rsum
        out["loss"].append(float(jnp.mean(jnp.stack(losses))))
        out["agg"].append(float(agg_gap(gs, g_w)))
        if i == 0:
            out["grad"] = _stack_workers([_norms(m, 0) for m in m_w],
                                         1.0 / beta)
            out["g_w"] = _stack_workers([_norms(g, 0) for g in g_w], 1.0)
            out["g_server"] = {p: float(v) for p, v in _norms(gs, 0).items()}
    if variant == "altered":
        first = next(p for p in sorted(x) if specs[p]["kind"] == "spectral")
        a = x[first]
        x[first] = a.reshape(-1).at[0].add(1.0).reshape(a.shape)
    out["dx"] = {p: float(jnp.sqrt(jnp.sum(jnp.square(
        x[p].astype(jnp.float32) - x0[p].astype(jnp.float32)))))
        for p in x}
    return out


def _stack_workers(per_worker: list, scale: float) -> dict:
    return {p: [float(n[p]) * scale for n in per_worker]
            for p in per_worker[0]}


def _nest_like(tree: dict, flat_vals: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = prefix + k
        out[k] = _nest_like(v, flat_vals, p + "/") if isinstance(v, dict) \
            else flat_vals[p]
    return out
