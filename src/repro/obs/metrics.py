"""In-graph metrics — a ``MetricSet`` pytree collected inside the jitted
step (DESIGN.md §10).

A MetricSet is an ordered name -> scalar mapping registered as a pytree,
so the optimizer can thread it through the step phases and return it in
``aux`` without a host sync: every value is a traced ``jnp`` scalar
(static accounting numbers like wire bytes become constants in the
graph). Collection is gated by ``EF21MuonConfig.metrics`` — the
metrics-off arm builds no MetricSet and lowers identically to a build
without this module.

Metric names are ``/``-separated taxonomies (DESIGN.md §10):

  ef/err_norm/<leaf>        ‖M_j - G_j'‖   post-update EF21 error, mean
                            over workers of the per-worker F-norm
  ef/rel_err/<leaf>         ‖C(v)-v‖/‖v‖   compression relative error of
                            v = M_j - G_j (0 where ‖v‖ == 0)
  ef/momentum_norm/<leaf>   ‖M_j‖          worker-mean momentum norm
  ef/topk_ties/<leaf>       TopK leaves: kept entries whose magnitude
                            equals the slice's smallest kept magnitude,
                            summed over the leaf's slices, mean over
                            workers (how much the tie cut at the lower
                            index decides)
  efp/err_norm/<leaf>       ‖X - W‖        EF21-P server model-estimate
                            error (s2w leg only)
  ns/orth_residual/<bucket> ‖G - I‖_F      Newton-Schulz orthogonality
                            residual, G the small-side gram of the
                            bucket direction, mean over the batch
  wire/...                  static per-direction wire bytes + stage count
  part/worker_version_lag_max   max s2w version lag across workers after
                            this round's rejoin (§13; 0 = all current)
  resync/replayed           workers that caught up this step by replaying
                            missed rounds from the ring (§13)
  resync/full               workers that rejoined via the full W resync
                            (lag > R)
  supervisor/retries        host-side: cumulative supervised-step
                            re-dispatches, merged into the step record by
                            the train CLI (never in-graph)

The helpers here are pure functions of tensors the step already
computes — adding them never feeds back into the update, which is what
makes the metrics-on arm value-bit-equal to metrics-off.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from repro.kernels.ref import magnitude_key32

_NAME_RE = re.compile(r"^[A-Za-z0-9_.+\-]+(/[A-Za-z0-9_.+\-]+)*$")


class MetricSet:
    """Ordered mapping of metric name -> scalar, registered as a pytree
    (names are static treedef data, values are leaves)."""

    def __init__(self, values: dict | None = None):
        self._values: dict = dict(values or {})

    def add(self, name: str, value) -> None:
        if not _NAME_RE.match(name):
            raise ValueError(f"bad metric name {name!r}")
        if name in self._values:
            raise ValueError(f"duplicate metric {name!r}")
        self._values[name] = jnp.asarray(value)

    def names(self) -> tuple[str, ...]:
        return tuple(self._values)

    def as_dict(self) -> dict:
        return dict(self._values)

    def host_floats(self) -> dict[str, float]:
        """Device-get every value (the one intentional sync point — the
        sink calls this every N steps, never the step itself)."""
        return {k: float(v) for k, v in
                zip(self._values, jax.device_get(list(self._values.values())))}

    def __len__(self) -> int:
        return len(self._values)

    def __getitem__(self, name: str):
        return self._values[name]

    def __contains__(self, name: str) -> bool:
        return name in self._values

    def __repr__(self) -> str:
        return f"MetricSet({list(self._values)})"


def _flatten(ms: MetricSet):
    return tuple(ms._values.values()), tuple(ms._values)


def _unflatten(names, values) -> MetricSet:
    return MetricSet(dict(zip(names, values)))


jax.tree_util.register_pytree_node(MetricSet, _flatten, _unflatten)


# ------------------------------------------------------------- norm helpers

def worker_mean_norm(x, lead: int = 1):
    """Mean over the ``lead`` leading (worker) dims of the F-norm over
    everything else — the per-layer norm the paper plots per worker."""
    x = jnp.asarray(x, jnp.float32)
    axes = tuple(range(lead, x.ndim))
    return jnp.mean(jnp.sqrt(jnp.sum(jnp.square(x), axis=axes)))


def rel_error(num, den, lead: int = 1):
    """Worker-mean of ‖num‖/‖den‖ per worker, 0 where ‖den‖ == 0."""
    num = jnp.asarray(num, jnp.float32)
    den = jnp.asarray(den, jnp.float32)
    axes = tuple(range(lead, num.ndim))
    n = jnp.sqrt(jnp.sum(jnp.square(num), axis=axes))
    d = jnp.sqrt(jnp.sum(jnp.square(den), axis=axes))
    return jnp.mean(jnp.where(d > 0, n / jnp.where(d > 0, d, 1.0), 0.0))


def topk_ties(target, indices):
    """Worker-mean over the leaf's slices of the kept entries tied with
    the smallest kept magnitude. ``target`` [W, *stack, *slice] is what
    TopK compressed, ``indices`` [W, *stack, k] its payload's."""
    flat = target.reshape(indices.shape[:-1] + (-1,))
    kept = magnitude_key32(jnp.take_along_axis(flat, indices, axis=-1))
    ties = jnp.sum(kept == jnp.min(kept, axis=-1, keepdims=True), axis=-1)
    return jnp.mean(jnp.sum(ties.reshape(ties.shape[0], -1), axis=1)
                    .astype(jnp.float32))


def orth_residual(d_b):
    """NS orthogonality residual of a bucket direction ``[B, m, n]``:
    mean over the batch of ‖G - I_k‖_F with G the gram over the smaller
    side (D Dᵀ for m <= n, Dᵀ D otherwise) — the quantity Newton-Schulz
    drives to 0 as the iterate approaches U Vᵀ."""
    d = jnp.asarray(d_b, jnp.float32)
    m, n = d.shape[-2:]
    if m <= n:
        g = jnp.einsum("...ij,...kj->...ik", d, d)
    else:
        g = jnp.einsum("...ji,...jk->...ik", d, d)
    k = min(m, n)
    r = g - jnp.eye(k, dtype=jnp.float32)
    return jnp.mean(jnp.sqrt(jnp.sum(jnp.square(r), axis=(-2, -1))))


def leaf_names(params) -> tuple[str, ...]:
    """Stable ``/``-joined key-path name per leaf of ``params``, in
    treedef (flatten) order — the <leaf> component of metric names."""
    paths, _ = zip(*jax.tree_util.tree_flatten_with_path(params)[0]) \
        if jax.tree_util.tree_flatten_with_path(params)[0] else ((), ())
    out = []
    for path in paths:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                raw = str(p.key)
            elif hasattr(p, "idx"):
                raw = str(p.idx)
            elif hasattr(p, "name"):
                raw = str(p.name)
            else:
                raw = str(p)
            parts.append(re.sub(r"[^A-Za-z0-9_.+\-]", "-", raw))
        out.append("/".join(parts) if parts else "param")
    return tuple(out)
