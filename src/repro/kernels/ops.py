"""Jit-ready wrappers around the Pallas kernels with padding + CPU fallback.

``use_pallas='auto'`` selects the Pallas path on TPU backends and the jnp
reference (the oracle in ref.py) on CPU, where Pallas only runs in
interpret mode (kept for tests, too slow for the training loop).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import ref
from .bitpack import pack_bits, unpack_bits
from .natural_pack import natural_encode
from .newton_schulz import (fused_ns_feasible, ns_iteration_fused,
                            ns_iteration_pallas)
from .topk_select import topk_select as topk_select_kernel

NS_COEFFS = ref.NS_COEFFS


def resolve_use_pallas(use_pallas: str | bool) -> bool:
    """The one kernel-selection rule of this package: ``"auto"`` means
    the Pallas kernels on a TPU backend and the jnp references everywhere
    else. ``EF21Muon`` resolves it once and passes the bool to every
    kernel call of the step, so no call on the chip falls back to a
    reference."""
    if use_pallas == "auto":
        return jax.default_backend() == "tpu"
    return bool(use_pallas)


# the stable ``name=`` of every NS pallas_call (kernels/newton_schulz.py)
NS_KERNEL_NAMES = ("ns_iteration_fused", "fused_matmul")


def count_ns_dispatches(jaxpr, names=NS_KERNEL_NAMES) -> int:
    """Recursively count NS pallas_call equations (fused or chained) in
    a jaxpr — the traced dispatch count the bucketing regression test
    and benchmarks/ns_bench.py both pin. Counts at trace level, so it
    works on any backend (nothing is lowered or executed)."""
    import jax.extend.core as jex

    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" \
                and eqn.params.get("name") in names:
            n += 1
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for vi in vs:                 # lax.cond/switch keep a tuple
                if isinstance(vi, jex.ClosedJaxpr):
                    n += count_ns_dispatches(vi.jaxpr, names)
                elif hasattr(vi, "eqns"):
                    n += count_ns_dispatches(vi, names)
    return n


def _pad_to(x: jax.Array, mult: int) -> tuple[jax.Array, tuple[int, int]]:
    m, n = x.shape
    pm = (-m) % mult
    pn = (-n) % mult
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x, (m, n)


def newton_schulz(g: jax.Array, steps: int = 5, coeffs=NS_COEFFS,
                  eps: float = 1e-7, use_pallas: str | bool = "auto",
                  block: int = 128, interpret: bool = False,
                  fused: str | bool = "auto") -> jax.Array:
    """Orthogonalise ``g`` (approximate UV^T of its SVD).

    Pallas path: pad to MXU-aligned multiples of ``block``, run the quintic
    iteration with blocked VMEM matmuls, then slice back. Zero padding is
    exact (padded rows/cols remain zero through X' = aX + (bA + cA^2)X).

    ``fused='auto'`` runs each iteration as ONE fused pallas_call (gram and
    poly in VMEM scratch) whenever the [m, m] gram fits the VMEM budget,
    falling back to the three-call chain; ``fused=False`` keeps the
    three-call chain unconditionally (the pre-fusion A/B reference).
    """
    if g.ndim != 2:
        raise ValueError("newton_schulz expects 2-D input")
    if not resolve_use_pallas(use_pallas):
        return ref.newton_schulz_ref(g, steps=steps, coeffs=coeffs, eps=eps)
    transpose = g.shape[0] > g.shape[1]
    x = g.T if transpose else g
    x = x / (jnp.linalg.norm(x.astype(jnp.float32)) + eps).astype(x.dtype)
    x, (m, n) = _pad_to(x, block)
    if fused == "auto":
        fused = fused_ns_feasible(x.shape[0], block, x.dtype.itemsize)
    for _ in range(steps):
        if fused:
            x = ns_iteration_fused(x[None], coeffs, block_m=block,
                                   block_n=block, interpret=interpret)[0]
        else:
            x = ns_iteration_pallas(x, coeffs, block=block,
                                    interpret=interpret)
    x = x[:m, :n]
    return x.T if transpose else x


def newton_schulz_batched(g: jax.Array, steps: int = 5, coeffs=NS_COEFFS,
                          eps: float = 1e-7,
                          use_pallas: str | bool = "auto", block: int = 128,
                          interpret: bool = False,
                          fused: str | bool = "auto",
                          mesh=None, pspec=None) -> jax.Array:
    """Orthogonalise a ``[B, m, n]`` stack of independent slices.

    The batched entry point behind shape bucketing (DESIGN.md §7): one
    dispatch chain of ``steps`` fused kernels for the whole stack. Callers
    canonicalise orientation (m <= n) before stacking — there is no
    per-slice transpose handling here. The jnp path is the bit-matching
    ``newton_schulz_batched_ref``; the Pallas path pads every slice to
    ``block`` multiples (zero padding is exact, as in ``newton_schulz``)
    and falls back to a vmapped three-call chain when the [m, m] gram
    exceeds the fused kernel's VMEM budget (or ``fused=False``).

    ``mesh``/``pspec`` make the chain sharding-aware (the
    ``ns_bucket_pspec`` of the stack, threaded down from the bucketed
    phase-5 dispatch): on the jnp path every iterate is pinned with
    ``with_sharding_constraint`` so the partitioner batch/TP-shards the
    chain instead of replicating it; on the Pallas path the fused kernel
    runs under ``shard_map`` over the batch axes of ``pspec``, each
    device dispatching its local ``[B/shards, m, n]`` sub-batch
    (``fused_ns_feasible`` gated on the per-device sub-batch). Both are
    value-identities — sharding never changes the math of a slice.
    """
    if g.ndim != 3:
        raise ValueError("newton_schulz_batched expects [B, m, n]")
    if not resolve_use_pallas(use_pallas):
        hook = None
        if mesh is not None and pspec is not None \
                and isinstance(mesh, jax.sharding.Mesh):
            sharding = jax.sharding.NamedSharding(mesh, pspec)
            hook = lambda x: jax.lax.with_sharding_constraint(x, sharding)
        return ref.newton_schulz_batched_ref(g, steps=steps, coeffs=coeffs,
                                             eps=eps, hook=hook)

    def chain(x):
        # per-shard body: normalise per slice, pad to block multiples,
        # run the iteration chain, slice back. Under shard_map x is the
        # local [B/shards, m, n] sub-batch and the VMEM feasibility gate
        # sees exactly what one device will dispatch.
        nrm = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                               axis=(-2, -1), keepdims=True))
        x = x / (nrm + eps).astype(x.dtype)
        m, n = x.shape[1:]
        pm, pn = (-m) % block, (-n) % block
        if pm or pn:
            x = jnp.pad(x, ((0, 0), (0, pm), (0, pn)))
        use_fused = fused
        if use_fused == "auto":
            use_fused = fused_ns_feasible(x.shape[1], block, x.dtype.itemsize)
        for _ in range(steps):
            if use_fused:
                x = ns_iteration_fused(x, coeffs, block_m=block,
                                       block_n=block, interpret=interpret)
            else:
                x = jax.vmap(lambda s: ns_iteration_pallas(
                    s, coeffs, block=block, interpret=interpret))(x)
        return x[:, :m, :n]

    if isinstance(mesh, jax.sharding.Mesh) and mesh.size > 1:
        # Mosaic kernels cannot be partitioned automatically: on a mesh
        # of several devices the chain always runs under shard_map, on
        # the batch axes of ``pspec`` when they divide the stack, else on
        # each device's replicated copy. Each shard needs its slices
        # whole (the fused kernel grams over the full [m, n] slice
        # locally), so any trailing model spec stays outside the
        # shard_map — the kernel is batch-parallel, TP applies to the
        # jnp path.
        from jax.sharding import PartitionSpec as P

        lead = pspec[0] if pspec is not None and len(pspec) else None
        axes = () if lead is None else (
            (lead,) if isinstance(lead, str) else tuple(lead))
        shards = 1
        for a in axes:
            shards *= mesh.shape[a]
        spec = P(lead, None, None) if shards > 1 \
            and g.shape[0] % shards == 0 else P()
        return jax.shard_map(chain, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, check_vma=False)(g)
    return chain(g)


def natural_compress(x: jax.Array, use_pallas: str | bool = "auto",
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Natural-compress any-shaped array -> (codes uint8 [N], packed signs
    uint8 [ceil(N/8)]). The wire payload is 9 bits/value."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    use_pallas = resolve_use_pallas(use_pallas)
    if use_pallas:
        lanes = 128
        pad = (-n) % (lanes * 8)
        padded = jnp.pad(flat, (0, pad)).reshape(-1, lanes)
        rows = padded.shape[0]
        block_rows = rows if rows < 256 else 256
        rpad = (-rows) % block_rows
        if rpad:
            padded = jnp.pad(padded, ((0, rpad), (0, 0)))
        code, sign = natural_encode(padded, block_rows=block_rows,
                                    interpret=interpret)
        code = code.reshape(-1)[:n + pad]
        sign = sign.reshape(-1)[:n + pad]
    else:
        pad = (-n) % 8
        flat_p = jnp.pad(flat, (0, pad))
        code, sign = ref.natural_compress_ref(flat_p)
    return code[:n], pack_bits(jnp.pad(sign[:n], (0, (-n) % 8)),
                               use_pallas=use_pallas, interpret=interpret)


def natural_decompress(code: jax.Array, packed_sign: jax.Array,
                       shape: tuple[int, ...], dtype=jnp.bfloat16,
                       use_pallas: str | bool = "auto",
                       interpret: bool = False) -> jax.Array:
    n = code.shape[0]
    sign = unpack_bits(packed_sign,
                       use_pallas=resolve_use_pallas(use_pallas),
                       interpret=interpret)[:n]
    vals = ref.natural_decompress_ref(code, sign)
    return vals.reshape(shape).astype(dtype)


def topk_select(flat: jax.Array, k: int, *, use_pallas: bool,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """The k largest-magnitude entries of a 1-D ``flat`` as (values in
    its dtype, int32 indices), in ascending index order: ``lax.top_k``'s
    set, ties to the lower index, found without a sort
    (``ref.topk_threshold``). The jnp path compacts with ``jnp.nonzero``;
    the Pallas path with the ``topk_select`` kernel, one call for every
    slice of the enclosing ``vmap``s. Both return the same payload bit
    for bit."""
    if not use_pallas:
        return ref.topk_select_ref(flat, k)

    @jax.custom_batching.custom_vmap
    def select(x):                            # x [..., n]
        threshold = lambda s: ref.topk_threshold(s, k)
        for _ in x.shape[:-1]:
            threshold = jax.vmap(threshold)
        t, c = threshold(x)
        idx, val = topk_select_kernel(x, t, c, k, interpret=interpret)
        return val.astype(x.dtype), idx

    @select.def_vmap
    def _batched(axis_size, in_batched, x):
        # each enclosing vmap adds a leading dim; the innermost call of
        # ``select`` folds them all into the kernel's slice axis
        if not in_batched[0]:
            x = jnp.broadcast_to(x, (axis_size,) + x.shape)
        return select(x), (True, True)

    return select(flat)
