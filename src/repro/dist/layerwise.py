"""Layer-wise tree plumbing for the EF21-Muon optimizer.

The optimizer is layer-wise by construction: every phase of a step
(EF21-P model shift, worker EF21 compression, server decompression, the
LMO update) is "for each parameter leaf: resolve its compressor, strip
its stack dims, vmap a per-slice function". A ``LayerPlan`` precomputes
all of that once per (treedef, metas, shapes) so the optimizer states
algorithm steps instead of tree mechanics:

    plan = LayerPlan.build(params, metas, w2s="rank10", s2w="natural",
                           use_pallas=False)
    new_x = plan.map_leaves(lmo_leaf, x_tree, g_tree)          # stack-vmapped
    outs  = plan.map_flat(ef_leaf, cw_l, gw_l, m_l, extra_vmap=1)  # + worker dim

Compressor resolution rule (deterministic, documented here once):
rank-type compressors (RankK, TopKSVD — with or without a Natural
wrapper) need a matrix slice; on a non-2D slice they fall back to
``TopK(0.25)``, keeping the Natural wrapper if one was requested. Such
leaves are vectors/scalars and contribute negligible wire bytes, so the
fallback fraction is not performance-relevant — but it is deterministic
and independent of the compressor *name*, unlike string sniffing.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.obs.trace import span


def vmap_n(fn: Callable, n: int) -> Callable:
    """vmap ``fn`` over the ``n`` leading (stack) dims of its args."""
    for _ in range(n):
        fn = jax.vmap(fn)
    return fn


def resolve_compressor(name: str, slice_shape: tuple[int, ...],
                       use_pallas: bool):
    """Instantiate the compressor for one leaf slice (see module
    docstring for the non-2D fallback rule), with its kernel choice."""
    # Deferred import: repro.core.muon (pulled in by repro.core.__init__)
    # imports this module, so a top-level core import would be circular.
    from repro.core import compressors as comp_lib

    comp = comp_lib.get_compressor(name, use_pallas)
    inner = comp.inner if isinstance(comp, comp_lib.WithNatural) else comp
    if isinstance(inner, (comp_lib.RankK, comp_lib.TopKSVD)) \
            and len(slice_shape) != 2:
        fallback = comp_lib.TopK(0.25, use_pallas=use_pallas)
        if isinstance(comp, comp_lib.WithNatural):
            return comp_lib.WithNatural(fallback, use_pallas=use_pallas)
        return fallback
    return comp


@dataclass(frozen=True)
class LeafPlan:
    """Everything static about one parameter leaf."""
    meta: Any                       # ParamMeta-like
    shape: tuple[int, ...]          # full leaf shape (no worker dim)
    stack_shape: tuple[int, ...]    # leading stack dims
    slice_shape: tuple[int, ...]    # per-layer operand the LMO/compressor sees
    n_stack: int                    # prod(stack_shape)
    w2s: Any                        # resolved worker->server compressor
    s2w: Any                        # resolved server->worker compressor


class LayerPlan:
    """Per-(treedef, metas, shapes) plan shared by every optimizer phase."""

    def __init__(self, treedef, leaves: list[LeafPlan], use_pallas: bool):
        self.treedef = treedef
        self.leaves = leaves
        self.use_pallas = use_pallas    # kernel choice of the wire codecs
        self._wire_layouts: dict = {}   # (dtype name, direction) -> WireLayout
        self._ns_buckets: dict = {}     # (mesh key, fsdp) -> tuple[NSBucket]
        self._stage_plans: dict = {}    # (mesh key, fsdp, stages) -> StagePlan
        self._staged_layouts: dict = {}  # (dtype, stage ids, direction)
        #                                  -> StagedWireLayout

    @classmethod
    def build(cls, params: Any, metas: Any, w2s: str = "identity",
              s2w: str = "identity", *,
              use_pallas: bool) -> "LayerPlan":
        """``params`` may be concrete arrays, ShapeDtypeStructs or
        tracers — only ``.shape`` is read. ``metas`` mirrors the params
        tree with ParamMeta leaves; incompressible leaves get identity
        compressors in both directions. ``use_pallas`` is the resolved
        kernel choice (``EF21Muon`` resolves it once), handed to every
        compressor and wire codec of the plan."""
        with span("plan/build"):
            leaves, treedef = jax.tree.flatten(params)
            metas_l = treedef.flatten_up_to(metas)
            plans = []
            for p, m in zip(leaves, metas_l):
                shape = tuple(p.shape)
                stack = shape[:m.stack_dims]
                sshape = shape[m.stack_dims:]
                wname = w2s if m.compressible else "identity"
                sname = s2w if m.compressible else "identity"
                plans.append(LeafPlan(
                    meta=m, shape=shape, stack_shape=stack, slice_shape=sshape,
                    n_stack=int(math.prod(stack)) if stack else 1,
                    w2s=resolve_compressor(wname, sshape, use_pallas),
                    s2w=resolve_compressor(sname, sshape, use_pallas)))
            return cls(treedef, plans, use_pallas)

    # ------------------------------------------------------------- tree ops
    def flatten(self, tree: Any) -> list:
        return self.treedef.flatten_up_to(tree)

    def unflatten(self, leaves: list) -> Any:
        return self.treedef.unflatten(leaves)

    def map_flat(self, fn: Callable, *flat: list, extra_vmap: int = 0) -> list:
        """``fn(leaf_plan, *slices)`` applied per leaf, vmapped over the
        leaf's stack dims plus ``extra_vmap`` extra leading dims (e.g. 1
        for the worker dimension). Inputs and output are flat lists in
        treedef order; tuple-valued ``fn`` results stay zipped per leaf."""
        out = []
        for lp, *xs in zip(self.leaves, *flat):
            out.append(vmap_n(partial(fn, lp),
                              lp.meta.stack_dims + extra_vmap)(*xs))
        return out

    def map_leaves(self, fn: Callable, *trees: Any,
                   extra_vmap: int = 0) -> Any:
        """Tree-in/tree-out version of ``map_flat``."""
        return self.unflatten(self.map_flat(
            fn, *[self.flatten(t) for t in trees], extra_vmap=extra_vmap))

    # ------------------------------------------------------ wire accounting
    def w2s_bytes_per_worker(self, wire_dtype) -> int:
        """Static bytes of one worker->server message (Table 2): the sum
        over leaves of stack-count x per-slice payload bytes. The single
        source of truth for wire accounting — the CLI and benchmarks read
        from here."""
        return sum(lp.n_stack * lp.w2s.payload_bytes(lp.slice_shape, wire_dtype)
                   for lp in self.leaves)

    def s2w_bytes_per_round(self, wire_dtype) -> int:
        """Static bytes of one server->worker model-update broadcast
        (the EF21-P / C_P direction, same Table-2 accounting convention
        as ``w2s_bytes_per_worker``). One message per round — the
        server broadcasts a single compressed S = C_P(X - W)."""
        return sum(lp.n_stack * lp.s2w.payload_bytes(lp.slice_shape, wire_dtype)
                   for lp in self.leaves)

    def dense_bytes(self, wire_dtype) -> int:
        """Uncompressed wire cost of the same message."""
        return dense_payload_bytes((lp.shape for lp in self.leaves),
                                   wire_dtype)

    # ------------------------------------------------------- NS bucketing
    def ns_buckets(self, mesh=None, fsdp: bool = False) -> tuple:
        """Shape buckets over the spectral leaves (DESIGN.md §7) — the
        static grouping behind the batched Newton-Schulz dispatch in
        phase 5 of the optimizer. With ``mesh`` each bucket also carries
        its ``ns_bucket_pspec`` (the sharding of the stacked chain).
        Built once per plan and (mesh shape, fsdp) combination."""
        from repro.dist.bucketing import build_buckets

        key = None if mesh is None else (
            tuple(mesh.axis_names),
            tuple(mesh.shape[a] for a in mesh.axis_names), fsdp)
        if key not in self._ns_buckets:
            with span("plan/ns_buckets"):
                self._ns_buckets[key] = build_buckets(self, mesh=mesh,
                                                      fsdp=fsdp)
        return self._ns_buckets[key]

    # ------------------------------------------------------- wire staging
    def stage_plan(self, mesh=None, fsdp: bool = False, wire_stages="auto",
                   ns_steps: int = 5):
        """The staged-wire-pipeline partition of this plan's leaves
        (DESIGN.md §8): stage 0 carries the per-leaf-path (eager) leaves,
        then one stage per NS bucket descending by NS FLOPs, capped at
        ``wire_stages`` by merging the smallest tail. Built once per
        (mesh shape, fsdp, wire_stages)."""
        from repro.dist.pipeline import build_stage_plan

        mesh_key = None if mesh is None else (
            tuple(mesh.axis_names),
            tuple(mesh.shape[a] for a in mesh.axis_names))
        key = (mesh_key, fsdp, wire_stages, ns_steps)
        if key not in self._stage_plans:
            with span("plan/stage_plan"):
                self._stage_plans[key] = build_stage_plan(
                    self, self.ns_buckets(mesh=mesh, fsdp=fsdp),
                    wire_stages=wire_stages, ns_steps=ns_steps)
        return self._stage_plans[key]

    def staged_wire_layout(self, wire_dtype, stage_plan,
                           direction: str = "w2s"):
        """The ``StagedWireLayout`` repartitioning ``wire_layout`` along
        ``stage_plan`` — memoised per (wire dtype, stage partition,
        direction). Both directions reuse the *same* leaf partition, so
        the s2w broadcasts pair 1:1 with the w2s gathers per stage."""
        from repro.wire.layout import build_staged_layout

        ids = tuple(s.leaf_ids for s in stage_plan.stages)
        key = (jnp.dtype(wire_dtype).name, ids, direction)
        if key not in self._staged_layouts:
            with span("plan/staged_wire_layout"):
                self._staged_layouts[key] = build_staged_layout(
                    self.wire_layout(wire_dtype, direction=direction), ids)
        return self._staged_layouts[key]

    def wire_layout(self, wire_dtype, direction: str = "w2s"):
        """The static WireLayout (repro.wire) for this plan and
        direction, memoised per (wire dtype, direction): the offset
        table of the fused per-worker payload buffer (``"w2s"``) or of
        the server's model-update broadcast message (``"s2w"``, §9).
        ``wire_layout(d, dir).total_nbytes`` is the *exact* byte count
        that direction's u8 collective moves — compare with the
        analytic Table-2 ``w2s_bytes_per_worker`` /
        ``s2w_bytes_per_round`` (which keep the paper's 4-byte-index
        convention)."""
        # Deferred import: repro.wire.layout imports this module.
        from repro.wire.layout import build_layout

        key = (jnp.dtype(wire_dtype).name, direction)
        if key not in self._wire_layouts:
            with span("plan/wire_layout"):
                self._wire_layouts[key] = build_layout(self, wire_dtype,
                                                       direction=direction)
        return self._wire_layouts[key]


def dense_payload_bytes(shapes, wire_dtype) -> int:
    """Wire bytes of an uncompressed message over the given leaf shapes —
    the one dense-accounting rule (LayerPlan and EF21Muon both call it)."""
    itemsize = jnp.dtype(wire_dtype).itemsize
    return sum(int(math.prod(s)) * itemsize for s in shapes)
