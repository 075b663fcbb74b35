"""Static wire layout: the whole per-direction message as ONE uint8
buffer with a precomputed offset table (DESIGN.md §6, §9).

Built once per (LayerPlan, wire dtype, direction) — the payload
structure of every leaf is derived abstractly (``jax.eval_shape`` over
the resolved compressor's ``init``/``compress``), so construction
allocates nothing and is safe inside a traced step.

Buffer layout, per message:

    [ leaf 0: stack slice 0 | stack slice 1 | ... ][ leaf 1: ... ] ...

Each slice region is the concatenation of that compressor's payload
leaves, each encoded by its codec (see ``codecs.py``).  ``pack`` maps
codecs over the lead + stack dims with the same ``vmap_n`` discipline
as every other optimizer phase, producing a ``[lead, total_nbytes]``
buffer.  The lead dim is the message multiplicity: ``n_workers``
independent messages for the w2s direction (replicating that buffer
over the worker mesh axis is the fused payload all-gather of the
step), and ``1`` for the s2w direction (the server's single broadcast
message, §9).  ``unpack`` is the bit-exact inverse, so the EF21/EF21-P
sender/receiver invariant survives the wire in both directions.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.layerwise import vmap_n

from .codecs import leaf_codecs


def _payload_struct(comp: Any, slice_shape: tuple[int, ...], in_dtype):
    """Abstract payload of one slice: eval_shape over init + compress."""
    def one(key):
        x = jnp.zeros(slice_shape, in_dtype)
        state = comp.init(key, slice_shape, in_dtype)
        payload, _ = comp.compress(state, x)
        return payload

    return jax.eval_shape(one, jax.random.key(0))


@dataclass(frozen=True)
class WireSpec:
    """Everything static about one parameter leaf's wire region."""
    offset: int                     # byte offset of the leaf region
    slice_nbytes: int               # packed bytes of ONE stack slice
    stack_shape: tuple[int, ...]
    n_stack: int
    codec_id: str                   # human-readable codec summary
    treedef: Any                    # payload treedef of one slice
    codecs: tuple                   # per payload leaf, flatten order
    splits: tuple[int, ...]         # byte offsets of payload leaves

    @property
    def region_nbytes(self) -> int:
        return self.n_stack * self.slice_nbytes

    # --------------------------------------------------- slice pack pair
    def pack_slice(self, payload: Any) -> jax.Array:
        leaves = self.treedef.flatten_up_to(payload)
        parts = [c.pack(x) for c, x in zip(self.codecs, leaves)]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def unpack_slice(self, buf: jax.Array) -> Any:
        leaves = [c.unpack(jax.lax.slice_in_dim(buf, o, o + c.nbytes))
                  for c, o in zip(self.codecs, self.splits)]
        return self.treedef.unflatten(leaves)


@dataclass(frozen=True)
class WireLayout:
    """Offset table + pack/unpack for the full per-step message."""
    specs: tuple[WireSpec, ...]     # aligned with LayerPlan.leaves
    total_nbytes: int               # exact bytes of one message
    direction: str = "w2s"          # which compressor family laid it out

    # ------------------------------------------------------ message pack
    def pack(self, flat_payloads: list) -> jax.Array:
        """Flat per-leaf payload list (leaves ``[lead, *stack, ...]`` —
        lead is ``n_workers`` for w2s, exactly as
        ``LayerPlan.map_flat(..., extra_vmap=1)`` produces them, or 1
        for the s2w broadcast message) -> ``[lead, total_nbytes]``
        uint8 buffer."""
        parts = []
        for spec, payload in zip(self.specs, flat_payloads):
            packed = vmap_n(spec.pack_slice,
                            len(spec.stack_shape) + 1)(payload)
            parts.append(packed.reshape(packed.shape[0], -1))
        return jnp.concatenate(parts, axis=1)

    def unpack(self, buf: jax.Array) -> list:
        """Bit-exact inverse of ``pack`` (same flat-list convention)."""
        n_workers = buf.shape[0]
        out = []
        for spec in self.specs:
            seg = jax.lax.slice_in_dim(
                buf, spec.offset, spec.offset + spec.region_nbytes, axis=1)
            seg = seg.reshape((n_workers,) + spec.stack_shape
                              + (spec.slice_nbytes,))
            out.append(vmap_n(spec.unpack_slice,
                              len(spec.stack_shape) + 1)(seg))
        return out

    # ------------------------------------------------------- bookkeeping
    def payload_structs(self, n_workers: int) -> list:
        """Abstract payload trees with the [n_workers, *stack] leading
        dims (what ``pack`` consumes) — for eval_shape checks/benches."""
        out = []
        for spec in self.specs:
            lead = (n_workers,) + spec.stack_shape
            out.append(jax.tree.map(
                lambda s, l=lead: jax.ShapeDtypeStruct(
                    l + tuple(s.shape), s.dtype),
                spec.treedef.unflatten(
                    [jax.ShapeDtypeStruct(c.shape,
                                          jnp.dtype(getattr(c, "dtype",
                                                            "int32")))
                     for c in spec.codecs])))
        return out

    def describe(self) -> list[dict]:
        """Static offset table (one row per leaf) for reports/tests."""
        return [{"offset": s.offset, "slice_nbytes": s.slice_nbytes,
                 "n_stack": s.n_stack, "codec": s.codec_id}
                for s in self.specs]


@dataclass(frozen=True)
class StagedWireLayout:
    """K contiguous stage sub-buffers repartitioning one ``WireLayout``
    along the staged wire pipeline (DESIGN.md §8).

    Each stage is itself a ``WireLayout`` over a subset of the plan's
    leaves (offsets rebased to be contiguous within the stage), so the
    per-stage pack/unpack reuses the exact §6 codec machinery — every
    leaf keeps its byte layout, only its *home buffer* changes. The
    stage byte counts sum to ``base.total_nbytes`` byte-for-byte: the
    "exactly ONE u8 all-gather of total_nbytes" invariant of §6 relaxes
    to "exactly K u8 all-gathers whose bytes sum to total_nbytes"."""
    base: WireLayout                          # the monolithic layout
    stage_leaf_ids: tuple[tuple[int, ...], ...]  # per stage, plan-leaf ids
    stages: tuple[WireLayout, ...]            # per-stage sub-layouts

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    @property
    def total_nbytes(self) -> int:
        return self.base.total_nbytes

    @property
    def direction(self) -> str:
        return self.base.direction

    def stage_nbytes(self, k: int) -> int:
        return self.stages[k].total_nbytes

    def pack_stage(self, k: int, flat_payloads: list) -> jax.Array:
        """Pack stage ``k``'s leaves out of the FULL plan-flat payload
        list (same convention as ``WireLayout.pack``) into that stage's
        ``[n_workers, stage_nbytes(k)]`` uint8 sub-buffer."""
        return self.stages[k].pack(
            [flat_payloads[i] for i in self.stage_leaf_ids[k]])

    def unpack_stage(self, k: int, buf: jax.Array) -> list:
        """Bit-exact inverse of ``pack_stage``: payload list aligned with
        ``stage_leaf_ids[k]``."""
        return self.stages[k].unpack(buf)


def build_staged_layout(layout: WireLayout,
                        stage_leaf_ids) -> StagedWireLayout:
    """Repartition ``layout`` into per-stage sub-layouts. The stage leaf
    id lists must partition ``range(len(layout.specs))`` — every leaf in
    exactly one stage — so the repartition is byte-exact by
    construction (validated)."""
    stage_leaf_ids = tuple(tuple(ids) for ids in stage_leaf_ids)
    flat = [i for ids in stage_leaf_ids for i in ids]
    if sorted(flat) != list(range(len(layout.specs))):
        raise ValueError(
            f"stage leaf ids {stage_leaf_ids} do not partition the "
            f"{len(layout.specs)} layout leaves")
    stages = []
    for ids in stage_leaf_ids:
        specs, offset = [], 0
        for i in ids:
            spec = dataclasses.replace(layout.specs[i], offset=offset)
            offset += spec.region_nbytes
            specs.append(spec)
        stages.append(WireLayout(specs=tuple(specs), total_nbytes=offset,
                                 direction=layout.direction))
    assert sum(s.total_nbytes for s in stages) == layout.total_nbytes
    return StagedWireLayout(base=layout, stage_leaf_ids=stage_leaf_ids,
                            stages=tuple(stages))


def build_layout(plan: Any, wire_dtype, direction: str = "w2s") -> WireLayout:
    """The WireLayout for a LayerPlan and direction — the static offset
    table the fused payload all-gather (w2s) or model-update broadcast
    (s2w, §9) is laid out by. ``direction`` selects which resolved
    compressor family (``lp.w2s`` / ``lp.s2w``) defines each leaf's
    payload structure; the byte machinery is direction-agnostic."""
    if direction not in ("w2s", "s2w"):
        raise ValueError(f"direction must be 'w2s' or 's2w', got "
                         f"{direction!r}")
    specs = []
    offset = 0
    for lp in plan.leaves:
        comp = getattr(lp, direction)
        in_dtype = (jnp.float32 if getattr(comp, "lossless_wire", False)
                    else jnp.dtype(wire_dtype))
        struct = _payload_struct(comp, lp.slice_shape, in_dtype)
        codecs, treedef = leaf_codecs(comp, lp.slice_shape, struct,
                                      plan.use_pallas)
        splits, pos = [], 0
        for c in codecs:
            splits.append(pos)
            pos += c.nbytes
        cid = getattr(comp, "name", type(comp).__name__) + "[" + \
            "+".join(c.cid for c in codecs) + "]"
        specs.append(WireSpec(
            offset=offset, slice_nbytes=pos, stack_shape=lp.stack_shape,
            n_stack=lp.n_stack, codec_id=cid, treedef=treedef,
            codecs=codecs, splits=tuple(splits)))
        offset += specs[-1].region_nbytes
    return WireLayout(specs=tuple(specs), total_nbytes=offset,
                      direction=direction)
