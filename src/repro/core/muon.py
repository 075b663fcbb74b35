"""EF21-Muon — the paper's contribution (Algorithms 1-3) as a composable
JAX optimizer.

Layer-wise by construction: every parameter leaf i carries a ParamMeta
(norm kind for its LMO, radius scale, stack depth), its own worker
compressors C_{i,j} and server compressor C_i, matching Algorithm 3. All
per-leaf mechanics (slice shapes, compressor resolution, stack vmaps)
live in one ``repro.dist.layerwise.LayerPlan`` built once per
(treedef, metas, shapes); the phases below state algorithm steps only.

The optimizer *owns* gradient evaluation (workers differentiate at their
model estimate W, not at X), so the API takes a grad function:

    opt   = EF21Muon(cfg)
    state = opt.init(key, params, metas)
    state, aux = opt.step(state, grad_and_loss, batch, t)

where ``grad_and_loss(params, batch_slice) -> (loss, grads)`` and ``batch``
has a leading worker dimension of size cfg.n_workers. Per-worker gradients
are computed with ``jax.vmap(..., in_axes=(None, 0))`` — workers stay
computationally independent, so the only cross-worker traffic in the
lowered HLO is the all-gather of compressed payloads (hooked via
``reshard_payloads`` by the distributed trainer).

Special cases recovered exactly (tested):
  * w2s = s2w = identity, n_workers = 1  ==> Gluon (=> Muon for spectral
    norms, Scion for spectral+sign maps).
  * beta = 1.0  ==> the deterministic Algorithm 2.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.dist.layerwise import LayerPlan, dense_payload_bytes, vmap_n
from repro.dist.participation import (mask_bcast, participation_mask,
                                      payload_finite_mask, reception_mask,
                                      validate_spec)
from repro.dist.pipeline import s2w_issue_order
from repro.dist.resync import (init_resync_state, replay_masks,
                               resolve_ring_depth, ring_push)
from repro.dist.sharding import worker_axis_for
from repro.kernels import resolve_use_pallas
from repro.obs.metrics import (MetricSet, leaf_names, orth_residual,
                               rel_error, topk_ties, worker_mean_norm)
from repro.obs.trace import (PHASE_SPANS, phase_span, sub_span,
                             wire_stage_span)

from .compressors import TopK
from .error_feedback import apply_payload, ef_compress_step
from .lmo import default_radius_scale, lmo_direction, lmo_direction_batched


@dataclass(frozen=True)
class ParamMeta:
    """Per-leaf optimizer metadata (the 'layer' of the layer-wise method)."""
    lmo: str = "spectral"          # norm kind for the LMO step
    radius_scale: float = 1.0      # per-layer radius multiplier t_i = scale * t
    stack_dims: int = 0            # leading dims that stack independent layers
    compressible: bool = True      # False => identity w2s compressor (tiny leaves)


def meta_like(x: jax.Array, path: str = "") -> ParamMeta:
    """Heuristic meta: 2-D matrices -> spectral (Muon), everything else ->
    sign (Scion's l_inf), embeddings/unembeddings -> sign."""
    shape = x.shape
    name = path.lower()
    stack = 0
    core = shape
    # stacked layers [L, ...] / experts [L, E, ...] are detected by models;
    # heuristic only handles unstacked leaves.
    if len(core) == 2 and not any(k in name for k in ("embed", "unembed", "lm_head")):
        return ParamMeta("spectral", default_radius_scale(core, "spectral"), stack)
    return ParamMeta("sign", 1.0, stack)


@dataclass(frozen=True)
class EF21MuonConfig:
    n_workers: int = 1
    beta: float = 0.1              # gradient weight: M = (1-beta) M + beta g
    w2s: str = "identity"          # worker->server compressor (C_D)
    s2w: str = "identity"          # server->worker compressor (C_P, EF21-P)
    ns_steps: int = 5
    use_pallas: Any = "auto"
    wire_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    wire_pack: bool = True         # fuse payloads into one uint8 wire buffer
    ns_bucketing: bool = True      # batch spectral LMOs by shape bucket (§7)
    wire_stages: Any = "auto"      # staged wire pipeline (§8): "auto" = one
                                   # stage per NS bucket + the eager chunk;
                                   # 1 = the monolithic single-gather path
                                   # (bit-identical A/B arm); N caps stages
    wire_pack_s2w: Any = "auto"    # pack the EF21-P server->worker model
                                   # update through the s2w wire leg (§9):
                                   # "auto" follows wire_pack; False keeps
                                   # the unpacked phase-1 path (the value-
                                   # bit-equal A/B arm); True forces it
    metrics: bool = False          # collect the in-graph MetricSet (§10):
                                   # per-leaf EF21 error/momentum norms,
                                   # compression rel. error, NS residual,
                                   # wire bytes — returned in
                                   # aux["metrics"], no host sync. Off =>
                                   # the step lowers identically (the
                                   # metric reads never feed the update,
                                   # so the on arm is value-bit-equal)
    trace_spans: bool = False      # jax.named_scope the five phases,
                                   # their sub-phases and every staged
                                   # wire collective (§10) so xprof shows
                                   # the §8 overlap by name; off => no
                                   # op-metadata change
    participation: Any = "full"    # elastic worker participation (§11):
                                   # "full" (every worker — takes the
                                   # exact pre-§11 code path, lowering-
                                   # identical), "bernoulli(p)",
                                   # "round_robin(k)", or a
                                   # dist.participation.Explicit mask
                                   # table. Absent workers' EF21 error/
                                   # momentum/compressor state freezes
                                   # and the server fold normalises by
                                   # the dynamic participant count; the
                                   # wire collectives keep their static
                                   # shapes (masked at fold time)
    participation_seed: int = 0    # seeds bernoulli schedules; the
                                   # history is deterministic in
                                   # (spec, seed, step) => resume-stable
    nonfinite_guard: bool = False  # per-worker payload finiteness check
                                   # (§11): a worker whose payload
                                   # carries NaN/Inf is demoted to non-
                                   # participating for the step; all-
                                   # poisoned steps fall back to a
                                   # global skip (X frozen). Forced on
                                   # whenever a FaultPlan is passed to
                                   # make_step
    resync: Any = None             # desynchronized-worker rejoin (§13):
                                   # None/0 compiles the subsystem out
                                   # (the default, lowering-identical
                                   # arm); an int R >= 1 keeps per-
                                   # worker model estimates W_j, a
                                   # [n_workers] version vector and a
                                   # replay ring of the last R packed
                                   # s2w broadcast rounds, so a worker
                                   # absent <= R rounds catches up by
                                   # replaying compressed deltas and a
                                   # longer absence takes a full W
                                   # copy. Requires a compressing s2w
                                   # leg (the stream being replayed)


def _unzip(pairs: list, n: int) -> tuple[list, ...]:
    return tuple(list(x) for x in zip(*pairs)) if pairs else tuple([] for _ in range(n))


@dataclass(frozen=True)
class WireBudget:
    """The static per-step collective budget of the two wire legs — the
    single source of truth behind the byte-for-byte invariants: exactly
    ``len(w2s_sizes)`` w2s payload all-gathers and ``len(s2w_sizes)``
    s2w broadcast all-gathers, each moving exactly its listed u8 bytes
    per device (one entry per stage sub-buffer; monolithic => one
    entry; an unpacked direction => no entries). Consumed by the
    dry-run attribution, the SPMD wire tests and the §12 lint rules, so
    the compiled-program checks can never drift from the resolution the
    step function actually uses (``EF21Muon.wire_budget``)."""
    pack_w2s: bool
    pack_s2w: bool
    n_stages: int                  # effective pipeline stages (1 = mono)
    w2s_sizes: tuple[int, ...]     # expected u8 bytes, one per gather
    s2w_sizes: tuple[int, ...]
    # replica-group size of a direction gather (the worker axis): lets
    # the lint attribution tell wire gathers from the model-axis TP
    # repack the partitioner may lower as sub-group gathers/permutes
    n_workers: int = 1

    @property
    def w2s_nbytes(self) -> int:
        return sum(self.w2s_sizes)

    @property
    def s2w_nbytes(self) -> int:
        return sum(self.s2w_sizes)

    @property
    def two_way_nbytes(self) -> int:
        return self.w2s_nbytes + self.s2w_nbytes


def resolve_pack_s2w(cfg: EF21MuonConfig, distributed: bool) -> bool:
    """The resolved s2w pack switch (§9): requires a compressing C_P and
    a communication hook, then ``wire_pack_s2w`` with "auto" following
    ``wire_pack``. Shared by ``make_step`` and every byte account."""
    return (cfg.s2w != "identity" and distributed
            and (cfg.wire_pack if cfg.wire_pack_s2w == "auto"
                 else bool(cfg.wire_pack_s2w)))


def resolve_stage_plan(cfg: EF21MuonConfig, plan, mesh=None,
                       fsdp: bool = False, any_pack: bool = True):
    """The resolved stage partition (§8), or None when the pipeline
    collapses to the monolithic single-gather path: staging needs a
    packed direction, NS bucketing, ``wire_stages != 1`` and more than
    one effective stage."""
    if not (any_pack and cfg.ns_bucketing and cfg.wire_stages != 1):
        return None
    sp = plan.stage_plan(mesh=mesh, fsdp=fsdp, wire_stages=cfg.wire_stages,
                         ns_steps=cfg.ns_steps)
    return sp if sp.n_stages > 1 else None


class EF21Muon:
    def __init__(self, cfg: EF21MuonConfig):
        # one kernel choice for the whole step: NS LMO, compressors and
        # wire codecs all read this resolved bool (kernels.ops)
        self.cfg = dataclasses.replace(
            cfg, use_pallas=resolve_use_pallas(cfg.use_pallas))
        self._plans: OrderedDict = OrderedDict()

    # ------------------------------------------------------------------ plan
    def plan(self, params: Any, metas: Any) -> LayerPlan:
        """The LayerPlan for this (treedef, shapes, dtypes, metas) —
        cached LRU (bounded at 8 entries, oldest dropped first), so init,
        every traced step and the wire accounting share one plan, and
        shape sweeps don't rebuild every live plan on eviction. Leaf
        dtypes are part of the key: switching param dtype must not reuse
        a stale plan (and its memoised wire layouts/buckets)."""
        leaves, treedef = jax.tree.flatten(params)
        metas_l = tuple(treedef.flatten_up_to(metas))
        key = (treedef, tuple(tuple(p.shape) for p in leaves),
               tuple(jnp.dtype(p.dtype).name for p in leaves), metas_l)
        if key in self._plans:
            self._plans.move_to_end(key)
        else:
            if len(self._plans) >= 8:
                self._plans.popitem(last=False)
            self._plans[key] = LayerPlan.build(
                params, metas, w2s=self.cfg.w2s, s2w=self.cfg.s2w,
                use_pallas=self.cfg.use_pallas)
        return self._plans[key]

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array, params: Any, metas: Any) -> dict:
        cfg = self.cfg
        sd = cfg.state_dtype
        plan = self.plan(params, metas)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, sd), params)
        g_w = jax.tree.map(
            lambda p: jnp.zeros((cfg.n_workers,) + p.shape, sd), params)
        m_w = None if cfg.beta >= 1.0 else jax.tree.map(
            lambda p: jnp.zeros((cfg.n_workers,) + p.shape, sd), params)

        n = len(plan.leaves)
        keys = jax.random.split(key, n * (cfg.n_workers + 1))
        cw_states, cs_states = [], []
        for i, lp in enumerate(plan.leaves):
            wire = jnp.dtype(cfg.wire_dtype)
            wkeys = jax.random.split(
                keys[i], cfg.n_workers * lp.n_stack).reshape(
                    (cfg.n_workers,) + lp.stack_shape)
            cw_states.append(vmap_n(
                lambda k, c=lp.w2s, s=lp.slice_shape: c.init(k, s, wire),
                lp.meta.stack_dims + 1)(wkeys))
            skeys = jax.random.split(keys[n + i], lp.n_stack).reshape(
                lp.stack_shape) if lp.stack_shape else keys[n + i]
            cs_states.append(vmap_n(
                lambda k, c=lp.s2w, s=lp.slice_shape: c.init(k, s, wire),
                lp.meta.stack_dims)(skeys))

        state = {
            "step": jnp.zeros((), jnp.int32),
            "x": params,
            "g_server": zeros,
            "g_w": g_w,
            "m_w": m_w,
            "cw_state": plan.unflatten(cw_states),
        }
        if cfg.s2w != "identity":
            state["w"] = jax.tree.map(lambda p: p.astype(sd), params)
            state["cs_state"] = plan.unflatten(cs_states)
        ring_depth = resolve_ring_depth(cfg.resync)
        if ring_depth:
            if cfg.s2w == "identity":
                raise ValueError(
                    "resync requires a compressing s2w leg (s2w != "
                    "'identity'): rejoin replays the server->worker "
                    "broadcast stream")
            # per-worker model estimates W_j (§13): every worker starts
            # current, bit-equal to the server's W
            state["w_w"] = jax.tree.map(
                lambda p: jnp.broadcast_to(
                    p.astype(sd)[None], (cfg.n_workers,) + p.shape) + 0,
                params)
            state["resync"] = init_resync_state(
                cfg.n_workers, ring_depth,
                plan.wire_layout(cfg.wire_dtype,
                                 direction="s2w").total_nbytes)
        return state

    # ------------------------------------------------------------ bookkeeping
    def w2s_bytes_per_worker(self, params: Any, metas: Any) -> int:
        """Static wire cost of one worker->server message (Table 2)."""
        return self.plan(params, metas).w2s_bytes_per_worker(
            self.cfg.wire_dtype)

    def wire_bytes_per_worker(self, params: Any, metas: Any) -> int:
        """Exact bytes of the fused uint8 wire buffer (repro.wire) — what
        the payload all-gather actually moves, next to the analytic
        Table-2 number above."""
        return self.plan(params, metas).wire_layout(
            self.cfg.wire_dtype).total_nbytes

    def s2w_bytes_per_round(self, params: Any, metas: Any) -> int:
        """Static wire cost of one server->worker model-update broadcast
        (Table-2 convention, EF21-P direction)."""
        return self.plan(params, metas).s2w_bytes_per_round(
            self.cfg.wire_dtype)

    def wire_bytes_s2w(self, params: Any, metas: Any) -> int:
        """Exact bytes of the fused s2w uint8 broadcast buffer (§9) —
        what the model-update broadcast actually moves per round."""
        return self.plan(params, metas).wire_layout(
            self.cfg.wire_dtype, direction="s2w").total_nbytes

    def dense_bytes(self, params: Any) -> int:
        return dense_payload_bytes(
            (p.shape for p in jax.tree.leaves(params)), self.cfg.wire_dtype)

    def wire_budget(self, params: Any, metas: Any, mesh=None,
                    fsdp: bool = False,
                    distributed: bool = True) -> WireBudget:
        """The resolved :class:`WireBudget` for this config on
        ``params`` — the exact u8 collective population ``make_step``'s
        lowering emits, computed through the same ``resolve_pack_s2w``
        / ``resolve_stage_plan`` switches the step function uses.
        ``distributed=False`` models the hook-less single-process step
        (no collectives, both directions unpacked)."""
        cfg = self.cfg
        plan = self.plan(params, metas)
        pack_w2s = bool(cfg.wire_pack and distributed)
        pack_s2w = resolve_pack_s2w(cfg, distributed)
        splan = resolve_stage_plan(cfg, plan, mesh=mesh, fsdp=fsdp,
                                   any_pack=pack_w2s or pack_s2w)

        def sizes(direction: str, packed: bool) -> tuple[int, ...]:
            if not packed:
                return ()
            if splan is not None:
                sw = plan.staged_wire_layout(cfg.wire_dtype, splan,
                                             direction=direction)
                return tuple(sw.stage_nbytes(k)
                             for k in range(sw.n_stages))
            return (plan.wire_layout(
                cfg.wire_dtype, direction=direction).total_nbytes,)

        return WireBudget(pack_w2s, pack_s2w,
                          splan.n_stages if splan is not None else 1,
                          sizes("w2s", pack_w2s), sizes("s2w", pack_s2w),
                          n_workers=cfg.n_workers)

    # The jit-friendly entry point: metas are static, so we build the step
    # function once per (metas, shapes) and let the caller jit it.
    def make_step(self, metas: Any,
                  reshard_payloads: Callable | None = None,
                  mesh=None,
                  fsdp: bool = False,
                  reshard_updates: Callable | None = None,
                  faults=None) -> Callable:
        """``reshard_payloads`` is the cross-worker communication hook
        (the trainer's worker-axis all-gather). None means single-process
        — there is no collective to fuse, so the wire pack/unpack is
        skipped entirely (it is a values-identity either way).

        ``reshard_updates`` is the same hook for the opposite direction
        (§9): the tiled ``[n_workers, nbytes]`` s2w broadcast buffer —
        every worker-domain's copy of the server's compressed model
        update — is pinned to the worker axis and re-replicated, which
        lowers to one u8 all-gather per stage sub-buffer whose
        per-device operand bytes are exactly the s2w layout account
        (the per-link cost of a broadcast). Defaults to
        ``reshard_payloads``; pass one explicitly to split the hooks.

        ``mesh``/``fsdp`` make the bucketed phase-5 dispatch
        sharding-aware: each NS bucket carries its ``ns_bucket_pspec``
        and the batched chain is pinned to it (constraints on the jnp
        path, ``shard_map`` around the fused kernel on the Pallas path)
        instead of losing the per-leaf TP/zero-1 shardings at the bucket
        concat. Single-process callers leave them unset.

        ``faults`` is an optional ``train.faults.FaultPlan`` — a seeded,
        declared schedule of worker drops, poisoned gradient leaves and
        bit-flipped wire payloads injected inside the step (§11). Passing
        one forces the non-finite guard on."""
        cfg = self.cfg
        validate_spec(cfg.participation, cfg.n_workers)
        # elastic participation (§11): the masked fold/commit path is
        # built only when something can actually mask — participation
        # "full" without the guard takes the exact pre-§11 code path
        # (lowering-identical, the bit-equal A/B arm)
        guard = cfg.nonfinite_guard or faults is not None
        elastic = cfg.participation != "full" or guard
        ring_depth = resolve_ring_depth(cfg.resync)
        if ring_depth and cfg.s2w == "identity":
            raise ValueError(
                "resync requires a compressing s2w leg (s2w != "
                "'identity'): rejoin replays the server->worker "
                "broadcast stream")
        resync_on = ring_depth > 0
        pack_wire = cfg.wire_pack and reshard_payloads is not None
        if reshard_updates is None:
            reshard_updates = reshard_payloads
        pack_s2w = resolve_pack_s2w(cfg, reshard_updates is not None)
        if reshard_payloads is None:
            reshard_payloads = lambda tree: tree
        if reshard_updates is None:
            reshard_updates = lambda tree: tree

        # Mosaic kernels cannot be partitioned automatically. On a mesh of
        # several devices the kernel-bearing pieces of the step (compress,
        # pack, unpack, decompress) therefore run per device under
        # shard_map: worker-side pieces on their worker's shard of the
        # worker axis, server-side pieces on each device's replicated
        # copy. That is a value identity and adds no u8 collective: the
        # (4, 1) nanogpt step compiled for a v5e 2x2 holds the same u8
        # all-gathers with and without it. The jnp path stays with the
        # partitioner, lowering as before.
        kernel_mesh = (mesh if cfg.use_pallas
                       and isinstance(mesh, jax.sharding.Mesh)
                       and mesh.size > 1 else None)
        wspec = (jax.sharding.PartitionSpec(worker_axis_for(mesh))
                 if kernel_mesh is not None else None)

        def per_device(fn, spec=jax.sharding.PartitionSpec()):
            if kernel_mesh is None:
                return fn
            return lambda *args: jax.shard_map(
                fn, mesh=kernel_mesh, in_specs=(spec,) * len(args),
                out_specs=spec, check_vma=False)(*args)

        def step(state: dict, grad_and_loss: Callable, batch: Any,
                 t: jax.Array | float) -> tuple[dict, dict]:
            plan = self.plan(state["x"], metas)

            # Observability (§10): in-graph MetricSet + phase/wire spans.
            # Both default off; the off arm takes the identical code path
            # (phase_span without graph= and every sub_span are no-ops)
            # so it compiles byte-identical to a build without the obs
            # layer. Metric reads never feed the update, so the
            # metrics-on arm stays value-bit-equal.
            gspan = cfg.trace_spans
            mset = MetricSet() if cfg.metrics else None
            lnames = leaf_names(state["x"]) if cfg.metrics else None

            # Stage structure first — both wire directions cut their
            # buffers along the same leaf partition (§8, §9).
            buckets = (plan.ns_buckets(mesh=mesh, fsdp=fsdp)
                       if cfg.ns_bucketing else ())
            bucketed = {i for b in buckets for i in b.leaf_ids}
            splan = resolve_stage_plan(cfg, plan, mesh=mesh, fsdp=fsdp,
                                       any_pack=pack_wire or pack_s2w)

            # ---- §13 reception mask: who hears THIS round's s2w
            # broadcast. Network-level semantics: scheduled absence and
            # declared drop faults gate reception; guard demotion does
            # NOT (a demoted worker's compute is poisoned, not its
            # downlink), so it is computed up front, before the guard
            # can see any payload.
            recv = None
            if resync_on:
                recv = reception_mask(
                    cfg.participation, cfg.n_workers, state["step"],
                    cfg.participation_seed, faults=faults)

            # ---- 1. EF21-P: workers' model estimate W (S = C_P(X - W)).
            # With s2w wire packing the broadcast leg is explicit (§9):
            # the server packs S into the s2w uint8 wire buffer, tiles
            # it to [n_workers, nbytes] (each row one worker-domain's
            # copy of the same message) and the reshard_updates hook
            # pins it to the worker axis then re-replicates — one u8
            # all-gather per stage sub-buffer whose per-device operand
            # is exactly the s2w layout bytes, i.e. the per-link cost
            # of the broadcast. W is then reconstructed from the *wire
            # bytes* via apply_payload, so server and workers advance
            # bit-identical EF21-P state; the unpacked arm
            # (wire_pack_s2w=False) is value-bit-equal because
            # pack -> unpack is bit-exact and apply_payload is the
            # same estimate update ef_compress_step performs.
            ring_row = None   # §13: this round's packed s2w bytes
            with phase_span(PHASE_SPANS[0], gspan):
                if cfg.s2w != "identity" and pack_s2w:
                    cs_f = plan.flatten(state["cs_state"])
                    w_f = plan.flatten(state["w"])
                    x_f0 = plan.flatten(state["x"])
                    s_payloads, cs_l = per_device(
                        lambda *fl: _unzip(plan.map_flat(
                            lambda lp, cs, w, x: ef_compress_step(
                                lp.s2w, cs, w, x, cfg.wire_dtype)[:2],
                            *fl), 2))(cs_f, w_f, x_f0)
                    # lead dim 1: the server's single broadcast message
                    lead = [jax.tree.map(lambda a: a[None], p)
                            for p in s_payloads]

                    def broadcast(buf):
                        # The max-fold over the gathered (bit-identical
                        # u8) rows is a value identity that consumes
                        # EVERY row, so the partitioner cannot shrink or
                        # elide the gather behind the invariant.
                        tiled = jnp.broadcast_to(
                            buf, (cfg.n_workers,) + tuple(buf.shape[1:]))
                        return jnp.max(reshard_updates(tiled),
                                       axis=0, keepdims=True)

                    def s2w_apply(i, pl):
                        lp = plan.leaves[i]
                        return per_device(vmap_n(
                            lambda q, w: apply_payload(lp.s2w, q, w),
                            lp.meta.stack_dims))(
                                jax.tree.map(lambda a: a[0], pl), w_f[i])

                    w_l: list = [None] * len(plan.leaves)
                    if splan is not None:
                        swire = plan.staged_wire_layout(
                            cfg.wire_dtype, splan, direction="s2w")
                        order = s2w_issue_order(plan, splan)
                        # all K broadcasts issued up front, heaviest
                        # receive chain first (§9 overlap story)
                        sbufs = {}
                        for k in order:
                            with phase_span(wire_stage_span("s2w", k),
                                            gspan):
                                sbufs[k] = broadcast(per_device(
                                    partial(swire.pack_stage, k))(lead))
                        if resync_on:
                            # §13 replay ring row: this round's gathered
                            # broadcast bytes verbatim, stage sub-
                            # buffers concatenated in stage order
                            ring_row = jnp.concatenate(
                                [sbufs[k][0]
                                 for k in range(splan.n_stages)])
                        for k in order:
                            for i, pl in zip(
                                    splan.stages[k].leaf_ids,
                                    per_device(partial(
                                        swire.unpack_stage, k))(
                                            sbufs[k])):
                                w_l[i] = s2w_apply(i, pl)
                    else:
                        swire = plan.wire_layout(cfg.wire_dtype,
                                                 direction="s2w")
                        with phase_span(wire_stage_span("s2w", 0), gspan):
                            buf = broadcast(per_device(swire.pack)(lead))
                        if resync_on:
                            ring_row = buf[0]
                        for i, pl in enumerate(
                                per_device(swire.unpack)(buf)):
                            w_l[i] = s2w_apply(i, pl)
                    w_tree = plan.unflatten(w_l)
                    cs_tree = plan.unflatten(cs_l)
                elif cfg.s2w != "identity":
                    s_payloads, cs_l, w_l = _unzip(plan.map_flat(
                        lambda lp, cs, w, x: ef_compress_step(
                            lp.s2w, cs, w, x, cfg.wire_dtype),
                        plan.flatten(state["cs_state"]),
                        plan.flatten(state["w"]),
                        plan.flatten(state["x"])), 3)
                    w_tree = plan.unflatten(w_l)
                    cs_tree = plan.unflatten(cs_l)
                    if resync_on:
                        # unpacked arm: no wire bytes exist, so pack the
                        # ring row locally through the same monolithic
                        # s2w layout — a value identity with the packed
                        # arm's gathered bytes (pack is deterministic
                        # and unpack is its bit-exact inverse)
                        lead = [jax.tree.map(lambda a: a[None], p)
                                for p in s_payloads]
                        ring_row = plan.wire_layout(
                            cfg.wire_dtype, direction="s2w").pack(lead)[0]
                else:
                    w_tree, cs_tree = state["x"], None

            # ---- §13 rejoin: push this round into the replay ring,
            # advance the version vector, and bring every receiving
            # worker's W_j current — by replaying missed rounds from the
            # ring (lag <= R, ascending round order, the exact
            # apply_payload algebra per slot) or by a full copy of the
            # server's post-round W (lag > R). Each ring slot is
            # decompressed ONCE (the broadcast was a single message) and
            # the per-worker application is where-masked, so replay adds
            # no collectives — the §8/§9 wire invariants are untouched.
            if resync_on:
                with phase_span("resync/replay", gspan):
                    ring_new = ring_push(state["resync"]["ring"],
                                         ring_row)
                    rm = replay_masks(state["resync"]["vv"],
                                      state["step"], recv, ring_depth)
                    if pack_s2w and splan is not None:
                        rswire = plan.staged_wire_layout(
                            cfg.wire_dtype, splan, direction="s2w")
                        offs = [0]
                        for k in range(rswire.n_stages):
                            offs.append(offs[-1] + rswire.stage_nbytes(k))

                        def unpack_row(row):
                            pls: list = [None] * len(plan.leaves)
                            for k in range(rswire.n_stages):
                                seg = jax.lax.slice_in_dim(
                                    row, offs[k], offs[k + 1])
                                for i, pl in zip(
                                        splan.stages[k].leaf_ids,
                                        per_device(partial(
                                            rswire.unpack_stage, k))(
                                                seg[None])):
                                    pls[i] = pl
                            return pls
                    else:
                        rswire = plan.wire_layout(cfg.wire_dtype,
                                                  direction="s2w")

                        def unpack_row(row):
                            return per_device(rswire.unpack)(row[None])

                    slot_pls = [unpack_row(ring_new[r])
                                for r in range(ring_depth)]
                    w_srv_f = plan.flatten(w_tree)

                    def rejoin_leaf(i, w):
                        lp = plan.leaves[i]
                        for r in range(ring_depth):
                            delta = per_device(vmap_n(
                                lambda q, c=lp.s2w, s=lp.slice_shape:
                                c.decompress(q, s, jnp.float32),
                                lp.meta.stack_dims))(
                                    jax.tree.map(lambda a: a[0],
                                                 slot_pls[r][i]))
                            w = jnp.where(
                                mask_bcast(rm.apply[r], w.ndim),
                                (w.astype(jnp.float32)
                                 + delta[None]).astype(w.dtype), w)
                        return jnp.where(
                            mask_bcast(rm.full, w.ndim),
                            w_srv_f[i].astype(w.dtype)[None], w)

                    w_w_tree = plan.unflatten(
                        [rejoin_leaf(i, w) for i, w in
                         enumerate(plan.flatten(state["w_w"]))])

            # ---- 2. per-worker stochastic gradients at W (no cross-worker comm)
            with phase_span(PHASE_SPANS[1], gspan):
                if resync_on:
                    # §13: each worker differentiates at its OWN model
                    # estimate W_j (stale for desynchronized workers —
                    # their commits are frozen by the §11 mask anyway)
                    w_cast = jax.tree.map(
                        lambda w, x: w.astype(x.dtype), w_w_tree,
                        state["x"])
                    losses, grads = jax.vmap(
                        grad_and_loss, in_axes=(0, 0))(w_cast, batch)
                else:
                    w_cast = jax.tree.map(
                        lambda w, x: w.astype(x.dtype), w_tree,
                        state["x"])
                    losses, grads = jax.vmap(
                        grad_and_loss, in_axes=(None, 0))(w_cast, batch)
                if faults is not None:
                    # poisoned gradient leaves (§11): NaN/Inf injected on
                    # the declared schedule — flows through momentum into
                    # the payload, where the non-finite guard demotes the
                    # worker. Losses stay clean: the injection models a
                    # corrupted backward pass, not a diverged model.
                    grads = faults.inject_grads(grads, state["step"])

            # ---- 3. momentum + EF21 per worker: R_j = C_D(M_j - G_j)
            with phase_span(PHASE_SPANS[2], gspan):
                beta = cfg.beta
                if state["m_w"] is not None:
                    m_new = jax.tree.map(
                        lambda m, g: ((1.0 - beta) * m.astype(jnp.float32)
                                      + beta * g.astype(jnp.float32)
                                      ).astype(m.dtype),
                        state["m_w"], grads)
                else:
                    m_new = jax.tree.map(
                        lambda g: g.astype(cfg.state_dtype), grads)

                gw_old = plan.flatten(state["g_w"])
                payloads, cw_l, gw_l = per_device(
                    lambda *fl: _unzip(plan.map_flat(
                        lambda lp, cw, gw, m: ef_compress_step(
                            lp.w2s, cw, gw, m, cfg.wire_dtype),
                        *fl, extra_vmap=1), 3), wspec)(
                            plan.flatten(state["cw_state"]), gw_old,
                            plan.flatten(m_new))
                sent = payloads      # as compressed, before the wire

            # ---- 4.+5. server receive + layer-wise LMO. Shared per-leaf
            # pieces first: decompress one leaf's gathered payloads, pin
            # the decompressed deltas replicated (§5: the payload buffer
            # was just all-gathered to every device — without the pin the
            # phase-5 bucket constraints propagate backward through
            # decompress and the partitioner reshards the *compressed u8
            # payloads*, splitting the fused payload all-gathers the wire
            # invariant in tests/test_sharding.py pins), fold the worker
            # mean into g_server, and the per-leaf / per-bucket LMOs.
            rep = None
            if cfg.ns_bucketing and isinstance(mesh, jax.sharding.Mesh):
                rep = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec())

            # ---- elastic participation (§11): the scheduled mask comes
            # from the step counter; the guard ANDs in per-worker payload
            # finiteness AFTER unpack (so torn wire buffers are caught
            # too). resolve_mask returns the final mask, the dynamic-
            # count fold denominator, the skip-step flag (no survivors)
            # and the demoted-by-guard count.
            sched_mask = recv   # §13 arm: same conjunction, computed
            if elastic and sched_mask is None:  # up front for the ring
                sched_mask = participation_mask(
                    cfg.participation, cfg.n_workers, state["step"],
                    cfg.participation_seed)
                if faults is not None:
                    sched_mask = sched_mask & faults.drop_mask(
                        state["step"])

            def resolve_mask(recv_payloads):
                m = sched_mask
                demoted = jnp.zeros((), jnp.int32)
                if guard:
                    finite = payload_finite_mask(recv_payloads,
                                                 cfg.n_workers)
                    demoted = jnp.sum((m & ~finite).astype(jnp.int32))
                    m = m & finite
                cnt = jnp.sum(m.astype(jnp.int32))
                return (m, jnp.maximum(cnt, 1).astype(jnp.float32),
                        cnt > 0, demoted)

            def recv_leaf(i, pl, gs, part=None):
                lp = plan.leaves[i]
                with sub_span("ef.decompress"):
                    d = per_device(vmap_n(lambda s: lp.w2s.decompress(
                        s, lp.slice_shape, jnp.float32),
                        lp.meta.stack_dims + 1))(pl)
                if rep is not None:
                    d = jax.lax.with_sharding_constraint(d, rep)
                if part is not None:
                    # mask-weighted fold over the dynamic participant
                    # count; where (not multiply) so a demoted worker's
                    # NaNs never reach the sum
                    m, denom = part[0], part[1]
                    d = jnp.where(mask_bcast(m, d.ndim), d, 0.0)
                    return (gs.astype(jnp.float32)
                            + jnp.sum(d, axis=0) / denom).astype(gs.dtype)
                return (gs.astype(jnp.float32)
                        + jnp.mean(d, axis=0)).astype(gs.dtype)

            def lmo_leaf(lp, x, g):
                d = lmo_direction(g, lp.meta.lmo, ns_steps=cfg.ns_steps,
                                  use_pallas=cfg.use_pallas)
                radius = jnp.asarray(t, jnp.float32) * lp.meta.radius_scale
                return (x.astype(jnp.float32)
                        + radius * d.astype(jnp.float32)).astype(x.dtype)

            def lmo_bucket(bi, b, gs_l, x_flat, x_l):
                g_b = b.stack([gs_l[i] for i in b.leaf_ids], mesh=mesh)
                d_b = lmo_direction_batched(
                    g_b, ns_steps=cfg.ns_steps,
                    use_pallas=cfg.use_pallas, mesh=mesh, pspec=b.pspec)
                if mset is not None:
                    # NS orthogonality residual per bucket (§10): how far
                    # the batched chain's output is from U Vᵀ — a pure
                    # read of d_b, never fed back into the update
                    m_, n_ = b.shape
                    mset.add(f"ns/orth_residual/b{bi}_{m_}x{n_}",
                             orth_residual(d_b))
                x_b = b.stack([x_flat[i] for i in b.leaf_ids],
                              dtype=jnp.float32, mesh=mesh)
                x_b = x_b + (b.radius_vector(t)[:, None, None]
                             * d_b.astype(jnp.float32))
                for i, piece in zip(b.leaf_ids, b.unstack(x_b, mesh=mesh)):
                    x_l[i] = piece.astype(x_flat[i].dtype)

            gsrv_l = plan.flatten(state["g_server"])
            x_flat = plan.flatten(state["x"])
            if pack_wire and splan is not None:
                # ---- staged wire pipeline (DESIGN.md §8): the §6 buffer
                # repartitioned into K stage sub-buffers aligned with the
                # NS buckets that consume them. All K gathers are issued
                # up front — K independent all-gather start/done pairs
                # for the latency-hiding scheduler — then each stage's
                # unpack -> decompress -> g_server fold -> batched LMO
                # consumes only its own sub-buffer, so the long NS chains
                # of the early (biggest-FLOP) stages overlap the still-
                # in-flight gathers of the later ones. Value-bit-equal to
                # the monolithic path: staging is a pure repartition.
                swire = plan.staged_wire_layout(cfg.wire_dtype, splan)
                bufs = []
                with phase_span(PHASE_SPANS[3], gspan):
                    for k in range(splan.n_stages):
                        with phase_span(wire_stage_span("w2s", k), gspan):
                            buf = reshard_payloads(per_device(
                                partial(swire.pack_stage, k), wspec)(
                                    payloads))
                            if faults is not None:
                                buf = faults.inject_wire(
                                    buf, state["step"], k, "w2s")
                            bufs.append(buf)
                gs_l: list = [None] * len(plan.leaves)
                x_l: list = [None] * len(plan.leaves)
                part = None
                staged_pl: list = [None] * len(plan.leaves)
                if elastic:
                    # the guard's per-worker demotion is a STEP-global
                    # decision, so every stage unpacks before the first
                    # fold (§11 degradation semantics: the K gathers
                    # still issue up front and keep their §8 bytes/
                    # counts, but the folds now wait on all of them —
                    # robustness trades away some overlap)
                    with phase_span(PHASE_SPANS[3], gspan):
                        for k, stage in enumerate(splan.stages):
                            for i, pl in zip(
                                    stage.leaf_ids,
                                    per_device(partial(
                                        swire.unpack_stage, k))(bufs[k])):
                                staged_pl[i] = pl
                        part = resolve_mask(staged_pl)
                for k, stage in enumerate(splan.stages):
                    with phase_span(PHASE_SPANS[3], gspan):
                        pls = ([staged_pl[i] for i in stage.leaf_ids]
                               if elastic
                               else per_device(partial(
                                   swire.unpack_stage, k))(bufs[k]))
                        for i, pl in zip(stage.leaf_ids, pls):
                            gs_l[i] = recv_leaf(i, pl, gsrv_l[i], part)
                    with phase_span(PHASE_SPANS[4], gspan):
                        for bi in stage.bucket_ids:
                            lmo_bucket(bi, buckets[bi], gs_l, x_flat, x_l)
                        for i in stage.leaf_ids:
                            if i not in bucketed:   # stage-0 eager leaves
                                lp = plan.leaves[i]
                                x_l[i] = vmap_n(partial(lmo_leaf, lp),
                                                lp.meta.stack_dims)(
                                                    x_flat[i], gs_l[i])
            else:
                # ---- monolithic phase 4: pack the whole message into
                # one contiguous uint8 buffer (repro.wire), gather it
                # across the worker axis (trainer hook == ONE fused
                # all-gather of exactly the accounted bytes), unpack
                # bit-exactly, decompress, average.
                with phase_span(PHASE_SPANS[3], gspan):
                    if pack_wire:
                        wire = plan.wire_layout(cfg.wire_dtype)
                        with phase_span(wire_stage_span("w2s", 0), gspan):
                            buf = reshard_payloads(
                                per_device(wire.pack, wspec)(payloads))
                            if faults is not None:
                                buf = faults.inject_wire(
                                    buf, state["step"], 0, "w2s")
                        payloads = per_device(wire.unpack)(buf)
                    else:
                        payloads = reshard_payloads(payloads)
                    part = resolve_mask(payloads) if elastic else None
                    gs_l = [recv_leaf(i, pl, gs, part) for i, (pl, gs)
                            in enumerate(zip(payloads, gsrv_l))]

                # ---- monolithic phase 5: layer-wise LMO on the server
                # iterate. With ns_bucketing the spectral leaves run one
                # batched Newton-Schulz chain per shape bucket (§7),
                # stacks folded into the batch dim, radii as a [B]
                # vector — bit-equal to the per-leaf path on jnp.
                with phase_span(PHASE_SPANS[4], gspan):
                    if cfg.ns_bucketing:
                        x_l = [
                            x if i in bucketed else
                            vmap_n(partial(lmo_leaf, lp),
                                   lp.meta.stack_dims)(x, g)
                            for i, (lp, x, g) in enumerate(
                                zip(plan.leaves, x_flat, gs_l))]
                        for bi, b in enumerate(buckets):
                            lmo_bucket(bi, b, gs_l, x_flat, x_l)
                    else:
                        x_l = per_device(partial(plan.map_flat, lmo_leaf))(
                            x_flat, gs_l)

            if elastic:
                # ---- §11 commit: absent/demoted workers' EF21 error
                # state (G_j), momentum and compressor sketches are
                # bitwise FROZEN (the Gluon-FL partial-participation
                # contraction argument needs exactly this); if no worker
                # survived — every payload poisoned — the whole step
                # falls back to a global skip: X and g_server do not
                # move (the fold already added exactly 0, but the LMO
                # direction of a stale g must not be walked either).
                effm, _, any_p, n_demoted = part

                def freeze(new, old):
                    return jax.tree.map(
                        lambda n, o: jnp.where(
                            mask_bcast(effm, n.ndim), n, o), new, old)

                gw_l = [freeze(n, o) for n, o in zip(gw_l, gw_old)]
                cw_l = [freeze(n, o) for n, o in
                        zip(cw_l, plan.flatten(state["cw_state"]))]
                if state["m_w"] is not None:
                    m_new = freeze(m_new, state["m_w"])
                x_l = [jnp.where(any_p, xn, xo)
                       for xn, xo in zip(x_l, x_flat)]
                gs_l = [jnp.where(any_p, gn, go)
                        for gn, go in zip(gs_l, gsrv_l)]
                if mset is not None:
                    mset.add("part/n_participants",
                             jnp.sum(effm.astype(jnp.float32)))
                    mset.add("part/demoted",
                             n_demoted.astype(jnp.float32))
                    mset.add("part/skipped_step",
                             1.0 - any_p.astype(jnp.float32))

            if mset is not None:
                # Per-leaf EF21 telemetry (§10) — pure reads of tensors
                # the phases above already hold. v = M_j - G_j is the
                # compressed target, C(v) = G_j' - G_j the decompressed
                # message, so ‖M_j - G_j'‖ is both the post-update EF21
                # error e_t and the compression residual ‖C(v) - v‖.
                m_flat = plan.flatten(m_new)
                wnew_f = (plan.flatten(w_tree)
                          if cfg.s2w != "identity" else None)
                for i, nm in enumerate(lnames):
                    err = (m_flat[i].astype(jnp.float32)
                           - gw_l[i].astype(jnp.float32))
                    v = (m_flat[i].astype(jnp.float32)
                         - gw_old[i].astype(jnp.float32))
                    mset.add(f"ef/err_norm/{nm}", worker_mean_norm(err))
                    mset.add(f"ef/rel_err/{nm}", rel_error(err, v))
                    mset.add(f"ef/momentum_norm/{nm}",
                             worker_mean_norm(m_flat[i]))
                    w2s = plan.leaves[i].w2s
                    if isinstance(getattr(w2s, "inner", w2s), TopK):
                        mset.add(f"ef/topk_ties/{nm}", topk_ties(
                            v.astype(cfg.wire_dtype), sent[i]["indices"]))
                    if wnew_f is not None:
                        # EF21-P model-estimate error ‖X - W‖ (s2w leg)
                        mset.add(f"efp/err_norm/{nm}", worker_mean_norm(
                            x_flat[i].astype(jnp.float32)
                            - wnew_f[i].astype(jnp.float32), lead=0))
                # static per-direction wire accounting (constants in the
                # graph — the sink's per-step rows stay self-describing)
                mset.add("wire/bytes_w2s", float(
                    plan.wire_layout(cfg.wire_dtype).total_nbytes))
                mset.add("wire/bytes_s2w", float(
                    plan.wire_layout(cfg.wire_dtype,
                                     direction="s2w").total_nbytes
                    if cfg.s2w != "identity" else 0.0))
                mset.add("wire/n_stages", float(
                    splan.n_stages if splan is not None else 1))
                if resync_on:
                    # §13 rejoin telemetry — pure reads of the replay
                    # mask algebra, never fed back into the update
                    mset.add("part/worker_version_lag_max",
                             rm.lag_max.astype(jnp.float32))
                    mset.add("resync/replayed",
                             rm.n_replayed.astype(jnp.float32))
                    mset.add("resync/full",
                             rm.n_full.astype(jnp.float32))

            new_state = {
                "step": state["step"] + 1,
                "x": plan.unflatten(x_l),
                "g_server": plan.unflatten(gs_l),
                "g_w": plan.unflatten(gw_l),
                "m_w": m_new if state["m_w"] is not None else None,
                "cw_state": plan.unflatten(cw_l),
            }
            if cfg.s2w != "identity":
                new_state["w"] = w_tree
                new_state["cs_state"] = cs_tree
            if resync_on:
                # §13: worker estimates, version vector and ring advance
                # even on a skipped step — the server's W advanced too,
                # and the broadcast stream must stay contiguous
                new_state["w_w"] = w_w_tree
                new_state["resync"] = {"vv": rm.vv_new, "ring": ring_new}
            aux = {"loss": jnp.mean(losses),
                   "grad_est_norm": jnp.sqrt(sum(
                       jnp.sum(jnp.square(g.astype(jnp.float32)))
                       for g in gs_l))}
            if elastic:
                aux["participation"] = part[0]
                aux["n_participants"] = jnp.sum(part[0].astype(jnp.int32))
                aux["skipped"] = ~part[2]
            if resync_on:
                aux["resync_replayed"] = rm.n_replayed
                aux["resync_full"] = rm.n_full
                aux["version_lag_max"] = rm.lag_max
            if mset is not None:
                aux["metrics"] = mset
            return new_state, aux

        return step
