"""Staged wire pipeline (DESIGN.md §8): stage assignment over the NS
buckets, the byte-exact repartition of the wire buffer into per-stage
sub-buffers, bit-exact per-stage pack/unpack (hypothesis-swept incl.
odd shapes and stacked leaves), and staged-vs-monolithic step
bit-equality on the jnp path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.muon import EF21Muon, EF21MuonConfig, ParamMeta
from repro.dist.layerwise import LayerPlan
from repro.dist.pipeline import (bucket_ns_flops, build_stage_plan,
                                 s2w_issue_order)
from repro.wire.layout import build_staged_layout


def _tree(key):
    """Eager (sign) leaves + three NS buckets of different FLOP weight:
    (32, 48) batch 5, (32, 80) batch 2, (16, 16) batch 1."""
    ks = jax.random.split(key, 7)
    params = {
        "wq": jax.random.normal(ks[0], (48, 32)),
        "wk": jax.random.normal(ks[1], (48, 32)),
        "w_in": jax.random.normal(ks[2], (32, 80)),
        "w_out": jax.random.normal(ks[3], (80, 32)),
        "blocks": jax.random.normal(ks[4], (3, 48, 32)),
        "tiny": jax.random.normal(ks[5], (16, 16)),
        "bias": jax.random.normal(ks[6], (32,)),
    }
    metas = {
        "wq": ParamMeta("spectral", 1.0, 0),
        "wk": ParamMeta("spectral", 1.0, 0),
        "w_in": ParamMeta("spectral", 1.5, 0),
        "w_out": ParamMeta("spectral", 1.0, 0),
        "blocks": ParamMeta("spectral", 2.0, 1),
        "tiny": ParamMeta("spectral", 1.0, 0),
        "bias": ParamMeta("sign", 1.0, 0, compressible=False),
    }
    return params, metas


# ------------------------------------------------------- stage assignment

def test_stage_plan_partitions_leaves(key):
    params, metas = _tree(key)
    plan = LayerPlan.build(params, metas, w2s="top10",
                           use_pallas=False)
    sp = plan.stage_plan()
    assert sp is plan.stage_plan()                       # memoised
    # every leaf in exactly one stage
    all_ids = sorted(i for s in sp.stages for i in s.leaf_ids)
    assert all_ids == list(range(len(plan.leaves)))
    # stage 0 is the eager chunk: exactly the non-bucketed leaves
    buckets = plan.ns_buckets()
    bucketed = {i for b in buckets for i in b.leaf_ids}
    assert set(sp.stages[0].leaf_ids) == \
        set(range(len(plan.leaves))) - bucketed
    assert sp.stages[0].bucket_ids == ()
    assert sp.eager_leaf_ids == sp.stages[0].leaf_ids
    # one stage per bucket, descending by NS FLOPs
    assert sp.n_stages == 1 + len(buckets)
    flops = [s.ns_flops for s in sp.stages[1:]]
    assert flops == sorted(flops, reverse=True)
    for s in sp.stages[1:]:
        (bi,) = s.bucket_ids
        assert sorted(buckets[bi].leaf_ids) == list(s.leaf_ids)
        assert s.ns_flops == bucket_ns_flops(buckets[bi])


def test_stage_plan_cap_merges_smallest_tail(key):
    params, metas = _tree(key)
    plan = LayerPlan.build(params, metas, w2s="top10",
                           use_pallas=False)
    auto = plan.stage_plan()
    assert auto.n_stages == 4          # eager + 3 buckets
    capped = plan.stage_plan(wire_stages=3)
    assert capped.n_stages == 3
    # head stages unchanged, tail merged (smallest-FLOP buckets last)
    assert capped.stages[:2] == auto.stages[:2]
    merged = capped.stages[2]
    assert set(merged.leaf_ids) == set(auto.stages[2].leaf_ids) \
        | set(auto.stages[3].leaf_ids)
    assert merged.ns_flops == auto.stages[2].ns_flops \
        + auto.stages[3].ns_flops
    # cap below the floor: everything in one stage; cap above: auto
    assert plan.stage_plan(wire_stages=1).n_stages == 1
    assert plan.stage_plan(wire_stages=99).stages == auto.stages
    with pytest.raises(ValueError):
        build_stage_plan(plan, plan.ns_buckets(), wire_stages=0)


def test_s2w_issue_order_descending_receive_work(key):
    """The s2w broadcast issue order (§9): a deterministic permutation of
    the stage indices, descending by per-stage receive work (leaf element
    counts — the decompress+apply chain each broadcast must hide), NOT by
    the NS FLOPs that ordered the w2s stages."""
    params, metas = _tree(key)
    plan = LayerPlan.build(params, metas, w2s="top10", s2w="natural",
                           use_pallas=False)
    sp = plan.stage_plan()
    order = s2w_issue_order(plan, sp)
    assert sorted(order) == list(range(sp.n_stages))
    assert order == s2w_issue_order(plan, sp)            # deterministic

    def work(k):
        return sum(np.prod(plan.leaves[i].shape) for i in sp.stages[k].leaf_ids)

    works = [work(k) for k in order]
    assert works == sorted(works, reverse=True)
    # ties break on stage index (stable ascending within equal work)
    for a, b in zip(order, order[1:]):
        if work(a) == work(b):
            assert a < b
    # the ordering is a schedule, not a repartition: every leaf still
    # appears exactly once across the ordered stages
    all_ids = sorted(i for k in order for i in sp.stages[k].leaf_ids)
    assert all_ids == list(range(len(plan.leaves)))


def test_stage_plan_no_buckets_is_single_stage(key):
    params = {"v": jax.random.normal(key, (8,))}
    metas = {"v": ParamMeta("sign", 1.0, 0)}
    plan = LayerPlan.build(params, metas, w2s="top10",
                           use_pallas=False)
    sp = plan.stage_plan()
    assert sp.n_stages == 1 and sp.stages[0].leaf_ids == (0,)


# ------------------------------------------- staged layout: byte repartition

def test_staged_layout_byte_exact_repartition(key):
    params, metas = _tree(key)
    plan = LayerPlan.build(params, metas, w2s="top10+natural",
                           use_pallas=False)
    sp = plan.stage_plan()
    layout = plan.wire_layout(jnp.bfloat16)
    staged = plan.staged_wire_layout(jnp.bfloat16, sp)
    assert staged is plan.staged_wire_layout(jnp.bfloat16, sp)  # memoised
    assert staged.base is layout
    assert staged.n_stages == sp.n_stages
    # stage bytes sum byte-for-byte to the monolithic buffer (the
    # relaxed K-gather wire invariant)
    assert sum(staged.stage_nbytes(k) for k in range(staged.n_stages)) \
        == layout.total_nbytes
    # per stage: offsets contiguous, per-leaf byte layout preserved
    for k, ids in enumerate(staged.stage_leaf_ids):
        pos = 0
        for spec, i in zip(staged.stages[k].specs, ids):
            base = layout.specs[i]
            assert spec.offset == pos
            pos += spec.region_nbytes
            assert (spec.slice_nbytes, spec.stack_shape, spec.codecs) == \
                (base.slice_nbytes, base.stack_shape, base.codecs)
        assert pos == staged.stages[k].total_nbytes
    # a non-partition is rejected
    with pytest.raises(ValueError):
        build_staged_layout(layout, ((0, 1), (1, 2)))


def _payloads_for(plan, key, n_workers=2, direction="w2s"):
    """Real per-leaf payload trees with [n_workers, *stack] leading dims,
    exactly as phase 3 (w2s) / phase 1 (s2w, lead dim 1) produces them."""
    out = []
    for j, lp in enumerate(plan.leaves):
        comp = getattr(lp, direction)
        wire = jnp.dtype(jnp.bfloat16)
        in_dtype = (jnp.float32
                    if getattr(comp, "lossless_wire", False) else wire)

        def one(k, c=comp, s=lp.slice_shape, d=in_dtype):
            x = jax.random.normal(k, s, jnp.float32).astype(d)
            payload, _ = c.compress(c.init(k, s, jnp.dtype(jnp.bfloat16)), x)
            return payload

        keys = jax.random.split(jax.random.fold_in(key, j),
                                n_workers * lp.n_stack).reshape(
                                    (n_workers,) + lp.stack_shape)
        fn = one
        for _ in range(lp.meta.stack_dims + 1):
            fn = jax.vmap(fn)
        out.append(fn(keys))
    return out


def test_staged_pack_unpack_roundtrip_bitexact(key):
    params, metas = _tree(key)
    plan = LayerPlan.build(params, metas, w2s="top10+natural",
                           use_pallas=False)
    staged = plan.staged_wire_layout(jnp.bfloat16, plan.stage_plan())
    payloads = _payloads_for(plan, key)
    for k, ids in enumerate(staged.stage_leaf_ids):
        buf = staged.pack_stage(k, payloads)
        assert buf.dtype == jnp.uint8
        assert buf.shape == (2, staged.stage_nbytes(k))
        got = staged.unpack_stage(k, buf)
        for i, g in zip(ids, got):
            la, ta = jax.tree.flatten(g)
            lb, tb = jax.tree.flatten(payloads[i])
            assert ta == tb
            for x, y in zip(la, lb):
                assert x.dtype == y.dtype
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@given(name=st.sampled_from(["top10+natural", "top10", "natural",
                             "identity", "identity+natural"]),
       direction=st.sampled_from(["w2s", "s2w"]),
       L=st.integers(1, 3), m=st.integers(3, 17), n=st.integers(3, 17),
       stages=st.sampled_from(["auto", 1, 2]), seed=st.integers(0, 2 ** 16))
@settings(max_examples=20, deadline=None)
def test_staged_roundtrip_property(name, direction, L, m, n, stages, seed):
    """Hypothesis: per-stage pack -> unpack is the identity bit-for-bit
    for arbitrary odd shapes, stacked leaves and stage caps, in BOTH wire
    directions (the s2w leg reuses the same leaf partition, §9), and the
    stage bytes always repartition the base buffer exactly."""
    key = jax.random.key(seed)
    params = {"w": jax.ShapeDtypeStruct((m, n), jnp.float32),
              "s": jax.ShapeDtypeStruct((L, n, m), jnp.float32),
              "v": jax.ShapeDtypeStruct((m,), jnp.float32)}
    metas = {"w": ParamMeta("spectral", 1.0, 0),
             "s": ParamMeta("spectral", 1.0, 1),
             "v": ParamMeta("sign", 1.0, 0, compressible=False)}
    plan = LayerPlan.build(params, metas, w2s=name, s2w=name,
                           use_pallas=False)
    staged = plan.staged_wire_layout(
        jnp.bfloat16, plan.stage_plan(wire_stages=stages),
        direction=direction)
    assert staged.direction == direction
    assert sum(staged.stage_nbytes(k) for k in range(staged.n_stages)) \
        == plan.wire_layout(jnp.bfloat16, direction=direction).total_nbytes
    payloads = _payloads_for(plan, key, n_workers=1, direction=direction)
    for k, ids in enumerate(staged.stage_leaf_ids):
        got = staged.unpack_stage(k, staged.pack_stage(k, payloads))
        for i, g in zip(ids, got):
            la, _ = jax.tree.flatten(g)
            lb, _ = jax.tree.flatten(payloads[i])
            for x, y in zip(la, lb):
                assert x.dtype == y.dtype
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -------------------------------------------------- staged step equivalence

def _quadratic_grad(params, batch):
    loss = sum(jnp.sum(jnp.square(p.astype(jnp.float32) - batch))
               for p in jax.tree.leaves(params))
    grads = jax.tree.map(
        lambda p: 2.0 * (p.astype(jnp.float32) - batch), params)
    return loss, grads


def _run_steps(params, metas, key, wire_stages, n=3, **cfg_kw):
    opt = EF21Muon(EF21MuonConfig(n_workers=2, beta=0.5,
                                  w2s="top10+natural", s2w="natural",
                                  use_pallas=False,
                                  wire_stages=wire_stages, **cfg_kw))
    state = opt.init(key, params, metas)
    fn = opt.make_step(metas, reshard_payloads=lambda t: t)
    step = jax.jit(lambda s, b, t, f=fn: f(s, _quadratic_grad, b, t))
    for _ in range(n):
        state, aux = step(state, jnp.ones((2, 1)) * 0.1, 0.01)
    assert np.isfinite(float(aux["loss"]))
    return state


def test_staged_step_bit_equal_monolithic(key):
    """The §8 acceptance invariant on the jnp path: the staged step
    (auto and a capped stage count) is value-bit-equal to the
    wire_stages=1 monolithic step — staging is a pure repartition."""
    params, metas = _tree(key)
    mono = _run_steps(params, metas, key, wire_stages=1)
    for ws in ("auto", 2):
        staged = _run_steps(params, metas, key, wire_stages=ws)
        same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)),
                            staged, mono)
        assert all(jax.tree.leaves(same)), (ws, same)


def test_staged_collapses_without_bucketing(key):
    """ns_bucketing=False leaves no buckets to stage against: the step
    must fall back to the monolithic single-buffer path (bit-equal)."""
    params, metas = _tree(key)
    a = _run_steps(params, metas, key, wire_stages="auto",
                   ns_bucketing=False)
    b = _run_steps(params, metas, key, wire_stages=1, ns_bucketing=False)
    same = jax.tree.map(lambda x, y: bool(jnp.all(x == y)), a, b)
    assert all(jax.tree.leaves(same))


def test_s2w_wire_leg_bit_equal_off_arm(key):
    """The §9 A/B switch: routing the EF21-P model update through the
    staged s2w wire buffers (wire_pack_s2w auto-engages here — the hook
    is set and wire_pack is on) is value-bit-equal to the unpacked
    phase-1 path, for both the staged and the monolithic schedule."""
    params, metas = _tree(key)
    for ws in ("auto", 1):
        on = _run_steps(params, metas, key, wire_stages=ws)
        off = _run_steps(params, metas, key, wire_stages=ws,
                         wire_pack_s2w=False)
        same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)), on, off)
        assert all(jax.tree.leaves(same)), (ws, same)
