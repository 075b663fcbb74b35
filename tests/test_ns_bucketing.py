"""Shape-bucketed batched Newton-Schulz (DESIGN.md §7): bucket formation,
stack/unstack exactness, bucketed-vs-per-leaf step bit-equality on the
jnp path, and the dispatch-count regression the whole refactor exists
for (ns_steps x n_buckets fused pallas_calls instead of
3 x ns_steps x n_spectral_leaves)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.core.lmo import lmo_direction, lmo_direction_batched
from repro.core.muon import EF21Muon, EF21MuonConfig, ParamMeta
from repro.dist.layerwise import LayerPlan
from repro.kernels import ref
from repro.kernels.ops import (count_ns_dispatches, newton_schulz,
                               newton_schulz_batched)
from repro.models.api import abstract_params, build_model


# --------------------------------------------------------- a small test tree

def _tiny_tree(key):
    """Hand-sized params/metas covering every bucketing case: same-shape
    group, transposed pair sharing a bucket, a stacked leaf folding into
    the batch dim, and non-spectral leaves left to the per-leaf path."""
    ks = jax.random.split(key, 6)
    params = {
        "wq": jax.random.normal(ks[0], (48, 32)),
        "wk": jax.random.normal(ks[1], (48, 32)),
        "w_in": jax.random.normal(ks[2], (32, 80)),
        "w_out": jax.random.normal(ks[3], (80, 32)),
        "blocks": jax.random.normal(ks[4], (3, 48, 32)),
        "bias": jax.random.normal(ks[5], (32,)),
    }
    metas = {
        "wq": ParamMeta("spectral", 1.0, 0),
        "wk": ParamMeta("spectral", 1.0, 0),
        "w_in": ParamMeta("spectral", 1.5, 0),
        "w_out": ParamMeta("spectral", 1.0, 0),
        "blocks": ParamMeta("spectral", 2.0, 1),
        "bias": ParamMeta("sign", 1.0, 0, compressible=False),
    }
    return params, metas


def test_bucket_formation(key):
    params, metas = _tiny_tree(key)
    plan = LayerPlan.build(params, metas, use_pallas=False)
    buckets = plan.ns_buckets()
    assert buckets is plan.ns_buckets()          # memoised
    by_shape = {b.shape: b for b in buckets}
    assert set(by_shape) == {(32, 48), (32, 80)}
    b1 = by_shape[(32, 48)]                       # canonical m <= n
    # treedef (dict-key) order: bias, blocks, w_in, w_out, wk, wq
    names = [plan.leaves[i].shape for i in b1.leaf_ids]
    assert b1.batch == 5                          # 3 (stack) + wk + wq
    assert b1.counts == (3, 1, 1)
    assert all(b1.transposes)                     # all stored [48, 32]
    assert b1.radius_scales == (2.0, 2.0, 2.0, 1.0, 1.0)
    assert names == [(3, 48, 32), (48, 32), (48, 32)]
    b2 = by_shape[(32, 80)]
    assert b2.batch == 2 and b2.transposes == (False, True)
    assert b2.radius_scales == (1.5, 1.0)
    # the sign vector is not bucketed
    bucketed = {i for b in buckets for i in b.leaf_ids}
    vector_ids = {i for i, lp in enumerate(plan.leaves)
                  if lp.meta.lmo != "spectral"}
    assert bucketed.isdisjoint(vector_ids)


def test_stack_unstack_roundtrip_exact(key):
    params, metas = _tiny_tree(key)
    plan = LayerPlan.build(params, metas, use_pallas=False)
    flat = plan.flatten(params)
    for b in plan.ns_buckets():
        stacked = b.stack([flat[i] for i in b.leaf_ids])
        assert stacked.shape == (b.batch,) + b.shape
        back = b.unstack(stacked)
        for i, piece in zip(b.leaf_ids, back):
            np.testing.assert_array_equal(np.asarray(piece),
                                          np.asarray(flat[i]))


def test_stack_mixed_dtypes_names_leaves(key):
    """Mixed leaf dtypes fail BEFORE any reshape work, and the TypeError
    names the offending leaf_ids (a trace-time phase-5 failure must point
    at leaves, not anonymous parts)."""
    params, metas = _tiny_tree(key)
    plan = LayerPlan.build(params, metas, use_pallas=False)
    b = plan.ns_buckets()[0]
    flat = plan.flatten(params)
    leaves = [flat[i] for i in b.leaf_ids]
    leaves[1] = leaves[1].astype(jnp.bfloat16)
    with pytest.raises(TypeError) as ei:
        b.stack(leaves)
    msg = str(ei.value)
    assert f"leaf {b.leaf_ids[1]}" in msg and "bfloat16" in msg
    # an explicit dtype= unifies instead of raising
    assert b.stack(leaves, dtype=jnp.float32).dtype == jnp.float32


def test_bucket_pspecs_on_mesh(key):
    """Mesh-aware buckets carry the ns_bucket_pspec — and shape groups
    sub-split by canonical TP orientation, so a transposed up/down pair
    (whose model axes land on opposite canonical dims) still runs
    model-sharded instead of replicated."""
    from test_sharding import FakeMesh

    params, metas = _tiny_tree(key)
    plan = LayerPlan.build(params, metas, use_pallas=False)
    mesh = FakeMesh(data=5, model=4)
    buckets = plan.ns_buckets(mesh=mesh)
    assert buckets is plan.ns_buckets(mesh=mesh)     # memoised per mesh
    assert plan.ns_buckets() != buckets              # and keyed off None
    by_key = {(b.shape, b.pspec): b for b in buckets}
    # (32, 48): all members transposed, model on canonical rows (48->32
    # transpose puts the divisible 32-dim first), batch 5 == data
    b1 = by_key[((32, 48), jax.sharding.PartitionSpec("data", "model",
                                                      None))]
    assert b1.batch == 5
    # (32, 80): w_in [32, 80] keeps model on cols, w_out [80, 32]
    # transposes it onto rows -> two orientation sub-buckets of batch 1
    shapes32_80 = [b for b in buckets if b.shape == (32, 80)]
    assert len(shapes32_80) == 2
    assert {b.pspec for b in shapes32_80} == {
        jax.sharding.PartitionSpec(None, "model", None),
        jax.sharding.PartitionSpec(None, None, "model")}
    # off-mesh build keeps the merged buckets (and no pspec)
    assert all(b.pspec is None for b in plan.ns_buckets())
    assert len(plan.ns_buckets()) == 2


@given(m=st.integers(4, 40), n=st.integers(4, 40), stack=st.integers(1, 3),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_stack_unstack_roundtrip_property(m, n, stack, seed):
    """stack -> unstack is the identity for arbitrary orientations and
    stack depths (transpose + reshape only, no arithmetic)."""
    k = jax.random.key(seed)
    params = {"a": jax.random.normal(k, (m, n)),
              "b": jax.random.normal(k, (n, m)),
              "s": jax.random.normal(k, (stack, m, n))}
    metas = {n_: ParamMeta("spectral", 1.0, 1 if n_ == "s" else 0)
             for n_ in params}
    plan = LayerPlan.build(params, metas, use_pallas=False)
    buckets = plan.ns_buckets()
    assert sum(b.batch for b in buckets) == stack + 2
    flat = plan.flatten(params)
    for b in buckets:
        back = b.unstack(b.stack([flat[i] for i in b.leaf_ids]))
        for i, piece in zip(b.leaf_ids, back):
            np.testing.assert_array_equal(np.asarray(piece),
                                          np.asarray(flat[i]))


# ------------------------------------------------- jnp-path bit equivalence

def test_batched_ref_bit_matches_per_slice(key):
    """newton_schulz_batched_ref == per-slice newton_schulz_ref, bitwise,
    for canonical (m <= n) stacks — the invariant the step equivalence
    rests on."""
    per_slice = jax.jit(lambda x: ref.newton_schulz_ref(x, steps=5))
    for shape in [(4, 96, 160), (3, 64, 64), (2, 13, 77)]:
        g = jax.random.normal(key, shape, jnp.float32)
        got = jax.jit(lambda x: ref.newton_schulz_batched_ref(x, steps=5))(g)
        want = jnp.stack([per_slice(g[i]) for i in range(shape[0])])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lmo_direction_batched_bit_matches_per_slice(key):
    g = jax.random.normal(key, (3, 48, 64), jnp.float32)
    got = jax.jit(lambda x: lmo_direction_batched(x, use_pallas=False))(g)
    per_slice = jax.jit(
        lambda x: lmo_direction(x, "spectral", use_pallas=False))
    want = jnp.stack([per_slice(g[i]) for i in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError):
        lmo_direction_batched(g, kind="sign")
    with pytest.raises(ValueError):
        lmo_direction_batched(g[0])


def _quadratic_grad(params, batch):
    loss = sum(jnp.sum(jnp.square(p.astype(jnp.float32) - batch))
               for p in jax.tree.leaves(params))
    grads = jax.tree.map(
        lambda p: 2.0 * (p.astype(jnp.float32) - batch), params)
    return loss, grads


def test_bucketed_step_bit_equal_per_leaf(key):
    """EF21-Muon step with ns_bucketing on == off, bit-for-bit, on the
    jnp path (the acceptance invariant: bucketing is a pure dispatch
    transformation)."""
    params, metas = _tiny_tree(key)
    batch = jnp.ones((2, 1)) * 0.1     # [n_workers, ...] broadcastable
    states = {}
    for bucketing in (True, False):
        opt = EF21Muon(EF21MuonConfig(n_workers=2, w2s="top10",
                                      ns_bucketing=bucketing))
        state = opt.init(key, params, metas)
        step = opt.make_step(metas)
        state, aux = jax.jit(
            lambda s, b: step(s, _quadratic_grad, b, 0.05))(state, batch)
        assert np.isfinite(float(aux["loss"]))
        states[bucketing] = state
    for field in ("x", "g_server", "g_w"):
        same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)),
                            states[True][field], states[False][field])
        assert all(jax.tree.leaves(same)), (field, same)


# ------------------------------------------------ dispatch-count regression

def test_step_dispatch_count_regression(key):
    """The HLO-level win, pinned at trace level: with ns_bucketing the
    step emits at most ns_steps x n_buckets NS pallas_calls; without it,
    ns_steps x n_spectral_leaves (fused per-leaf); the pre-fusion chain
    was 3 x ns_steps x n_spectral_leaves (pinned in
    test_unfused_chain_dispatch_count)."""
    params, metas = _tiny_tree(key)
    batch = jnp.ones((1, 1)) * 0.1
    counts = {}
    for bucketing in (True, False):
        opt = EF21Muon(EF21MuonConfig(n_workers=1, w2s="top10",
                                      use_pallas=True,
                                      ns_bucketing=bucketing))
        state = opt.init(key, params, metas)
        step = opt.make_step(metas)
        jaxpr = jax.make_jaxpr(
            lambda s, b: step(s, _quadratic_grad, b, 0.05))(state, batch)
        counts[bucketing] = count_ns_dispatches(jaxpr.jaxpr)
    plan = LayerPlan.build(params, metas, use_pallas=False)
    n_buckets = len(plan.ns_buckets())
    n_spectral = sum(1 for lp in plan.leaves if lp.meta.lmo == "spectral")
    ns_steps = 5
    assert counts[True] <= ns_steps * n_buckets, counts
    assert counts[False] == ns_steps * n_spectral, counts
    assert counts[True] < counts[False]


def test_unfused_chain_dispatch_count(key):
    """fused=False preserves the pre-fusion 3-calls-per-iteration chain
    (the A/B baseline the ISSUE counts against)."""
    g = jnp.zeros((96, 160))
    for fused, expect in ((False, 3 * 5), ("auto", 5)):
        jaxpr = jax.make_jaxpr(lambda x: newton_schulz(
            x, steps=5, use_pallas=True, fused=fused))(g)
        assert count_ns_dispatches(jaxpr.jaxpr) == expect, fused
    jaxpr = jax.make_jaxpr(lambda x: newton_schulz_batched(
        x, steps=5, use_pallas=True))(jnp.zeros((7, 96, 160)))
    assert count_ns_dispatches(jaxpr.jaxpr) == 5   # batch rides the grid


def test_infeasible_gram_falls_back_to_chain(key):
    """Slices whose [m, m] gram exceeds the fused VMEM budget fall back
    to the three-call chain instead of a miscompiled kernel."""
    from repro.kernels.newton_schulz import fused_ns_feasible
    assert fused_ns_feasible(768, 128, 4)
    assert not fused_ns_feasible(4096, 128, 4)
    g = jnp.zeros((4096, 4224))
    jaxpr = jax.make_jaxpr(lambda x: newton_schulz(
        x, steps=2, use_pallas=True))(g)
    assert count_ns_dispatches(jaxpr.jaxpr) == 3 * 2


@pytest.mark.slow
def test_nanogpt_step_dispatch_count():
    """Acceptance pin on the paper's model: a traced nanogpt-124m step
    with ns_bucketing emits at most ns_steps x n_buckets NS kernels
    (benchmarks/ns_bench.py records the same numbers in BENCH_ns.json)."""
    cfg = get_config("nanogpt-124m")
    model = build_model(cfg)
    shapes, metas = abstract_params(model)
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    opt = EF21Muon(EF21MuonConfig(n_workers=1, w2s="top10",
                                  use_pallas=True, ns_bucketing=True))
    state = opt.init(jax.random.key(0), params, metas)
    step = opt.make_step(metas)

    def gl(p, batch):
        return jax.value_and_grad(lambda q: model.loss(q, batch))(p)

    batch = {"tokens": jnp.zeros((1, 1, 16), jnp.int32),
             "labels": jnp.zeros((1, 1, 16), jnp.int32)}
    jaxpr = jax.make_jaxpr(lambda s, b: step(s, gl, b, 0.01))(state, batch)
    plan = opt.plan(params, metas)
    n_buckets = len(plan.ns_buckets())
    assert count_ns_dispatches(jaxpr.jaxpr) <= 5 * n_buckets


@pytest.mark.slow
def test_spmd_bucketing_ab_flop_ratio_and_equality():
    """The sharding-awareness acceptance, on a real 8-host-device mesh
    with zero1_lmo=True (subprocess; benchmarks/ns_bench.py runs the
    same A/B in the slow CI job):

      * bucketing-on / bucketing-off per-device HLO FLOPs <= 1.02x
        (the bucket concat used to drop per-leaf TP/zero-1 shardings and
        replicate the NS chain: +13.7% on the 512-chip granite dry-run);
      * the staged wire invariant (§8) holds: the staged arm lowers
        exactly K u8 payload all-gathers (K = pipeline stages), the
        monolithic and per-leaf arms exactly one, all measuring bytes
        == the WireLayout account byte-for-byte;
      * the staged arm's overlap-aware exposed-collective time is
        strictly below the monolithic arm's, and staged == monolithic
        stays bit-equal (a pure repartition) even under TP;
      * bucketed == per-leaf stays BIT-equal on the jnp path on the
        (8, 1) mesh, where sharding only ever slices batch/stack dims
        (on the (4, 2) mesh TP splits NS contractions, so cross-arm
        agreement is reduction-order-limited: ulp-level);
      * the shard_map-wrapped fused Pallas iteration matches the oracle
        on per-device sub-batches."""
    from benchmarks.ns_bench import (NS_SPMD_RATIO_BOUND,
                                     PIPELINE_EXPOSED_BOUND, spmd_ab)

    rec = spmd_ab()
    assert rec["ns_flops_ratio"] <= NS_SPMD_RATIO_BOUND, rec
    assert rec["n_stages_on"] > 1, rec
    assert rec["u8_count_on"] == rec["n_stages_on"], rec
    assert rec["u8_count_off"] == 1 and rec["u8_count_mono"] == 1, rec
    assert rec["u8_bytes_on"] == rec["u8_bytes_off"] \
        == rec["u8_bytes_mono"] == rec["wire_bytes"], rec
    assert rec["exposed_ratio"] is not None \
        and rec["exposed_ratio"] <= PIPELINE_EXPOSED_BOUND, rec
    assert rec["bit_equal_staged_mono"], rec
    assert rec["bit_equal_8x1"], rec
    assert rec["x_max_abs_diff_4x2"] < 1e-6, rec
    assert rec["shard_map_max_err"] < 2e-3, rec


# ------------------------------------------------------ padding exactness

@given(bsz=st.integers(1, 3), m=st.integers(3, 140), n=st.integers(3, 140),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=6, deadline=None)
def test_bucketed_padding_exactness_property(bsz, m, n, seed):
    """Pallas (interpret) batched NS on zero-padded non-multiple-of-128
    stacks matches the unpadded batched oracle — padding is exact through
    the fused iteration, any shape."""
    g = jax.random.normal(jax.random.key(seed), (bsz, m, n), jnp.float32)
    got = newton_schulz_batched(g, steps=3, use_pallas=True, interpret=True)
    want = ref.newton_schulz_batched_ref(g, steps=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------ LRU plan cache

def test_plan_cache_keyed_on_leaf_dtypes(key):
    """Regression: the LRU key carried (treedef, shapes, metas) but not
    leaf dtypes, so switching param dtype silently reused a stale
    LayerPlan (and its memoised wire layouts / ns buckets)."""
    opt = EF21Muon(EF21MuonConfig())
    meta = {"w": ParamMeta("spectral", 1.0, 0)}
    p32 = {"w": jnp.zeros((8, 8), jnp.float32)}
    pbf = {"w": jnp.zeros((8, 8), jnp.bfloat16)}
    plan32 = opt.plan(p32, meta)
    planbf = opt.plan(pbf, meta)
    assert planbf is not plan32
    assert len(opt._plans) == 2
    # both keys stay live and identity-stable
    assert opt.plan(p32, meta) is plan32
    assert opt.plan(pbf, meta) is planbf


def test_plan_cache_lru_eviction(key):
    """Shape sweeps evict the oldest plan only — the 8 most recent stay
    live (was: wholesale clear())."""
    opt = EF21Muon(EF21MuonConfig())
    meta = ParamMeta("spectral", 1.0, 0)
    plans = []
    for i in range(9):
        p = {"w": jnp.zeros((8 + i, 8))}
        plans.append(opt.plan(p, {"w": meta}))
    assert len(opt._plans) == 8
    # 0 evicted, 1..8 still cached (identity-stable)
    assert opt.plan({"w": jnp.zeros((9, 8))}, {"w": meta}) is plans[1]
    assert opt.plan({"w": jnp.zeros((16, 8))}, {"w": meta}) is plans[8]
    new0 = opt.plan({"w": jnp.zeros((8, 8))}, {"w": meta})
    assert new0 is not plans[0]
    # the new0 insert evicted 2; cache now holds (oldest first):
    # 3, 4, 5, 6, 7, 1, 8, 0'. A hit refreshes recency: touch 3 (the
    # next eviction candidate) — the next insert then evicts 4, not 3.
    assert opt.plan({"w": jnp.zeros((11, 8))}, {"w": meta}) is plans[3]
    opt.plan({"w": jnp.zeros((99, 8))}, {"w": meta})   # evicts 4
    assert opt.plan({"w": jnp.zeros((11, 8))}, {"w": meta}) is plans[3]
    assert opt.plan({"w": jnp.zeros((12, 8))}, {"w": meta}) is not plans[4]
