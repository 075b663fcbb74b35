"""repro.wire: bit-exact pack/unpack for every codec, fused-buffer
layout invariants, the lossless_wire capability flag, and a checkpoint
round-trip of full EF21 state with wire-format compressors enabled."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
# property tests skip individually when hypothesis is absent; the
# plain oracle tests in this file still run (see _hypothesis_compat)
from _hypothesis_compat import given, settings, st

from repro.core import compressors as C
from repro.core.error_feedback import apply_payload, ef_compress_step
from repro.core.muon import EF21Muon, EF21MuonConfig, ParamMeta
from repro.dist.layerwise import LayerPlan
from repro.wire.codecs import NarrowIntCodec, RawCodec, index_domains


def _single_leaf_layout(name, shape, stack_dims=0, lmo="spectral",
                        direction="w2s"):
    params = {"p": jax.ShapeDtypeStruct(shape, jnp.float32)}
    metas = {"p": ParamMeta(lmo, 1.0, stack_dims)}
    plan = LayerPlan.build(params, metas, **{direction: name},
                           use_pallas=False)
    return plan, plan.wire_layout(jnp.bfloat16, direction=direction)


def _tree_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype, (x.dtype, y.dtype)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _payload_for(comp, shape, key):
    wire = jnp.dtype(jnp.bfloat16)
    in_dtype = (jnp.float32 if getattr(comp, "lossless_wire", False)
                else wire)
    x = jax.random.normal(key, shape, jnp.float32).astype(in_dtype)
    state = comp.init(key, shape, wire)
    payload, _ = comp.compress(state, x)
    return payload


@given(name=st.sampled_from(sorted(C.REGISTRY)),
       m=st.integers(3, 33), n=st.integers(3, 33),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=25, deadline=None)
def test_every_codec_roundtrips_bitexact(name, m, n, seed):
    """Hypothesis: pack -> unpack is the identity, bit-for-bit, for every
    registry compressor on arbitrary (odd, tail-padding-forcing) shapes."""
    key = jax.random.key(seed)
    plan, layout = _single_leaf_layout(name, (m, n))
    comp = plan.leaves[0].w2s
    payload = jax.tree.map(lambda a: a[None],            # worker dim of 1
                           _payload_for(comp, (m, n), key))
    buf = layout.pack([payload])
    assert buf.dtype == jnp.uint8
    assert buf.shape == (1, layout.total_nbytes)
    _tree_equal(layout.unpack(buf)[0], payload)


@given(name=st.sampled_from(["top10+natural", "natural", "top10",
                             "identity"]),
       L=st.integers(1, 4), m=st.integers(3, 17), n=st.integers(3, 17),
       W=st.integers(1, 3), seed=st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_stacked_leaf_roundtrips_bitexact(name, L, m, n, W, seed):
    """Same invariant on stacked leaves [W, L, m, n] — the codecs are
    vmapped over the worker and stack dims exactly as the step does."""
    key = jax.random.key(seed)
    plan, layout = _single_leaf_layout(name, (L, m, n), stack_dims=1)
    comp = plan.leaves[0].w2s
    keys = jax.random.split(key, W * L).reshape(W, L)
    payload = jax.vmap(jax.vmap(
        lambda k: _payload_for(comp, (m, n), k)))(keys)
    buf = layout.pack([payload])
    assert buf.shape == (W, layout.total_nbytes)
    _tree_equal(layout.unpack(buf)[0], payload)


@given(name=st.sampled_from(sorted(C.REGISTRY) + ["identity+natural"]),
       stacked=st.booleans(), L=st.integers(1, 3),
       m=st.integers(3, 33), n=st.integers(3, 33),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=25, deadline=None)
def test_s2w_direction_roundtrips_bitexact(name, stacked, L, m, n, seed):
    """Hypothesis (§9): the s2w wire leg round-trips bit-exactly for
    every registry compressor (plus identity+natural, the quantised
    Identity wrapper) on arbitrary odd shapes and stacked leaves — the
    model-update broadcast buffer carries a lead dim of 1, not
    n_workers, and the layout records its direction."""
    key = jax.random.key(seed)
    shape = (L, m, n) if stacked else (m, n)
    plan, layout = _single_leaf_layout(name, shape,
                                       stack_dims=int(stacked),
                                       direction="s2w")
    assert layout.direction == "s2w"
    comp = plan.leaves[0].s2w
    if stacked:
        keys = jax.random.split(key, L).reshape(1, L)
        payload = jax.vmap(jax.vmap(
            lambda k: _payload_for(comp, (m, n), k)))(keys)
    else:
        payload = jax.tree.map(lambda a: a[None],     # server lead dim 1
                               _payload_for(comp, (m, n), key))
    buf = layout.pack([payload])
    assert buf.dtype == jnp.uint8
    assert buf.shape == (1, layout.total_nbytes)
    _tree_equal(layout.unpack(buf)[0], payload)


@pytest.mark.parametrize("name", sorted(C.REGISTRY))
def test_registry_codec_roundtrip_fixed_odd_shape(name, key):
    """Non-hypothesis floor: every registry compressor round-trips
    bit-exactly on one odd shape (tail padding in signs and indices)."""
    shape = (13, 21)
    plan, layout = _single_leaf_layout(name, shape)
    comp = plan.leaves[0].w2s
    payload = jax.tree.map(lambda a: a[None],
                           _payload_for(comp, shape, key))
    buf = layout.pack([payload])
    assert buf.shape == (1, layout.total_nbytes)
    _tree_equal(layout.unpack(buf)[0], payload)


def test_layout_offset_table_is_static_and_contiguous():
    params = {"w": jnp.zeros((3, 16, 24)), "v": jnp.zeros((40,)),
              "e": jnp.zeros((64, 1024))}
    metas = {"w": ParamMeta("spectral", 1.0, 1),
             "v": ParamMeta("sign", 1.0, 0, compressible=False),
             "e": ParamMeta("sign", 1.0, 0)}
    plan = LayerPlan.build(params, metas, w2s="top10+natural",
                           use_pallas=False)
    layout = plan.wire_layout(jnp.bfloat16)
    assert plan.wire_layout(jnp.bfloat16) is layout       # memoised
    pos = 0
    for spec in layout.specs:
        assert spec.offset == pos                         # contiguous
        pos += spec.region_nbytes
    assert pos == layout.total_nbytes
    # incompressible leaf ships the exact f32 diff (lossless identity)
    table = layout.describe()
    byleaf = {r["codec"]: r for r in table}
    assert "identity[raw:float32]" in byleaf
    # 64*1024 = 65536 elements -> u16 indices still suffice
    assert any(r["codec"].startswith("top10%+natural[u16") for r in table)
    # eval_shape over pack agrees with the offset table, no allocation
    structs = layout.payload_structs(n_workers=2)
    out = jax.eval_shape(layout.pack, structs)
    assert out.shape == (2, layout.total_nbytes) and out.dtype == jnp.uint8


def test_narrow_width_selection_per_domain():
    from repro.kernels.bitpack import narrow_width
    assert narrow_width(1 << 16) == 2
    assert narrow_width((1 << 16) + 1) == 3
    assert narrow_width(1 << 24) == 3
    assert narrow_width((1 << 24) + 1) == 4
    # a wide-domain TopK leaf falls back to raw int32 indices
    plan, layout = _single_leaf_layout("top10", (1 << 12, 1 << 13))
    (spec,) = layout.specs
    assert any(isinstance(c, RawCodec) and c.dtype == "int32"
               for c in spec.codecs)
    assert not any(isinstance(c, NarrowIntCodec) for c in spec.codecs)


def test_index_domains_column_topk():
    assert index_domains(C.ColumnTopK(0.1), (128, 300)) == {"indices": 300}
    assert index_domains(
        C.WithNatural(C.TopK(0.1, use_pallas=False), use_pallas=False), (16, 8)) == \
        {"indices": 128}
    assert index_domains(C.Natural(use_pallas=False), (16, 8)) == {}


def test_packed_step_equals_unpacked_step_bitexact(key):
    """The whole point: routing phase 4 through the wire buffer changes
    nothing — packed and unpacked steps produce bit-identical states."""
    params = {"w": jnp.zeros((3, 12, 16)), "v": jnp.zeros((24,))}
    metas = {"w": ParamMeta("spectral", 1.0, 1),
             "v": ParamMeta("sign", 1.0, 0, compressible=False)}
    T = jax.tree.map(lambda p: jax.random.normal(
        jax.random.fold_in(key, 3), p.shape), params)

    def gal(p, b):
        loss = sum(jnp.sum((x - t) ** 2) for x, t in
                   zip(jax.tree.leaves(p), jax.tree.leaves(T)))
        return loss, jax.tree.map(lambda x, t: 2 * (x - t), p, T)

    states = {}
    for packed in (True, False):
        opt = EF21Muon(EF21MuonConfig(
            n_workers=2, beta=0.5, w2s="top10+natural", s2w="natural",
            use_pallas=False, wire_pack=packed))
        state = opt.init(key, params, metas)
        # explicit hook: packing only engages around a reshard boundary
        fn = opt.make_step(metas, reshard_payloads=lambda t: t)
        step = jax.jit(lambda s, b, t, f=fn: f(s, gal, b, t))
        for i in range(3):
            state, _ = step(state, jnp.zeros((2, 1)), 0.01)
        states[packed] = state
    _tree_equal(states[True], states[False])


def test_wire_bytes_bookkeeping_matches_layout(key):
    opt = EF21Muon(EF21MuonConfig(n_workers=2, w2s="top10+natural"))
    params = {"w": jnp.zeros((8, 16, 32))}
    metas = {"w": ParamMeta("spectral", 1.0, 1)}
    wire = opt.wire_bytes_per_worker(params, metas)
    analytic = opt.w2s_bytes_per_worker(params, metas)
    assert wire == opt.plan(params, metas).wire_layout(
        jnp.bfloat16).total_nbytes
    # narrow indices put the wire at or below the 4-byte-index account
    assert 0 < wire <= analytic


# ------------------------------------------------- lossless_wire satellite

def test_identity_subclass_stays_lossless(key):
    """The capability flag (not a type-name check) drives the EF wire
    dtype: an Identity subclass must keep the exact f32 path."""
    class LoggedIdentity(C.Identity):
        pass

    comp = LoggedIdentity()
    assert comp.lossless_wire
    target = jax.random.normal(key, (9, 9)) * 1e-3
    payload, _, est = ef_compress_step(comp, {}, jnp.zeros((9, 9)), target)
    assert payload.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(est), np.asarray(target))


def test_with_natural_identity_end_to_end(key):
    """WithNatural(Identity): compress/decompress/payload_bytes agree
    (satellite: the payload_bytes Identity branch is now reachable)."""
    comp = C.get_compressor("identity+natural", use_pallas=False)
    assert isinstance(comp.inner, C.Identity)
    assert not comp.lossless_wire                  # the wrapper quantises
    shape = (13, 21)
    n = 13 * 21
    assert comp.payload_bytes(shape, jnp.bfloat16) == n + (n + 7) // 8
    x = jax.random.normal(key, shape).astype(jnp.bfloat16)
    payload, _ = comp.compress({}, x)
    assert set(payload) == {"codes", "signs"}
    xh = comp.decompress(payload, shape, jnp.float32)
    # natural semantics: relative error <= 1/3 elementwise
    xb = np.asarray(x, np.float32)
    rel = np.abs(np.asarray(xh) - xb) / np.maximum(np.abs(xb), 1e-30)
    assert rel.max() <= 1 / 3 + 1e-2
    # EF sender/receiver invariant holds through the wrapper
    est_s = jnp.zeros(shape)
    est_r = jnp.zeros(shape)
    payload, _, est_s = ef_compress_step(comp, {}, est_s, x.astype(jnp.float32))
    est_r = apply_payload(comp, payload, est_r)
    np.testing.assert_array_equal(np.asarray(est_s), np.asarray(est_r))


# -------------------------------------------- checkpoint round-trip (EF21)

def test_checkpoint_roundtrip_with_wire_compressors(tmp_path, key):
    """Full EF21 state (momentum, per-worker estimates, compressor state,
    EF21-P model estimates) survives a save/load round-trip bit-exactly
    with wire-format compressors on both directions, and training
    continues identically from the restored state."""
    import os

    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.data import SyntheticLM
    from repro.models.api import build_model
    from repro.train.checkpoint import load_checkpoint, save_checkpoint
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config("granite-3-2b").reduced()
    model = build_model(cfg)
    tr = Trainer(model, TrainerConfig(n_workers=2, beta=0.5,
                                      w2s="top10+natural", s2w="natural",
                                      remat=False, use_pallas=False))
    data = SyntheticLM(cfg, ShapeSpec("t", "train", 32, 4), n_workers=2,
                       seed=0)
    state = tr.init(key)
    step = jax.jit(tr.make_step())
    state, _ = step(state, data.batch_at(0), 0.01)
    path = os.path.join(tmp_path, "ef21_wire.npz")
    save_checkpoint(path, state, step=1)
    state2, at = load_checkpoint(path, state)
    assert at == 1
    _tree_equal(state, state2)
    a, _ = step(state, data.batch_at(1), 0.01)
    b, _ = step(state2, data.batch_at(1), 0.01)
    _tree_equal(a, b)


def test_checkpoint_roundtrip_with_s2w_wire_engaged(tmp_path, key):
    """Satellite of §9: with the s2w wire leg actually ENGAGED (reshard
    hooks set, so phase 1 runs pack -> broadcast -> unpack ->
    apply_payload), the EF21-P state pair (cs_state, w) survives a
    save/load round-trip bit-exactly and training continues identically
    — the wire bytes ARE the recurrence, so a restored server must
    replay it bit-for-bit."""
    import os

    from repro.train.checkpoint import load_checkpoint, save_checkpoint

    params = {"w": jnp.zeros((3, 12, 16)), "v": jnp.zeros((24,))}
    metas = {"w": ParamMeta("spectral", 1.0, 1),
             "v": ParamMeta("sign", 1.0, 0, compressible=False)}
    T = jax.tree.map(lambda p: jax.random.normal(
        jax.random.fold_in(key, 3), p.shape), params)

    def gal(p, b):
        loss = sum(jnp.sum((x - t) ** 2) for x, t in
                   zip(jax.tree.leaves(p), jax.tree.leaves(T)))
        return loss, jax.tree.map(lambda x, t: 2 * (x - t), p, T)

    opt = EF21Muon(EF21MuonConfig(
        n_workers=2, beta=0.5, w2s="top10+natural", s2w="natural",
        use_pallas=False))
    state = opt.init(key, params, metas)
    assert state["cs_state"] is not None and state["w"] is not None
    fn = opt.make_step(metas, reshard_payloads=lambda t: t)
    step = jax.jit(lambda s, b, t, f=fn: f(s, gal, b, t))
    state, _ = step(state, jnp.zeros((2, 1)), 0.01)
    path = os.path.join(tmp_path, "s2w_wire.npz")
    save_checkpoint(path, state, step=1)
    state2, at = load_checkpoint(path, state)
    assert at == 1
    _tree_equal(state["cs_state"], state2["cs_state"])
    _tree_equal(state["w"], state2["w"])
    _tree_equal(state, state2)
    for i in range(2):
        state, _ = step(state, jnp.zeros((2, 1)), 0.01)
        state2, _ = step(state2, jnp.zeros((2, 1)), 0.01)
    _tree_equal(state, state2)


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("name", ["top10+natural", "rank10+natural",
                                  "natural"])
def test_plan_hands_one_kernel_choice_to_compressors_and_codecs(
        name, use_pallas):
    """LayerPlan.build resolves ``use_pallas`` once and every compressor
    of the plan (WithNatural's inner one and the non-2D fallback
    included) and every narrow wire codec carries that choice."""
    params = {"w": jax.ShapeDtypeStruct((64, 96), jnp.float32),
              "v": jax.ShapeDtypeStruct((96,), jnp.float32)}
    metas = {"w": ParamMeta("spectral", 1.0, 0),
             "v": ParamMeta("sign", 1.0, 0)}
    plan = LayerPlan.build(params, metas, w2s=name, s2w=name,
                           use_pallas=use_pallas)
    assert plan.use_pallas is use_pallas
    for lp in plan.leaves:
        for comp in (lp.w2s, lp.s2w):
            for c in (comp, getattr(comp, "inner", None)):
                if hasattr(c, "use_pallas"):
                    assert c.use_pallas is use_pallas, c
    narrow = [c for d in ("w2s", "s2w")
              for spec in plan.wire_layout(jnp.bfloat16, d).specs
              for c in spec.codecs if isinstance(c, NarrowIntCodec)]
    assert bool(narrow) == (name != "natural")   # TopK indices
    assert all(c.use_pallas is use_pallas for c in narrow)


def test_auto_kernel_choice_resolves_to_references_on_cpu():
    """"auto" resolves once, from the platform: the jnp references here."""
    opt = EF21Muon(EF21MuonConfig(use_pallas="auto", w2s="top10+natural"))
    assert opt.cfg.use_pallas is (jax.default_backend() == "tpu")
    params = {"w": jax.ShapeDtypeStruct((64, 96), jnp.float32)}
    plan = opt.plan(params, {"w": ParamMeta("spectral", 1.0, 0)})
    assert plan.use_pallas is opt.cfg.use_pallas
    assert plan.leaves[0].w2s.use_pallas is opt.cfg.use_pallas
