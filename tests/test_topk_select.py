"""TopK selection without a sort (kernels/topk_select.py, ref.py).

The kept set must be ``lax.top_k(abs(x), k)``'s, ties to the lower
index, in ascending index order, with the values of those entries bit
for bit; the Pallas kernel (interpret mode) and the jnp reference must
return the same payload. ``lax.top_k`` is kept here as the oracle: a
``TopK`` built on it, and the training step run through it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.core import compressors as C
from repro.data import SyntheticLM
from repro.kernels import ref
from repro.kernels.ops import topk_select
from repro.models.api import build_model
from repro.train.trainer import Trainer, TrainerConfig


def _oracle_topk_select(flat, k, **_):
    """The selection TopK made before: a sort-based ``lax.top_k``."""
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx.astype(jnp.int32)


def _data(kind, n, dtype, seed):
    x = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
    if kind == "quarters":                    # heavy ties
        x = np.round(x * 4) / 4
    elif kind == "zeros":
        x = np.zeros(n, np.float32)
    elif kind == "half_zeros":
        x[::2] = 0.0
    elif kind == "signed_zeros":              # ±0 and ties around them
        x = np.round(x) * 0.0
        x[1::3] = np.round(x[1::3] + 1)
    return jnp.asarray(x, dtype)


def _bits(v):
    v = np.asarray(v)
    return v.view(np.uint16 if v.dtype.itemsize == 2 else np.uint32)


def _check(x, k, vals, idx):
    """Same set as lax.top_k, ascending, values bit-equal to x[idx]."""
    lead = x.shape[:-1]
    want = jnp.sort(jax.lax.top_k(jnp.abs(x), k)[1], axis=-1)
    assert vals.shape == idx.shape == lead + (k,)
    assert vals.dtype == x.dtype and idx.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    assert bool(jnp.all(jnp.diff(idx, axis=-1) > 0))
    np.testing.assert_array_equal(
        _bits(vals), _bits(jnp.take_along_axis(x, idx, axis=-1)))


CASES = [  # (kind, n, k)
    ("normal", 3, 1), ("normal", 77, 77), ("normal", 127, 13),
    ("quarters", 1001, 100), ("quarters", 4099, 1), ("zeros", 300, 30),
    ("half_zeros", 2049, 1000), ("signed_zeros", 640, 64),
    ("quarters", 70001, 7001),                # two kernel blocks
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("kind,n,k", CASES)
def test_kernel_and_reference_match_top_k(kind, n, k, dtype):
    x = _data(kind, n, dtype, seed=n + k)
    vr, ir = topk_select(x, k, use_pallas=False)
    _check(x, k, vr, ir)
    vp, ip = topk_select(x, k, use_pallas=True, interpret=True)
    _check(x, k, vp, ip)
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
    np.testing.assert_array_equal(_bits(vp), _bits(vr))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_vmapped_workers_and_stack(use_pallas):
    """[workers, stack, n] under two vmaps, as the optimizer calls it:
    the kernel takes every slice in one call."""
    n, k = 3000, 300
    x = jnp.stack([jnp.stack([_data(kind, n, jnp.bfloat16, w * 3 + s)
                              for s, kind in enumerate(
                                  ("quarters", "half_zeros", "normal"))])
                   for w in range(2)])
    sel = jax.jit(jax.vmap(jax.vmap(lambda s: topk_select(
        s, k, use_pallas=use_pallas, interpret=True))))
    vals, idx = sel(x)
    _check(x, k, vals, idx)


def test_threshold_keeps_exactly_k_and_cuts_ties_low():
    x = jnp.asarray([0.5, -1.0, 0.5, 2.0, -0.5, 0.5, 0.0], jnp.float32)
    t, c = ref.topk_threshold(x, 4)
    keep = np.asarray(ref.topk_keep_mask(x, t, c))
    # 2.0 and -1.0 above the threshold, then the two lowest-index 0.5s
    np.testing.assert_array_equal(keep, [1, 1, 1, 1, 0, 0, 0])
    assert int(t) == int(ref.magnitude_key32(jnp.float32(0.5)))
    assert int(c) == 3


def _roundtrip(x, natural, select):
    """decompress(compress(x)) of top10 (and +Natural) with TopK's
    selection replaced by ``select``."""
    comp = C.TopK(0.1, use_pallas=False)
    if natural:
        comp = C.WithNatural(comp, use_pallas=False)
    mp = pytest.MonkeyPatch()
    mp.setattr(C, "topk_select", select)
    try:
        payload, _ = comp.compress({}, x)
        return comp.decompress(payload, x.shape, jnp.float32)
    finally:
        mp.undo()


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("natural", [False, True])
def test_topk_roundtrip_equals_lax_top_k_scatter(use_pallas, natural):
    """decompress(compress(x)) is the old formula's dense delta bit for
    bit: the same entries, only their order in the payload changed."""
    x = _data("quarters", 48 * 40, jnp.bfloat16, seed=5).reshape(48, 40)
    select = lambda f, k, **_: topk_select(f, k, use_pallas=use_pallas,
                                           interpret=True)
    got = _roundtrip(x, natural, select)
    want = _roundtrip(x, natural, _oracle_topk_select)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _train(key, steps=3):
    """The tiny CPU cell: a two-layer nanoGPT, two workers on
    top10+natural messages."""
    cfg = get_config("nanogpt-124m").reduced()
    tr = Trainer(build_model(cfg), TrainerConfig(
        n_workers=2, beta=0.5, w2s="top10+natural", remat=True,
        use_pallas=False))
    data = SyntheticLM(cfg, ShapeSpec("t", "train", 32, 4), n_workers=2,
                       seed=0)
    state = tr.init(key)
    step = jax.jit(tr.make_step())
    for i in range(steps):
        state, _ = step(state, data.batch_at(i), 0.01)
    return state


def test_training_state_bit_equal_to_lax_top_k_path(key, monkeypatch):
    got = _train(key)
    monkeypatch.setattr(C, "topk_select", _oracle_topk_select)
    want = _train(key)
    same = jax.tree.map(
        lambda a, b: bool(np.array_equal(_bits(a), _bits(b)))
        if jnp.issubdtype(a.dtype, jnp.floating)
        else bool(jnp.array_equal(a, b)), got, want)
    assert all(jax.tree.leaves(same)), same


@pytest.mark.parametrize("dtype,passes", [(jnp.bfloat16, 15),
                                          (jnp.float32, 31)])
def test_threshold_passes_follow_the_dtype(dtype, passes):
    """One compare-and-count pass per key bit the dtype can set."""
    jaxpr = jax.make_jaxpr(lambda x: ref.topk_threshold(x, 3))(
        jnp.zeros(10, dtype))
    loops = [e.params["length"] for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "scan"]
    assert loops == [passes]
