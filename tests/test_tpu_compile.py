"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each kernel wrapper is lowered and compiled at nanogpt-124m
widths against a v5e topology that JAX describes without a chip, which
refuses what the chip's compiler would refuse (unsupported casts and
shifts, VMEM overruns) and what interpret mode cannot see. The topology
is described inside a module fixture, never at import, so every xdist
worker collects the same tests and only the one running this file loads
the TPU compiler.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitpack as bp
from repro.kernels.ops import (natural_compress, natural_decompress,
                               newton_schulz_batched, topk_select)

D_MODEL, D_FF = 768, 3072
K_TOP10 = math.ceil(0.1 * D_MODEL * D_FF)   # top10 of one MLP slice


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e 2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip; keep these compiles out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(12, D_MODEL, D_MODEL),
                                   (12, D_MODEL, D_FF)])
def test_fused_ns_compiles_for_v5e(shape, dtype, one_chip):
    _compile(lambda g: newton_schulz_batched(g, steps=5, use_pallas=True),
             one_chip, (shape, dtype))


@pytest.mark.parametrize("n", [K_TOP10, D_MODEL])
def test_bitpack_compiles_for_v5e(n, one_chip):
    n8 = 8 * math.ceil(n / 8)
    _compile(lambda b: bp.pack_bits(b, use_pallas=True), one_chip,
             ((n8,), jnp.uint8))
    _compile(lambda p: bp.unpack_bits(p, use_pallas=True), one_chip,
             ((n8 // 8,), jnp.uint8))


@pytest.mark.parametrize("n", [K_TOP10, D_MODEL])
def test_natural_codec_compiles_for_v5e(n, one_chip):
    _compile(lambda x: natural_compress(x, use_pallas=True), one_chip,
             ((n,), jnp.bfloat16))
    _compile(lambda c, s: natural_decompress(c, s, (n,), use_pallas=True),
             one_chip, ((n,), jnp.uint8), ((math.ceil(n / 8),), jnp.uint8))


# index domains at nanogpt widths: a [768] vector fits 2 bytes, a
# [768, 768] or [768, 3072] slice needs 3 (the embedding's 4 is raw)
@pytest.mark.parametrize("width,k", [(2, math.ceil(0.1 * D_MODEL)),
                                     (3, math.ceil(0.1 * D_MODEL ** 2)),
                                     (3, K_TOP10)])
def test_narrow_codec_compiles_for_v5e(width, k, one_chip):
    assert bp.narrow_width(D_MODEL) == 2
    assert bp.narrow_width(D_MODEL * D_FF) == 3
    _compile(lambda i: bp.narrow_encode(i, width, use_pallas=True),
             one_chip, ((k,), jnp.int32))
    _compile(lambda b: bp.narrow_decode(b, width, use_pallas=True),
             one_chip, ((width * k,), jnp.uint8))


# the embedding slice and a stack of MLP slices, both workers, flattened
# under the vmaps the optimizer adds: the threshold passes and the
# compaction kernel, and no sort
@pytest.mark.parametrize("shape", [(2, 50304, D_MODEL),
                                   (2, 12, D_MODEL, D_FF)])
def test_topk_select_compiles_for_v5e(shape, one_chip):
    k = math.ceil(0.1 * shape[-2] * shape[-1])
    fn = lambda s: topk_select(s.reshape(-1), k,  # noqa: E731
                               use_pallas=True)
    for _ in shape[:-2]:
        fn = jax.vmap(fn)
    text = _compile(fn, one_chip, (shape, jnp.bfloat16)).as_text()
    assert "topk_select" in text
    assert not re.search(r"\bsort\(", text)


# Mosaic kernels cannot be partitioned automatically: on a mesh of several
# devices every kernel of the step must run under shard_map, or lowering
# fails. These two off-default paths of the step hold kernels too: the
# §13 replay (unpack + Natural decompress of the ring rows) and the
# per-leaf Newton-Schulz LMO of ns_bucketing=False.
@pytest.mark.parametrize("kw", [{"resync": 2}, {"ns_bucketing": False}],
                         ids=["resync", "per-leaf-ns"])
def test_kernel_step_on_mesh_compiles_for_v5e(kw, v5e_2x2):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.muon import EF21Muon, EF21MuonConfig, ParamMeta

    mesh = Mesh(np.asarray(v5e_2x2[:4]).reshape(4, 1), ("data", "model"))
    rep, wsh = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    opt = EF21Muon(EF21MuonConfig(n_workers=4, w2s="top10+natural",
                                  s2w="natural", use_pallas=True, **kw))
    params = {"w": jax.ShapeDtypeStruct((256, 512), jnp.float32),
              "v": jax.ShapeDtypeStruct((512,), jnp.float32)}
    metas = {"w": ParamMeta("spectral", 1.0, 0),
             "v": ParamMeta("sign", 1.0, 0)}

    def gal(p, wb):
        return jax.value_and_grad(lambda q: wb[0] * sum(
            jnp.sum(a ** 2) for a in jax.tree.leaves(q)))(p)

    def reshard(tree):     # the trainer's worker-axis all-gather hook
        return jax.tree.map(lambda a: jax.lax.with_sharding_constraint(
            jax.lax.with_sharding_constraint(a, wsh), rep), tree)

    step = opt.make_step(metas, reshard_payloads=reshard, mesh=mesh)
    state = jax.eval_shape(lambda k, p: opt.init(k, p, metas),
                           jax.random.key(0), params)
    put = lambda t: jax.tree.map(   # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), t)
    compiled = jax.jit(lambda s, b: step(s, gal, b, 0.01)).lower(
        put(state), put(jax.ShapeDtypeStruct((4, 1), jnp.float32))
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
