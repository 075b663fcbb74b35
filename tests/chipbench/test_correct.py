"""``correct`` on a tiny cell on the CPU: a sound run passes, and a run
whose timed step is broken underneath, or the control in the program's
place, fails. Everything but the look for a chip runs as on the chip:
the harness drives the program's compiled step and compares it with the
plain reference."""
import contextlib
import io
import json

import jax
import jax.numpy as jnp
import pytest

import faults
import tiny
from chipbench import compare, data, ef21_reference, program, registry, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("chipbench"))


def drive(root, monkeypatch, seed):
    """One run of the tiny cell; (result line, standard error)."""
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: None)
    monkeypatch.setattr(run, "has_chips", lambda devices, n: True)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", tiny.CELL, "--seed", str(seed),
                       "--seconds", "0.5"], root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


def test_sound_run_is_correct(root, monkeypatch):
    line, err = drive(root, monkeypatch, tiny.SOUND_SEEDS[1])
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    # the step's footprint is the memory metric and the device's peak
    mem = line["metrics"]["step_hbm_gib"]["value"]
    assert mem > 0
    assert mem * 2**30 == line["device"]["memory_peak_bytes"]
    assert "memory: step" in err
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == set(tiny.LIMITS)
    # the numbers compared close standard error too
    tail = err.strip().splitlines()[-len(tiny.LIMITS):]
    assert [t.split(":")[0] for t in tail] == \
        [f"check {n}" for n in line["checks"]]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_step_is_not_correct(root, monkeypatch, fault):
    faults.plant(monkeypatch, faults.FAULTS[fault])
    line, _ = drive(root, monkeypatch, 7)
    assert line["correct"] is False


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described TPU v5e: the chip's compiler, no chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"cannot describe a v5e: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_step_memory_counts_activations(root, v5e_chip):
    """The step compiled for a described v5e at the tiny cell's tokens
    and at four times as many, with the same state: the peak that
    ``step_hbm_gib`` reads holds the state and grows by the
    activations, not only by the batch."""
    reg = registry.Registry(root)
    cell = reg.cell(tiny.CELL)
    fam = reg.reference(cell.config)
    arch, seq = cell.config["arch"], cell.config["seq"]
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
        tree)
    parts = []
    for mult in (1, 4):
        tr = dict(cell.traffic, tokens_per_worker_step=mult
                  * cell.traffic["tokens_per_worker_step"], n_batches=1)
        prog = program.Program(run.ROOT, cell.config, tr)
        key = data.seed_key(5)
        state = on_chip(jax.eval_shape(
            lambda: prog.trainer.opt.init(key, fam.init_params(arch, key),
                                          prog.trainer.metas)))
        batch = on_chip(jax.eval_shape(
            lambda: data.traffic_batches(5, tr, seq, arch["vocab"])[0]))
        t = jax.ShapeDtypeStruct((), jnp.float32, sharding=v5e_chip)
        prog.executable = prog.trainer.jit_step(batch).lower(
            state, batch, t).compile()
        parts.append(prog.footprint())
    small, big = parts
    assert small["peak"] >= small["argument"]
    batch_growth = big["argument"] - small["argument"]
    assert 0 < batch_growth < small["argument"] / 4
    assert big["peak"] > small["peak"] + batch_growth
    assert big["temp"] > small["temp"]


def test_control_fails_the_limits(root):
    """The reference in one precision step lower, in the program's
    place, fails at least one number on every seed."""
    reg = registry.Registry(root)
    cell = reg.cell(tiny.CELL)
    fam = reg.reference(cell.config)
    arch, tr = cell.config["arch"], cell.traffic
    for seed in (1, 2):
        key = jax.random.fold_in(data.seed_key(seed), 0)
        batches = data.traffic_batches(seed, tr, cell.config["seq"],
                                       arch["vocab"])[:3]
        exact = ef21_reference.run(fam, arch, tr,
                                   fam.init_params(arch, key), batches, 3)
        lowp = ef21_reference.run(fam, arch, tr,
                                  fam.init_params(arch, key), batches, 3,
                                  variant="lowp")
        ok, checks = compare.judge(compare.numbers(lowp, exact),
                                   cell.limits)
        assert not ok, checks
        ok, _ = compare.judge(compare.numbers(exact, exact), cell.limits)
        assert ok
