"""Desynchronized-worker rejoin + supervised recovery (DESIGN.md §13).

The resync algebra (dist/resync.py), its optimizer integration (the
version vector, replay ring, and full-resync fallback inside the jitted
step), the §13 reception semantics, the new host-side fault clauses
(stall/crash), the supervisor state machine, and the checkpoint
durability satellites.

The pinned invariant: a worker absent across K s2w broadcasts is, after
rejoin, BIT-identical to the always-present workers — on any compressor,
because every worker applies the same broadcast byte stream through the
same ``apply_payload`` algebra, whether on time or replayed from the
ring. The lossless-wire arm additionally ties the shared estimate to the
server's iterate; the lossy arm to the EF21-P contraction bound.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compressors as compressors_mod
from repro.core.compressors import Identity
from repro.core.muon import EF21Muon, EF21MuonConfig, ParamMeta
from repro.dist.participation import Explicit, reception_mask
from repro.dist.resync import (init_resync_state, replay_masks,
                               resolve_ring_depth, ring_push,
                               serve_full_resync)
from repro.train.checkpoint import load_checkpoint, save_checkpoint
from repro.train.faults import (CRASH_EXIT, CrashFault, DropFault,
                                FaultPlan, StallFault, parse_faults)
from repro.train.supervisor import (Supervisor, SupervisorConfig,
                                    SupervisorError)


# ------------------------------------------------------------ fixtures

def _hetero(n_w=4, dim=12, seed=0):
    """Heterogeneous quadratic workers: worker j pulls toward target
    T_j, so partial participation visibly changes the trajectory."""
    key = jax.random.key(seed)
    Ts = jax.random.normal(key, (n_w, dim, dim))

    def gal(p, wb):
        t = Ts[jnp.int32(wb[0])]
        return 0.5 * jnp.sum((p - t) ** 2), (p - t)

    params = jnp.zeros((dim, dim))
    metas = ParamMeta("spectral", 1.0, 0)
    batch = jnp.arange(float(n_w)).reshape(n_w, 1)
    return params, metas, gal, batch


def _run(cfg, n_steps=10, n_w=4, seed=0):
    params, metas, gal, batch = _hetero(n_w=n_w, seed=seed)
    opt = EF21Muon(cfg)
    state = opt.init(jax.random.key(seed), params, metas)
    step = jax.jit(lambda s, b: opt.make_step(metas)(s, gal, b, 0.05))
    auxes = []
    for _ in range(n_steps):
        state, aux = step(state, batch)
        auxes.append(aux)
    return state, auxes


def _resync_cfg(n_w=4, s2w="natural", resync=4, masks=None, **kw):
    part = Explicit(tuple(masks)) if masks is not None else "full"
    return EF21MuonConfig(n_workers=n_w, beta=0.5, w2s="top10", s2w=s2w,
                          use_pallas=False, participation=part,
                          resync=resync, **kw)


# ------------------------------------------------- resolve_ring_depth

def test_resolve_ring_depth_off_values():
    assert resolve_ring_depth(None) == 0
    assert resolve_ring_depth(0) == 0
    assert resolve_ring_depth(False) == 0
    assert resolve_ring_depth(4) == 4


def test_resolve_ring_depth_rejects_negative():
    with pytest.raises(ValueError):
        resolve_ring_depth(-2)


def test_resync_requires_compressing_s2w():
    params, metas, _, _ = _hetero()
    opt = EF21Muon(_resync_cfg(s2w="identity"))
    with pytest.raises(ValueError, match="resync"):
        opt.init(jax.random.key(0), params, metas)


# --------------------------------------------------- replay-mask algebra

def test_replay_masks_current_worker_applies_only_newest_slot():
    # vv == step: on-time application is the degenerate replay — only
    # the current round (slot R-1) applies
    R, n = 4, 3
    rm = replay_masks(jnp.full((n,), 7), 7, jnp.ones((n,), bool), R)
    ap = np.asarray(rm.apply)
    assert (ap[R - 1] == True).all()            # noqa: E712
    assert not ap[: R - 1].any()
    assert (np.asarray(rm.vv_new) == 8).all()
    assert int(rm.n_replayed) == 0 and int(rm.n_full) == 0
    assert int(rm.lag_max) == 0


def test_replay_masks_lagged_worker_replays_missed_rounds():
    # worker 1 at vv=5, step=7, R=4: ring holds rounds 4..7 after the
    # push; it must apply rounds 5,6,7 == slots 1,2,3
    R = 4
    vv = jnp.asarray([7, 5, 7])
    rm = replay_masks(vv, 7, jnp.ones((3,), bool), R)
    ap = np.asarray(rm.apply)
    assert (ap[:, 1] == [False, True, True, True]).all()
    assert int(rm.n_replayed) == 1 and int(rm.n_full) == 0
    assert (np.asarray(rm.vv_new) == 8).all()


def test_replay_masks_lag_beyond_ring_takes_full():
    R = 3
    vv = jnp.asarray([9, 2, 9])     # worker 1 needs round 2 < 9-(R-1)=7
    rm = replay_masks(vv, 9, jnp.ones((3,), bool), R)
    assert not np.asarray(rm.apply)[:, 1].any()
    assert np.asarray(rm.full).tolist() == [False, True, False]
    assert int(rm.n_full) == 1 and int(rm.n_replayed) == 0


def test_replay_masks_absent_worker_frozen():
    recv = jnp.asarray([True, False, True])
    rm = replay_masks(jnp.full((3,), 4), 4, recv, 2)
    assert not np.asarray(rm.apply)[:, 1].any()
    assert not bool(rm.full[1])
    assert np.asarray(rm.vv_new).tolist() == [5, 4, 5]
    assert int(rm.lag_max) == 1


def test_ring_push_rolls_oldest_out():
    ring = jnp.arange(6, dtype=jnp.uint8).reshape(3, 2)
    out = np.asarray(ring_push(ring, jnp.asarray([9, 9], jnp.uint8)))
    assert (out[:2] == np.asarray(ring)[1:]).all()
    assert (out[2] == 9).all()


def test_init_resync_state_shapes():
    st = init_resync_state(5, 3, 64)
    assert st["vv"].shape == (5,) and st["vv"].dtype == jnp.int32
    assert st["ring"].shape == (3, 64) and st["ring"].dtype == jnp.uint8


def test_init_resync_state_rejects_oversized_ring_row():
    # a packed s2w row past the XLA int32 dim limit (e.g. granite-3-2b
    # at 512 devices: 2.85 GB/round) must fail loudly with guidance,
    # not crash XLA shape inference deep in lowering
    with pytest.raises(ValueError, match="serve_full_resync"):
        init_resync_state(4, 3, 2**31)


# ------------------------------------------------ reception semantics

def test_reception_mask_ands_schedule_and_drops():
    fp = FaultPlan(n_workers=3, drops=(DropFault(2, 0, 10),))
    spec = Explicit(((1, 0, 1),))
    m = np.asarray(reception_mask(spec, 3, 0, faults=fp))
    assert m.tolist() == [True, False, False]


# ------------------------------------------- optimizer-level invariant

ABSENT, K = 1, 3   # worker 1 misses K consecutive broadcasts


def _absence_masks(n_w=4, start=3, k=K):
    full = (1,) * n_w
    gone = tuple(0 if j == ABSENT else 1 for j in range(n_w))
    return [full] * start + [gone] * k + [full] * 8


def test_rejoin_within_ring_is_bit_identical_lossy():
    # lag K <= R: replay. The pinned §13 invariant — after rejoin every
    # worker's W estimate is BIT-equal to the server's (hence to every
    # always-present worker's), on a lossy compressor.
    state, auxes = _run(_resync_cfg(resync=4, masks=_absence_masks()),
                        n_steps=12)
    assert sum(int(a["resync_replayed"]) for a in auxes) >= 1
    assert sum(int(a["resync_full"]) for a in auxes) == 0
    w = np.asarray(state["w"])
    for j in range(4):
        assert np.array_equal(np.asarray(state["w_w"][j]), w), j
    # lag telemetry: grows during the absence, returns to 0 after
    lags = [int(a["version_lag_max"]) for a in auxes]
    assert max(lags) == K and lags[-1] == 0


def test_rejoin_within_ring_is_bit_identical_lossless():
    # same invariant on a lossless wire: registry-aliased Identity
    # subclass, so s2w != "identity" (the resync guard is a string
    # check) while the leg itself is exact
    compressors_mod.REGISTRY.setdefault(
        "identity-wire", lambda up: type("IdentityWire", (Identity,), {})())
    state, auxes = _run(
        _resync_cfg(s2w="identity-wire", resync=4,
                    masks=_absence_masks()), n_steps=12)
    assert sum(int(a["resync_replayed"]) for a in auxes) >= 1
    w = np.asarray(state["w"])
    for j in range(4):
        assert np.array_equal(np.asarray(state["w_w"][j]), w), j
    # the lossless leg ties W to the server's iterate up to exactly one
    # LMO step of lag (W is advanced before X moves): here the step is
    # spectral-LMO with radius t, so ||x - w||_F <= t * sqrt(dim)
    x = np.asarray(state["x"])
    assert np.linalg.norm(x - w) <= 0.05 * np.sqrt(x.shape[-1]) + 1e-5


def test_lossy_rejoin_within_ef_bound():
    # EF21-P keeps ||X - W|| bounded on the lossy arm too — weaker than
    # the lossless tie, but the drift must stay comparable to the
    # always-present run's compression error, not grow with absence
    base, _ = _run(_resync_cfg(resync=4), n_steps=12)
    state, _ = _run(_resync_cfg(resync=4, masks=_absence_masks()),
                    n_steps=12)
    drift = np.linalg.norm(np.asarray(state["x"]) - np.asarray(state["w"]))
    base_drift = np.linalg.norm(
        np.asarray(base["x"]) - np.asarray(base["w"]))
    assert np.isfinite(drift)
    assert drift <= 4.0 * base_drift + 1e-6


def test_lag_beyond_ring_takes_full_resync():
    # absence of 6 rounds > R=3: replay impossible, full W copy instead
    masks = _absence_masks(start=2, k=6)
    state, auxes = _run(_resync_cfg(resync=3, masks=masks), n_steps=12)
    assert sum(int(a["resync_full"]) for a in auxes) == 1
    assert sum(int(a["resync_replayed"]) for a in auxes) == 0
    w = np.asarray(state["w"])
    for j in range(4):
        assert np.array_equal(np.asarray(state["w_w"][j]), w), j


def test_resync_off_leaves_state_and_aux_clean():
    state, auxes = _run(EF21MuonConfig(
        n_workers=4, beta=0.5, w2s="top10", s2w="natural",
        use_pallas=False), n_steps=3)
    assert "w_w" not in state and "resync" not in state
    assert "resync_replayed" not in auxes[0]
    assert "version_lag_max" not in auxes[0]


def test_resync_metrics_surface():
    _, auxes = _run(_resync_cfg(resync=2, metrics=True), n_steps=2)
    names = auxes[0]["metrics"].names()
    for want in ("part/worker_version_lag_max", "resync/replayed",
                 "resync/full"):
        assert want in names, want


def test_resync_survives_all_absent_step():
    # every worker misses a round: global skip advances W and the ring;
    # the rejoin replays that round to everyone and stays bit-consistent
    masks = [(1, 1, 1, 1), (0, 0, 0, 0), (1, 1, 1, 1)]
    state, auxes = _run(_resync_cfg(resync=4, masks=masks), n_steps=9)
    w = np.asarray(state["w"])
    for j in range(4):
        assert np.array_equal(np.asarray(state["w_w"][j]), w), j


# --------------------------------------------------- serve_full_resync

def test_serve_full_resync_round_trips(tmp_path):
    state, _ = _run(_resync_cfg(resync=2), n_steps=2)
    path = str(tmp_path / "ck")
    save_checkpoint(path, state, step=2)
    like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    w, version = serve_full_resync(path, like)
    assert version == 2
    np.testing.assert_array_equal(np.asarray(w), np.asarray(state["w"]))


def test_serve_full_resync_rejects_non_optimizer_tree(tmp_path):
    path = str(tmp_path / "ck")
    save_checkpoint(path, {"params": np.zeros(3)}, step=1)
    with pytest.raises(ValueError, match="no 'x' entry"):
        serve_full_resync(path, {"params": np.zeros(3)})


# -------------------------------------------------- fault grammar (§13)

def test_parse_stall_round_trip():
    fp = parse_faults("stall:w=1:steps=5-7:ms=250", n_workers=4)
    assert fp.stalls == (StallFault(1, 5, 7, ms=250),)
    assert fp.stall_ms(5) == 250 and fp.stall_ms(7) == 0
    assert fp.stall_ms(5, attempt=1) == 0     # retries skip the stall
    assert bool(fp.active_any(6)) and not bool(fp.active_any(8))


def test_parse_stall_default_ms():
    fp = parse_faults("stall:w=0:steps=3", n_workers=2)
    assert fp.stalls[0].ms == 1000
    assert fp.stalls[0].start == 3 and fp.stalls[0].stop == 4


def test_parse_crash_round_trip():
    fp = parse_faults("crash:step=9", n_workers=2)
    assert fp.crashes == (CrashFault(9),)
    assert fp.crashes[0].start == 9 and fp.crashes[0].stop == 10
    assert bool(fp.active_any(9)) and not bool(fp.active_any(10))


def test_parse_mixed_clauses_with_host_faults():
    fp = parse_faults(
        "drop:w=1:steps=2-4,stall:w=0:steps=5:ms=50,crash:step=8",
        n_workers=3)
    assert len(fp.drops) == 1 and len(fp.stalls) == 1
    assert len(fp.crashes) == 1


@pytest.mark.parametrize("bad", [
    "stall:w=9:steps=1:ms=5",   # worker out of range
    "stall:w=0:steps=1:ms=0",   # non-positive stall
    "crash:steps=3",            # crash takes step=, not steps=
    "stall:w=0",                # missing steps
])
def test_parse_host_faults_reject(bad):
    with pytest.raises(ValueError):
        parse_faults(bad, n_workers=4)


def test_host_crash_gated_on_resumed_runs():
    fp = parse_faults("crash:step=4", n_workers=2)
    fp.host_crash(4, start_step=2)   # resumed run: must NOT exit
    fp.host_crash(3, start_step=0)   # wrong step: no exit
    assert CRASH_EXIT == 43


def test_host_stall_sleeps_and_reports(monkeypatch):
    import repro.train.faults as faults_mod
    slept = []
    monkeypatch.setattr(faults_mod.time, "sleep",
                        lambda s: slept.append(s))
    fp = parse_faults("stall:w=0:steps=2:ms=80", n_workers=1)
    assert fp.host_stall(2) == 80 and slept == [0.08]
    assert fp.host_stall(2, attempt=1) == 0 and len(slept) == 1


# ---------------------------------------------------------- supervisor

class _ListWriter:
    def __init__(self):
        self.records = []

    def write(self, rec_kind, **fields):
        self.records.append({"rec_kind": rec_kind, **fields})


def test_supervisor_passthrough_without_watchdog():
    sup = Supervisor(SupervisorConfig())
    result, rs, rstep = sup.run_step(lambda s, b: (s + b, {}), 1, 2,
                                     step=0)
    assert result == (3, {}) and rs is None and rstep is None
    assert sup.retries == 0


def test_supervisor_timeout_then_retry_succeeds():
    fp = parse_faults("stall:w=0:steps=5:ms=10000", n_workers=1)
    w = _ListWriter()
    sup = Supervisor(SupervisorConfig(step_timeout_s=0.1, max_retries=2,
                                      backoff_base_s=0.01), writer=w)
    result, rs, rstep = sup.run_step(lambda s: s * 2, 21, step=5,
                                     faults=fp)
    assert result == 42 and rs is None and rstep is None
    assert sup.retries == 1
    assert [r["event"] for r in w.records] == ["timeout"]
    assert all(r["rec_kind"] == "recovery" for r in w.records)
    assert w.records[0]["step"] == 5 and w.records[0]["attempt"] == 0


def test_supervisor_transient_exception_retries():
    attempts = []

    def flaky(state):
        attempts.append(1)
        if len(attempts) < 3:
            raise RuntimeError("transient")
        return state

    sup = Supervisor(SupervisorConfig(max_retries=3, backoff_base_s=0.0))
    result, _, _ = sup.run_step(flaky, "ok", step=1)
    assert result == "ok" and sup.retries == 2


def test_supervisor_exhaustion_raises():
    w = _ListWriter()
    sup = Supervisor(SupervisorConfig(max_retries=1, backoff_base_s=0.0),
                     writer=w)
    with pytest.raises(SupervisorError, match="after 2 attempt"):
        sup.run_step(lambda s: 1 / 0, None, step=3)
    assert [r["event"] for r in w.records] == ["retry", "retry",
                                               "gave_up"]


def test_supervisor_reloads_last_good_checkpoint(tmp_path):
    path = str(tmp_path / "ck")
    good = {"x": np.arange(4.0, dtype=np.float32)}
    save_checkpoint(path, good, step=6)
    w = _ListWriter()
    sup = Supervisor(
        SupervisorConfig(max_retries=0, checkpoint_path=path),
        writer=w, state_like={"x": np.zeros(4, np.float32)})

    def bad(state):
        raise RuntimeError("device poisoned")

    result, rs_state, rs_step = sup.run_step(bad, None, step=9)
    # the stored checkpoint step IS the next step to execute
    assert result is None and rs_step == 6
    np.testing.assert_allclose(np.asarray(rs_state["x"]), good["x"])
    assert sup.reloads == 1
    assert [r["event"] for r in w.records] == ["retry", "reload"]
    # a second failure with no forward progress must raise, not loop
    with pytest.raises(SupervisorError):
        sup.run_step(bad, None, step=9)


def test_supervisor_maybe_checkpoint_cadence(tmp_path):
    path = str(tmp_path / "ck")
    sup = Supervisor(SupervisorConfig(checkpoint_path=path,
                                      checkpoint_every=4))
    assert not sup.maybe_checkpoint({"x": np.zeros(2)}, 0)
    assert sup.maybe_checkpoint({"x": np.ones(2)}, 3)    # (3+1) % 4 == 0
    tree, step = load_checkpoint(path, {"x": np.zeros(2, np.float32)})
    # stored step = next step to execute (the CLI resume convention)
    assert step == 4 and np.asarray(tree["x"]).tolist() == [1.0, 1.0]


def test_supervisor_config_validation():
    with pytest.raises(ValueError):
        SupervisorConfig(max_retries=-1)
    with pytest.raises(ValueError):
        SupervisorConfig(step_timeout_s=0.0)


# ------------------------------------------- checkpoint satellites

def test_checkpoint_legacy_bare_archive_rotates_to_prev(tmp_path):
    # a pre-".npz" run left its archive at the bare path; a fresh save
    # must rotate it aside, or load_checkpoint prefers the stale bare
    # file forever
    bare = str(tmp_path / "ck")
    with open(bare, "wb") as f:
        np.savez(f, **{"x": np.zeros(3), "__step__": np.asarray(1)})
    save_checkpoint(bare, {"x": np.ones(3, np.float32)}, step=5)
    assert not os.path.exists(bare)
    assert os.path.exists(bare + ".npz") and os.path.exists(
        bare + ".npz.prev")
    tree, step = load_checkpoint(bare, {"x": np.zeros(3, np.float32)})
    assert step == 5 and np.asarray(tree["x"]).tolist() == [1.0] * 3


def test_checkpoint_publish_fsyncs_parent_dir(tmp_path, monkeypatch):
    import repro.train.checkpoint as ck
    synced = []
    real_fsync = os.fsync
    monkeypatch.setattr(ck.os, "fsync",
                        lambda fd: (synced.append(fd), real_fsync(fd)))
    save_checkpoint(str(tmp_path / "ck"), {"x": np.zeros(2)}, step=0)
    # one fsync for the tmp file, one for the parent directory
    assert len(synced) == 2
