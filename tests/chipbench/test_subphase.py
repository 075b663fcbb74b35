"""The split of a phase's device time among the program's sub-phase
scopes, on hand-built traces whose answers are known and on recorded
chip traces."""
from pathlib import Path

import pytest

from chipbench import run, subphase
from chipbench import trace_reduce as trc
from chipbench.registry import Registry

REPO = Path(__file__).resolve().parents[2]
P2 = "ef21/p2_grads"
P3 = "ef21/p3_ef_compress"
BWD = "jit(step)/ef21/p2_grads/vmap(transpose(jvp()))/while/body/"
NEW = ("phase_ms.p2_attn", "phase_ms.p2_mlp", "phase_ms.p2_vocab",
       "phase_ms.p3_select", "phase_ms.p3_ef_update",
       "phase_ms.p4_decompress")


def test_ops_go_to_the_scope_they_carry():
    ops = [(0.0, 10.0, "fusion.1",
            "jit(step)/ef21/p2_grads/vmap(jvp(lm.vocab))/gather:"),
           (10.0, 20.0, "fusion.2", BWD + "checkpoint/lm.attn/dot_general:"),
           (30.0, 30.0, "fusion.3",
            BWD + "checkpoint/rematted_computation/lm.mlp/dot_general:"),
           (60.0, 5.0, "copy.4", BWD + "dynamic_update_slice:"),
           (65.0, 7.0, "fusion.5",
            "jit(step)/ef21/p2_grads/vmap(transpose(jvp(lm.vocab)))/"
            "scatter-add:"),
           (72.0, 3.0, "sort.6", "jit(step)/ef21/p3_ef_compress/"
            "vmap(ef.select)/top_k:")]
    got = subphase.subphase_ns(ops, P2, subphase.P2)
    assert got == {"lm.vocab": 17.0, "lm.attn": 20.0, "lm.mlp": 30.0,
                   None: 5.0}
    assert subphase.subphase_ns(ops, P3, subphase.P3) == {"ef.select": 3.0}


def test_an_op_without_a_name_takes_its_neighbours_sub_phase():
    sel = "jit(step)/ef21/p3_ef_compress/vmap(ef.select)/top_k:"
    upd = "jit(step)/ef21/p3_ef_compress/vmap(ef.update)/scatter:"
    ops = [(0.0, 10.0, "sort.1", sel), (10.0, 4.0, "sort.2", ""),
           (14.0, 10.0, "sort.3", sel), (24.0, 3.0, "fusion.4", ""),
           (27.0, 5.0, "fusion.5", upd), (32.0, 2.0, "fusion.6", ""),
           (34.0, 6.0, "fusion.7", upd)]
    assert subphase.subphase_ns(ops, P3, subphase.P3) == {
        "ef.select": 24.0, None: 3.0, "ef.update": 13.0}


def test_sub_phases_add_up_to_their_phase():
    sel = "jit(step)/ef21/p3_ef_compress/vmap(ef.select)/top_k:"
    ops = [(0.0, 100.0, "while.1", BWD + "checkpoint/lm.attn/while:"),
           (0.0, 40.0, "fusion.2", BWD + "checkpoint/lm.attn/dot_general:"),
           (40.0, 10.0, "fusion.3", ""),
           (50.0, 50.0, "fusion.4", BWD + "checkpoint/lm.mlp/mul:"),
           (100.0, 10.0, "sort.5", sel), (110.0, 10.0, "sort.6", ""),
           (120.0, 8.0, "fusion.7",
            "jit(step)/ef21/p3_ef_compress/vmap()/mul:"),
           (128.0, 1.0, "copy.8", "jit(step)/copy:")]
    ph = trc.phase_ns(ops)
    for phase, names in ((P2, subphase.P2), (P3, subphase.P3)):
        got = subphase.subphase_ns(ops, phase, names)
        assert sum(got.values()) == pytest.approx(ph[phase])
    # the loop that holds ops counts once, through its leaves; an
    # unnamed op between two sub-phases, or between one and an op
    # outside any, belongs to none
    assert subphase.subphase_ns(ops, P2, subphase.P2) == {
        "lm.attn": 40.0, None: 10.0, "lm.mlp": 50.0}
    assert subphase.subphase_ns(ops, P3, subphase.P3) == {
        "ef.select": 10.0, None: 18.0}


@pytest.mark.parametrize("op, hit", [
    ("jit(step)/ef21/p3_ef_compress/vmap(vmap(select))/top_k:", True),
    ("jit(step)/ef21/p3_ef_compress/select:", True),
    ("jit(step)/ef21/p3_ef_compress/select", True),
    ("jit(step)/ef21/p3_ef_compress/select_n:", False),
    ("jit(step)/ef21/p3_ef_compress/jit(_where)/select_n:", False),
    ("jit(step)/ef21/p3_ef_compress/preselect/x:", False),
])
def test_a_name_matches_whole_path_segments_only(op, hit):
    got = subphase.subphase_ns([(0.0, 1.0, "fusion.1", op)], P3,
                               ("select",))
    assert got == ({"select": 1.0} if hit else {None: 1.0})


def traced_ctx(path):
    """What a reader sees of a recorded trace: its first step."""
    tr = trc.read_trace(str(path))
    return run.Ctx(tr, trc.window(tr), 1, len(tr.devices), None, None,
                   None, None, None)


def read_all(ctx):
    reg = Registry(REPO)
    return {name: reg.metric_reader(name)(ctx) for name in NEW}


def test_readers_find_nothing_in_a_trace_without_sub_phases():
    """The identity trace recorded before the program named its
    sub-phases: every sub-phase reader returns None."""
    ctx = traced_ctx(REPO / "chipbench/traces/"
                     "nanogpt-124m.identity.trace.json.gz")
    assert read_all(ctx) == dict.fromkeys(NEW)
    for phase in ("ef21/p2_grads", "ef21/p3_ef_compress",
                  "ef21/p4_wire_recv"):
        assert ctx.per_chip_step_ms(
            lambda ops: trc.phase_ns(ops).get(phase, 0.0)) > 0


def test_recorded_top10_chip_trace():
    """One TPU v5e trace of nanogpt-124m under the top10-natural.2w mix
    (a traced run of chipbench/run.py, cut to the first step and its
    host annotations): every sub-phase reader finds its scope, the TopK
    selection leads the compress, and no phase's sub-phases sum past
    it."""
    ctx = traced_ctx(REPO / "chipbench/traces/"
                     "nanogpt-124m.top10-natural.trace.json.gz")
    got = read_all(ctx)
    assert all(v is not None and v > 0 for v in got.values()), got
    ops = trc.clip(ctx.trace.devices[0], ctx.window)
    ph = trc.phase_ns(ops)
    for phase, names in (("ef21/p2_grads", subphase.P2),
                         ("ef21/p3_ef_compress", subphase.P3),
                         ("ef21/p4_wire_recv", subphase.P4)):
        sub = subphase.subphase_ns(ops, phase, names)
        assert sum(sub.values()) == pytest.approx(ph[phase])
        assert sum(v for k, v in sub.items() if k) <= ph[phase]
    p3 = subphase.subphase_ns(ops, "ef21/p3_ef_compress", subphase.P3)
    assert max(p3, key=lambda k: p3[k]) == "ef.select"
    assert got["phase_ms.p3_select"] > got["phase_ms.p3_ef_update"]
    # the sub-scopes cover most of their phases
    p2_ms = got["phase_ms.p2_attn"] + got["phase_ms.p2_mlp"] \
        + got["phase_ms.p2_vocab"]
    assert p2_ms >= 0.9 * ph["ef21/p2_grads"] / 1e6
    assert got["phase_ms.p3_select"] + got["phase_ms.p3_ef_update"] >= \
        0.8 * ph["ef21/p3_ef_compress"] / 1e6
