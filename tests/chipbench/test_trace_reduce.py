"""The reduction from a device trace to the per-layer numbers, on a
hand-built trace whose answers are known."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as trc

P2 = "jit(step)/ef21/p2_grads/vmap(jvp())/dot_general"
P3 = "jit(step)/ef21/p3_ef_compress/top_k"
P5 = "jit(step)/ef21/p5_lmo/pallas_call"


def hand_trace():
    # device 0: a loop op holding two ops, a gap, a compress op, a
    # gap, the LMO kernel; times in ns
    dev0 = [(100.0, 300.0, "while.1", P2),
            (100.0, 100.0, "fusion.1", P2),
            (250.0, 150.0, "convolution.2", P2),
            (500.0, 200.0, "sort.3", P3),
            (900.0, 100.0, "ns_iteration_fused.4", P5),
            (1000.0, 50.0, "copy.5", "jit(step)/copy")]
    # device 1: a second chip
    dev1 = [(100.0, 200.0, "fusion.9", P2),
            (200.0, 200.0, "all-gather-done.1", "jit(step)/wire/w2s"),
            (600.0, 100.0, "all-reduce.2", "jit(step)/x")]
    host = [(50.0, 60.0, "chipbench/step"),
            (400.0, 600.0, "chipbench/wait"),
            (700.0, 50.0, "chipbench/step")]
    return trc.Trace({0: dev0, 1: dev1}, host)


def test_busy_is_the_union_of_intervals():
    tr = hand_trace()
    assert trc.busy_intervals(tr.devices[0]) == [[100.0, 400.0],
                                                 [500.0, 700.0],
                                                 [900.0, 1050.0]]
    assert trc.busy_ns(tr.devices[0]) == 650.0


def test_leaf_ops_drop_the_enclosing_loop():
    leaves = trc.leaf_ops(hand_trace().devices[0])
    assert [o[2] for o in leaves] == ["fusion.1", "convolution.2", "sort.3",
                                      "ns_iteration_fused.4", "copy.5"]


def test_phases_add_up_to_the_device_time():
    ops = hand_trace().devices[0]
    ph = trc.phase_ns(ops)
    assert ph == {"ef21/p2_grads": 250.0, "ef21/p3_ef_compress": 200.0,
                  "ef21/p5_lmo": 100.0, None: 50.0}
    assert sum(ph.values()) == sum(o[1] for o in trc.leaf_ops(ops))


def test_an_op_without_a_name_takes_its_neighbours_phase():
    ops = [(0.0, 10.0, "sort.1", P3), (10.0, 5.0, "fusion.2", ""),
           (15.0, 10.0, "sort.3", P3), (25.0, 5.0, "copy.4", ""),
           (30.0, 10.0, "ns.5", P5)]
    assert trc.phase_ns(ops) == {"ef21/p3_ef_compress": 25.0,
                                 None: 5.0, "ef21/p5_lmo": 10.0}


def test_each_op_counts_under_one_phase():
    assert trc.phase_of("a/ef21/p4_wire_recv/wire/w2s/stage0/"
                        "ef21/p5_lmo/x") == "ef21/p4_wire_recv"
    assert trc.phase_of("jit(step)/copy") is None


def test_kernel_time_by_name():
    ops = hand_trace().devices[0]
    assert trc.kernel_ns(ops, "ns_iteration_fused") == 100.0
    assert trc.kernel_ns(ops, "natural_encode") == 0.0


def test_idle_gaps_are_named_by_the_host():
    tr = hand_trace()
    win = trc.window(tr)
    assert win == (50.0, 1050.0)
    gaps = trc.idle_gaps(tr.devices[0], tr.host, win)
    assert gaps == [("chipbench/wait", 200.0), ("chipbench/wait", 100.0),
                    ("chipbench/step", 50.0)]
    assert sum(g for _, g in gaps) + trc.busy_ns(tr.devices[0]) == \
        win[1] - win[0]


def test_clip_cuts_at_the_window():
    ops = [(0.0, 100.0, "a", ""), (90.0, 20.0, "b", ""),
           (200.0, 5.0, "c", "")]
    assert trc.clip(ops, (50.0, 100.0)) == [(50.0, 50.0, "a", ""),
                                            (90.0, 10.0, "b", "")]


def test_round_trip_through_json():
    tr = hand_trace()
    back = trc.Trace.from_json(tr.to_json())
    assert back.devices == tr.devices and back.host == tr.host


def test_window_needs_a_step_annotation():
    with pytest.raises(ValueError, match="annotation"):
        trc.window(trc.Trace({0: [(0.0, 1.0, "a", "")]}, []))


RECORDED = (Path(__file__).resolve().parents[2]
            / "chipbench/traces/nanogpt-124m.identity.trace.json.gz")


def test_recorded_chip_trace():
    """One TPU v5e trace of nanogpt-124m under the identity.2w mix (a
    traced run of chipbench/run.py, cut to the first step and its host
    annotations): the reader finds the device, the phases and the
    kernels, and the sums close."""
    tr = trc.read_trace(str(RECORDED))
    assert list(tr.devices) == [0]
    win = trc.window(tr)
    ops = trc.clip(tr.devices[0], win)
    ph = trc.phase_ns(ops)
    assert {"ef21/p2_grads", "ef21/p3_ef_compress", "ef21/p4_wire_recv",
            "ef21/p5_lmo"} <= set(ph)
    assert max(ph, key=lambda k: ph[k] if k else 0) == "ef21/p2_grads"
    leaves = trc.leaf_ops(ops)
    assert sum(ph.values()) == pytest.approx(sum(o[1] for o in leaves))
    assert ph.get(None, 0.0) < 0.05 * sum(ph.values())
    busy = trc.busy_ns(ops)
    assert sum(o[1] for o in leaves) <= busy <= win[1] - win[0]
    gaps = trc.idle_gaps(ops, tr.host, win)
    assert sum(g for _, g in gaps) + busy == pytest.approx(win[1] - win[0])
    assert trc.kernel_ns(ops, "ns_iteration_fused") > 0
    assert trc.kernel_ns(ops, "natural_encode") == 0
