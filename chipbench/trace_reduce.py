"""Reduces a profiler trace to the numbers the per-layer metrics read.

``read_trace`` turns the ``*.trace.json.gz`` that the JAX profiler
writes beside its ``.xplane.pb`` into a plain ``Trace``: for each TPU
device, the operations of its "XLA Ops" line (start, duration, HLO
name, and the ``tf_op`` op name that carries the program's named
scopes), and the host's annotations on the same clock. The rest works
on that plain form, so tests can build one by hand:

- ``busy_intervals``: the union of a device's operation intervals;
- ``leaf_ops``: the operations that contain no other (a device line may
  nest a loop's body inside the loop), so that no time counts twice;
- ``phase_ns``: device time per ``ef21/...`` phase, each operation under
  the first phase its op name carries; an operation that carries none
  (XLA gives the operations it makes itself, such as the pieces of a
  sort, no op name) takes the phase of the operations on both sides of
  it where they agree, and is unattributed otherwise;
- ``idle_gaps``: the gaps between busy intervals, each named by the
  innermost host annotation open at its middle.
"""
from __future__ import annotations

import gzip
import json
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PHASE = re.compile(r"ef21/p\d_[a-z0-9_]+")
# the event argument that holds the framework op name (named scopes)
OP_NAME_KEY = "tf_op"


@dataclass
class Trace:
    # device id -> [(start_ns, dur_ns, hlo name, op name)]
    devices: dict = field(default_factory=dict)
    # [(start_ns, dur_ns, annotation name)]
    host: list = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps({"devices": {str(k): v
                                       for k, v in self.devices.items()},
                           "host": self.host})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls({int(k): [tuple(e) for e in v]
                    for k, v in d["devices"].items()},
                   [tuple(e) for e in d["host"]])


def read_trace(path: str, host_prefixes=("chipbench/",)) -> Trace:
    """The operations of every TPU device's "XLA Ops" line, and the host
    annotations whose names start with one of ``host_prefixes``; times
    in ns."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    tr = Trace()
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        m = DEVICE_PLANE.match(proc)
        start, dur = e["ts"] * 1e3, e.get("dur", 0.0) * 1e3
        if m and threads.get((e["pid"], e["tid"])) == OPS_LINE:
            op = e.get("args", {}).get(OP_NAME_KEY, "")
            tr.devices.setdefault(int(m.group(1)), []).append(
                (start, dur, e["name"], op))
        elif proc.startswith("/host:") and e["name"].startswith(
                host_prefixes):
            tr.host.append((start, dur, e["name"]))
    for ops in tr.devices.values():
        ops.sort()
    tr.host.sort()
    return tr


def window(tr: Trace, step_name: str = "chipbench/step") -> tuple:
    """[first step annotation's start, last device operation's end]."""
    starts = [s for s, _, n in tr.host if n == step_name]
    if not starts:
        raise ValueError(f"no {step_name!r} annotation in the trace")
    ends = [s + d for ops in tr.devices.values() for s, d, *_ in ops]
    if not ends:
        raise ValueError("no device operation in the trace")
    return min(starts), max(ends)


def clip(ops: list, win: tuple) -> list:
    """The operations inside the window, cut at its edges."""
    lo, hi = win
    out = []
    for s, d, *rest in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b - a, *rest))
    return out


def busy_intervals(ops: list) -> list:
    """Merged [start, end) intervals in which some operation ran."""
    merged: list = []
    for s, d, *_ in sorted(ops):
        e = s + d
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(ops: list) -> float:
    return sum(e - s for s, e in busy_intervals(ops))


def leaf_ops(ops: list) -> list:
    """Operations that contain no other operation of the same device."""
    order = sorted(ops, key=lambda o: (o[0], -o[1]))
    leaves = []
    stack: list = []          # open operations: [op, has_child]
    for op in order:
        s = op[0]
        while stack and s >= stack[-1][0][0] + stack[-1][0][1]:
            done, has_child = stack.pop()
            if not has_child:
                leaves.append(done)
        if stack and s + op[1] <= stack[-1][0][0] + stack[-1][0][1]:
            stack[-1][1] = True
        stack.append([op, False])
    while stack:
        done, has_child = stack.pop()
        if not has_child:
            leaves.append(done)
    return sorted(leaves)


def phase_of(op_name: str) -> str | None:
    m = PHASE.search(op_name)
    return m.group(0) if m else None


def phased_leaves(ops: list) -> list:
    """The leaf operations as (start, dur, HLO name, phase or None): an
    operation without a phase of its own takes that of its nearest
    phased neighbours when both carry the same one."""
    leaves = leaf_ops(ops)
    own = [phase_of(op) for *_, op in leaves]
    prev, nxt = [None] * len(own), [None] * len(own)
    last = None
    for i, ph in enumerate(own):
        last = ph or last
        prev[i] = last
    last = None
    for i in range(len(own) - 1, -1, -1):
        last = own[i] or last
        nxt[i] = last
    return [(s, d, name, own[i] or (prev[i] if prev[i] == nxt[i] else None))
            for i, (s, d, name, _) in enumerate(leaves)]


def phase_ns(ops: list) -> dict:
    """{phase or None: summed duration} over the leaf operations; the
    values add up to the leaves' total."""
    out: dict = {}
    for s, d, name, ph in phased_leaves(ops):
        out[ph] = out.get(ph, 0.0) + d
    return out


def kernel_ns(ops: list, kernel: str, phase: str | None = None) -> float:
    """Summed duration of the leaf operations whose HLO name carries
    ``kernel`` (a Pallas kernel's ``name=``), within ``phase`` where one
    is given."""
    return sum(d for s, d, name, ph in phased_leaves(ops)
               if kernel in name and (phase is None or ph == phase))


def idle_gaps(ops: list, host: list, win: tuple) -> list:
    """[(name, ns)] of the gaps in the window where the device ran
    nothing, named by the innermost host annotation at the gap's middle
    ("idle" where none is open), longest first."""
    lo, hi = win
    edges = [lo] + [x for iv in busy_intervals(ops) for x in iv] + [hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        open_ = [(s, n) for s, d, n in host if s <= mid <= s + d]
        name = max(open_)[1] if open_ else "idle"
        gaps.append((name, b - a))
    return sorted(gaps, key=lambda g: -g[1])
