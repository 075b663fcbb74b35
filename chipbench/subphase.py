"""Device time of the sub-phases inside one phase of the step.

The program names one level of scopes below its ``ef21/...`` phases
(``trace_spans=True``): ``lm.attn``, ``lm.mlp`` and ``lm.vocab`` inside
the gradients phase, ``ef.select`` and ``ef.update`` inside the
compress, ``ef.decompress`` inside the receive. ``subphase_ns`` splits
a phase's time among such names over the same leaf operations that
``trace_reduce.phased_leaves`` puts in the phase, so its values add up
to ``trace_reduce.phase_ns(ops)[phase]``:

- an operation goes to the first name found as a whole path segment of
  its op name (delimited by the start, ``/``, ``(``, ``)``, ``:`` or
  the end: a backward op's ``transpose(jvp(lm.attn))`` counts, a
  ``select_n`` never counts for ``select``);
- an operation without an op name takes the sub-phase of its nearest
  named neighbours in the phase where both agree, as
  ``phased_leaves`` does for phases;
- anything else goes under ``None``.
"""
from __future__ import annotations

import re

from chipbench import trace_reduce as trc

# each phase's sub-scopes, as the program names them
P2 = ("lm.attn", "lm.mlp", "lm.vocab")
P3 = ("ef.select", "ef.update")
P4 = ("ef.decompress",)


def _segment(name: str):
    """A pattern that finds ``name`` as a whole path segment."""
    return re.compile(r"(?:^|[/(])" + re.escape(name) + r"(?=$|[/):])")


def subphase_ns(ops: list, phase: str, names) -> dict:
    """{name or None: summed duration} of the leaf operations in
    ``phase``."""
    pats = [(n, _segment(n)) for n in names]
    inside = [(d, op) for (_, d, _, op), (*_, ph) in
              zip(trc.leaf_ops(ops), trc.phased_leaves(ops))
              if ph == phase]
    own = [next((n for n, p in pats if p.search(op)), None)
           for _, op in inside]
    prev, nxt = [None] * len(inside), [None] * len(inside)
    last = None
    for i, (_, op) in enumerate(inside):
        prev[i] = last
        if op:
            last = own[i]
    last = None
    for i in range(len(inside) - 1, -1, -1):
        nxt[i] = last
        if inside[i][1]:
            last = own[i]
    out: dict = {}
    for i, (d, op) in enumerate(inside):
        sub = own[i] if op else (prev[i] if prev[i] == nxt[i] else None)
        out[sub] = out.get(sub, 0.0) + d
    return out


def read_ms(ctx, phase: str, names, name: str):
    """ms per step of ``name`` in ``phase``, averaged over the chips;
    None where no operation of the window carries it."""
    ms = ctx.per_chip_step_ms(
        lambda ops: subphase_ns(ops, phase, names).get(name, 0.0))
    return ms or None
