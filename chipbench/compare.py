"""The comparison that decides ``correct``.

A run's readout (``ef21_reference.run`` gives the same keys for the
reference) is compared with the reference's as numbers, each against a
limit of its own from the cell's limits file:

- ``loss``: the largest |L - L_ref| / |L_ref| over the checked steps;
  ``loss1`` the same at the first step alone, from the same weights on
  both sides (the later steps' losses also carry every sign the sign
  LMO drew from a gradient entry near 0);
- ``grad``, ``g_w``, ``g_server``: after step 1, the gradient each
  worker computed, each worker's EF21 estimate and the server's
  aggregate, by the worst leaf: |norm - norm_ref| over the larger of the
  reference leaf's norm and the median leaf's;
- ``dx``: the same for |X - X0| after the last checked step, over the
  leaves whose reference gradient is at least a thousandth of the
  median leaf's (the others move by round-off alone);
- ``agg``: |G - mean_j G_j| / |G|, which EF21 keeps at rounding: the
  workers' messages as the server received them, against the workers'
  own estimates.
"""
from __future__ import annotations

import math
import statistics

# reference gradient under this share of the median leaf's: the leaf
# moves by round-off alone and is left out of ``dx``
STILL_LEAF = 1e-3


def _worst(prog: dict, ref: dict, leaves=None) -> float:
    """Worst leaf of |p - r| / max(r, median r), with a leading worker
    list allowed on each leaf."""
    def cols(d):
        v = next(iter(d.values()))
        n = len(v) if isinstance(v, list) else 0
        if not n:
            return [{p: x for p, x in d.items()}]
        return [{p: x[j] for p, x in d.items()} for j in range(n)]

    worst = 0.0
    for cp, cr in zip(cols(prog), cols(ref)):
        med = statistics.median(cr.values())
        for p, r in cr.items():
            if leaves is not None and p not in leaves:
                continue
            worst = max(worst, abs(cp[p] - r) / max(r, med))
    return worst


def moving_leaves(ref: dict) -> set:
    """Leaves whose worker-mean reference gradient norm is at least
    ``STILL_LEAF`` of the median leaf's."""
    g = {p: sum(v) / len(v) for p, v in ref["grad"].items()}
    med = statistics.median(g.values())
    return {p for p, v in g.items() if v >= STILL_LEAF * med}


def numbers(prog: dict, ref: dict) -> dict:
    """Every number compared, by name."""
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])]
    return {"loss": max(gaps), "loss1": gaps[0],
            "grad": _worst(prog["grad"], ref["grad"]),
            "g_w": _worst(prog["g_w"], ref["g_w"]),
            "g_server": _worst(prog["g_server"], ref["g_server"]),
            "dx": _worst(prog["dx"], ref["dx"], moving_leaves(ref)),
            "agg": max(prog["agg"])}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers that have
    a limit. A number that is not finite fails, and is given as null."""
    checks = {}
    ok = True
    for name, lim in limits.items():
        v = values[name]
        ok = ok and math.isfinite(v) and v <= lim
        checks[name] = {"value": v if math.isfinite(v) else None,
                        "limit": lim}
    return ok, checks
