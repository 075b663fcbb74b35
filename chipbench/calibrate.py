#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 \\
        --first-seed <n> [--variant-seeds 3] [--out <file.json>]

For each of ``--seeds`` seeds it drives the cell as ``run.py`` does (the
same compiled step, weights and batches from the seed, the checked
steps) and compares the program with the plain reference: the lower
readings. For the first ``--variant-seeds`` of them it also puts the
reference's variants in the program's place (``ef21_reference``): the
control (one precision step lower) and the planted faults (half the
batch; one entry of the produced parameters altered): the upper
readings. A state left unchanged
reads 1 on every norm gap and needs no run.

``proposed_limits`` applies the rule that sets a limit from the two
readings; the limits file records the readings and the limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import compare, run  # noqa: E402

# a fault counts against a number where it reads this many times the
# lower reading (a state left unchanged: UNCHANGED_FACTOR)
FAULT_FACTOR, UNCHANGED_FACTOR = 10.0, 3.0
# the control counts where it reads this many times the lower reading
CONTROL_FACTOR = 3.0
# where the limit sits between lower L and upper U, in log space from L
LIMIT_POSITION = 0.6


def proposed_limits(lower: dict, control: dict, faults: dict) -> dict:
    """{number: {"lower", "upper", "limit", "by"}} for each number with
    an upper reading: the smallest of the control's (where it is at
    least CONTROL_FACTOR x the lower) and each fault's (where at least
    FAULT_FACTOR x); the limit at LIMIT_POSITION of the way from lower
    to upper in log space, so more room lies above the lower."""
    out = {}
    for name, lo in lower.items():
        ups = []
        if control.get(name, 0.0) >= CONTROL_FACTOR * lo:
            ups.append((control[name], "control"))
        for fault, vals in faults.items():
            f = UNCHANGED_FACTOR if fault == "unchanged" else FAULT_FACTOR
            if vals.get(name, 0.0) >= f * lo:
                ups.append((vals[name], fault))
        if not ups or lo <= 0:
            out[name] = {"lower": lo, "upper": None, "limit": None,
                         "by": None}
            continue
        up, by = min(ups)
        lim = math.exp(math.log(lo) + LIMIT_POSITION
                       * (math.log(up) - math.log(lo)))
        out[name] = {"lower": lo, "upper": up, "limit": float(f"{lim:.3g}"),
                     "by": by}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--variant-seeds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    opened = run.open_cell(ROOT, args.workload)
    if opened is None:
        return 2
    reg, cell, family, prog = opened
    variants = ["lowp", "half_batch", "altered"]
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + i
        t0 = time.perf_counter()
        r = run.Run(prog, family, cell, seed)
        readout, step_s = r.checked_steps()
        r.state = None
        exact = r.reference()
        row = {"seed": seed, "step_s": step_s,
               "program": compare.numbers(readout, exact),
               "losses": readout["loss"]}
        if i < args.variant_seeds:
            for v in variants:
                row[v] = compare.numbers(r.reference(v), exact)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = list(rows[0]["program"])
    lower = {n: max(r["program"][n] for r in rows) for n in names}
    vrows = [r for r in rows if "lowp" in r]
    control = {n: min(r["lowp"][n] for r in vrows) for n in names}
    faults = {v: {n: min(r[v][n] for r in vrows) for n in names}
              for v in variants if v != "lowp"}
    # a state left unchanged: every norm gap reads 1 (no gradient, no
    # estimate, no change), by the comparison's own measure
    faults["unchanged"] = {n: 1.0 for n in ("grad", "g_w", "g_server",
                                            "dx")}
    summary = {"workload": cell.name, "seeds": [r["seed"] for r in rows],
               "lower": lower, "control_min": control, "fault_min": faults,
               "proposed": proposed_limits(lower, control, faults),
               "seconds": time.perf_counter() - T_START}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows,
                                              "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
