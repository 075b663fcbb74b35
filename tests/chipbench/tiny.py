"""A tiny cell for the CPU tests: a copy of the benchmark's files with a
two-layer nanoGPT-shaped configuration and a two-worker top10+natural
mix added, as a later PR would add a cell."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny.top10-natural"
# limits for this size on the CPU, between the readings of sound runs
# of the program (seeds 1-4: loss <= 9.9e-5, grad <= 2.2e-3,
# g_w <= 7.6e-3, g_server <= 4.9e-3, dx <= 2.3e-2, agg <= 3.3e-9) and
# of the control (seeds 1-3: loss >= 4.3e-4, grad >= 1.6e-2,
# g_w >= 2.7e-2, g_server >= 1.8e-2, dx >= 3.0e-2)
LIMITS = {"loss": 2.5e-4, "grad": 6e-3, "g_w": 1.5e-2, "g_server": 1e-2,
          "dx": 6e-2, "agg": 1e-5}
SOUND_SEEDS = (1, 2, 3, 4)


def make_root(tmp: Path, limits: dict | None = None) -> Path:
    """A checkout-like root holding BENCHMARK.json and chipbench/, with
    the tiny cell's files added."""
    root = Path(tmp) / "bench"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads(
        (REPO / "chipbench/configs/nanogpt-124m.json").read_text())
    cfg.update(name="tiny", seq=64)
    cfg["arch"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                       d_ff=256, vocab=256, max_position=64)
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(cfg))
    tr = json.loads(
        (REPO / "chipbench/traffic/top10-natural.2w.json").read_text())
    tr.update(tokens_per_worker_step=128, n_batches=4)
    (root / "chipbench/traffic/tiny.json").write_text(json.dumps(tr))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "tiny", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / f"chipbench/limits/{CELL}.json").write_text(
        json.dumps({"limits": limits or LIMITS}))
    return root
