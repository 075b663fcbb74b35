"""A new cell needs new files and entries only: in a copy of the
benchmark, a new traffic mix, configuration, per-layer metric and
limits file resolve into a cell without any existing file changing."""
import hashlib
import json
import types

import tiny
from chipbench.registry import Registry


def digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_from_new_files_only(tmp_path):
    root = tiny.make_root(tmp_path)
    before = digests(root)
    cb = root / "chipbench"
    cfg = json.loads((cb / "configs/tiny.json").read_text())
    cfg.update(name="tiny-wide")
    cfg["arch"].update(d_model=128, n_heads=8, n_kv_heads=8)
    (cb / "configs/tiny-wide.json").write_text(json.dumps(cfg))
    tr = json.loads((cb / "traffic/tiny.json").read_text())
    tr.update(w2s="top20", n_workers=3)
    (cb / "traffic/top20.3w.json").write_text(json.dumps(tr))
    (cb / "metrics/steps_per_s.py").write_text(
        "def read(ctx):\n    return ctx.steps / ctx.window_s()\n")
    cell = "tiny-wide.top20"
    (cb / f"limits/{cell}.json").write_text(
        json.dumps({"limits": {"loss": 1e-3}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "test",
                             "file": "chipbench/configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tiny-wide",
                               "traffic": "top20.3w", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps_per_s", "unit": "1/s",
                               "better": "higher",
                               "source": "device_trace",
                               "layer": "trainer step (train/trainer.py)",
                               "moves": "tokens_per_s",
                               "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    changed = {p for p, h in digests(root).items()
               if p in before and before[p] != h}
    assert changed == {type(next(iter(before)))("BENCHMARK.json")}

    reg = Registry(root)
    c = reg.cell(cell)
    assert c.config["arch"]["d_model"] == 128
    assert c.traffic["w2s"] == "top20" and c.traffic["n_workers"] == 3
    assert c.limits == {"loss": 1e-3}
    names = [m["name"] for m in c.per_layer]
    assert "steps_per_s" in names and "mfu" in names
    assert "natural_encode_roofline" not in names   # listed for others
    ctx = types.SimpleNamespace(steps=10, window_s=lambda: 2.0)
    assert reg.metric_reader("steps_per_s")(ctx) == 5.0
    # the existing cells resolve as before
    assert "steps_per_s" not in [m["name"] for m in
                                 reg.cell(tiny.CELL).per_layer]
    fam = reg.reference(c.config)
    assert fam.leaf_specs(c.config["arch"])["embed"]["shape"] == (256, 128)
