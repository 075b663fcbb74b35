"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of
its own, found by name, and so does each per-layer metric's reader and
each cell's limits:

    chipbench/configs/<config>.json      sizes, source, cuts, ``arch``
    chipbench/traffic/<traffic>.json     workers, compressor, tokens
    chipbench/limits/<cell>.json         the limit of each number compared
    chipbench/metrics/<metric>.py        ``read(ctx)`` -> number or None
    chipbench/references/<name>.py       plain reference of a model family

Adding a cell, a configuration, a mix or a metric is adding files and
entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict
    end_to_end: list
    per_layer: list


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = _load_json(self.root / "BENCHMARK.json")
        self.dir = self.root / "chipbench"

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in self.bench["configs"]}
        config = _load_json(self.root / configs[w["config"]]["file"])
        traffic = _load_json(self.dir / "traffic" / f"{w['traffic']}.json")
        limits = _load_json(self.dir / "limits" / f"{name}.json")["limits"]
        e2e = [m for m in self.bench["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        per_layer = [m for m in self.bench["per_layer"]
                     if name in m.get("workloads", [name])
                     and m["moves"] in reported]
        return Cell(name, config, traffic, w["chips"], limits, e2e,
                    per_layer)

    def reference(self, config: dict):
        """The plain reference module the configuration names."""
        name = config["reference"]
        return load_module(self.dir / "references" / f"{name}.py",
                           f"chipbench_reference_{name}")

    def metric_reader(self, name: str):
        return load_module(self.dir / "metrics" / f"{name}.py",
                           f"chipbench_metric_{name}").read

    def peak(self, device_kind: str) -> dict:
        peaks = _load_json(self.dir / "peaks.json")["devices"]
        if device_kind not in peaks:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"chipbench/peaks.json; have {sorted(peaks)}")
        return peaks[device_kind]
