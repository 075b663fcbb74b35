#!/usr/bin/env python3
"""Chip benchmark of EF21-Muon training: one cell, one run, one line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a model configuration,
a traffic mix and the chips it needs. The run:

1. set-up (``setup_s``, from the start of the process): finds a TPU
   with enough chips or exits 2 printing no result; makes the weights
   from ``--seed`` on the device with the configuration's plain
   reference, the optimizer state around them and a pool of batches;
   compiles the cell's step (JAX's persistent cache in ``.jax_cache/``
   of the checkout); takes the three checked steps through the same
   compiled step and batches the window uses, reading the state's norms
   after steps 1 and 3;
2. with ``--trace 0``: dispatches steps back to back for ``--seconds``
   (at most four queued, nothing compiles) and ends at
   ``block_until_ready`` on the state: ``tokens_per_s`` is every
   worker's tokens of every step over that time; with ``--trace 1``:
   traces a few steps with the profiler instead and reduces the trace
   to the cell's per-layer metrics;
3. frees the program's state, runs the plain reference over the same
   three steps and compares (``compare.py``) each number with the
   cell's limit.

``step_hbm_gib`` is the most device memory the compiled step holds at
once, by XLA's buffer assignment (``Program.footprint``): the state and
batch it takes, and its activations and other temporaries, each while
it lives. The allocator's ``peak_bytes_in_use`` leaves the step's
temporaries out on the TPU, so it is printed on standard error beside
it and not used.

The last lines on standard error are the numbers compared with their
limits; the last line on standard output is the JSON result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
# libtpu logs to /tmp/tpu_logs unless told otherwise: a fixed path
# outside the checkout that two runs would share
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

from chipbench import compare, data, ef21_reference, program  # noqa: E402
from chipbench import trace_reduce as trc  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

CHECKED_STEPS = 3
# steps queued ahead of the device: the host may stall for up to
# IN_FLIGHT - 1 steps without the device running dry
IN_FLIGHT = 4
# a traced window runs at least this long, in at least MIN_TRACED steps
TRACE_SECONDS = 2.0
MIN_TRACED, MAX_TRACED = 3, 30
TOP = 10


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent cache at a fixed path inside the checkout."""
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def has_chips(devices, n: int) -> bool:
    """JAX found a TPU with at least ``n`` chips."""
    return devices[0].platform == "tpu" and len(devices) >= n


def device_summary(footprint: dict) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": footprint["peak"]}


def memory_line(footprint: dict) -> str:
    """The step's footprint by part, beside the allocator's own
    figures, for standard error."""
    stats = jax.devices()[0].memory_stats() or {}
    alloc = {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                       "bytes_limit")}
    return f"memory: step {footprint} allocator {alloc}"


def check_layout(prog_layout: dict, specs: dict, dtype: str) -> None:
    """The reference's parameters are the program's, path by path."""
    ref = {p: (tuple(s["shape"]), dtype) for p, s in specs.items()}
    if ref != prog_layout:
        only_p = sorted(set(prog_layout) - set(ref))
        only_r = sorted(set(ref) - set(prog_layout))
        diff = sorted(p for p in set(ref) & set(prog_layout)
                      if ref[p] != prog_layout[p])
        raise SystemExit(f"parameter layouts differ: program only "
                         f"{only_p}, reference only {only_r}, shapes "
                         f"{[(p, prog_layout[p], ref[p]) for p in diff]}")


class Ctx:
    """What a per-layer metric reader sees of a traced run."""

    def __init__(self, trace, win, steps, n_chips, tokens_per_step,
                 traffic, specs, flops_per_token, peak):
        self.trace, self.window, self.steps = trace, win, steps
        self.n_chips, self.tokens_per_step = n_chips, tokens_per_step
        self.traffic, self.specs = traffic, specs
        self.flops_per_token, self.peak = flops_per_token, peak

    def device_ops(self) -> dict:
        """{device: operations clipped to the window}."""
        return {d: trc.clip(ops, self.window)
                for d, ops in self.trace.devices.items()}

    def per_chip_step_ms(self, ns_of_ops) -> float:
        """A per-device quantity in ns over the window, as ms per step
        averaged over the chips."""
        ops = self.device_ops()
        total = sum(ns_of_ops(o) for o in ops.values()) / len(ops)
        return total / self.steps / 1e6

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def window_steps(prog, state, pool, seconds: float, start: int):
    """Steps back to back for ``seconds``, at most IN_FLIGHT queued."""
    n, losses = 0, []
    t0 = time.perf_counter()
    while True:
        state, aux = prog.step(state, pool[(start + n) % len(pool)])
        n += 1
        losses.append(aux["loss"])
        if n >= IN_FLIGHT:
            losses[n - IN_FLIGHT].block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(state)
    return state, n, time.perf_counter() - t0, losses


def traced_steps(prog, state, pool, n_steps: int, start: int, out_dir):
    """``n_steps`` steps as in the window, under the profiler."""
    losses = []
    jax.profiler.start_trace(out_dir)
    try:
        for i in range(n_steps):
            with jax.profiler.TraceAnnotation("chipbench/step"):
                state, aux = prog.step(state, pool[(start + i) % len(pool)])
            losses.append(aux["loss"])
            if i + 1 >= IN_FLIGHT:
                with jax.profiler.TraceAnnotation("chipbench/wait"):
                    losses[i + 1 - IN_FLIGHT].block_until_ready()
        with jax.profiler.TraceAnnotation("chipbench/wait"):
            jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    return state, losses


def breakdown(ctx: Ctx) -> dict:
    """The longest device operations (by HLO name with its phase) and
    the longest idle gaps (by host annotation), seconds over the window
    averaged over the chips."""
    ops_by_dev = ctx.device_ops()
    n = len(ops_by_dev)
    by_op: dict = {}
    gaps: dict = {}
    for ops in ops_by_dev.values():
        for s, d, name, ph in trc.phased_leaves(ops):
            key = f"{ph or '-'}:{name}"
            by_op[key] = by_op.get(key, 0.0) + d / 1e9 / n
        for name, ns in trc.idle_gaps(ops, ctx.trace.host, ctx.window):
            gaps[name] = gaps.get(name, 0.0) + ns / 1e9 / n
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"device_ops": top(by_op), "idle_gaps": top(gaps)}


class Run:
    """One seed of a cell: weights, state and batches made from the
    seed, and the checked steps through the compiled step."""

    def __init__(self, prog, family, cell, seed: int):
        self.prog, self.family, self.cell = prog, family, cell
        self.arch, self.traffic = cell.config["arch"], cell.traffic
        key = data.seed_key(seed)
        self.wkey = jax.random.fold_in(key, 0)
        self.pool = data.traffic_batches(
            seed, self.traffic, cell.config["seq"], self.arch["vocab"])
        self.state = prog.init_state(family.init_params(self.arch,
                                                        self.wkey),
                                     jax.random.fold_in(key, 1))
        if prog.executable is None:
            prog.compile(self.state, self.pool[0])

    def checked_steps(self):
        """The first steps, through the window's own call and batches;
        returns the program's readout and the seconds of a step."""
        prog, losses = self.prog, []
        self.state, aux = prog.step(self.state, self.pool[0])
        losses.append(aux["loss"])
        first = program.first_readout(self.state, self.traffic["beta"])
        t0 = time.perf_counter()
        for i in range(1, CHECKED_STEPS):
            self.state, aux = prog.step(self.state, self.pool[i])
            losses.append(aux["loss"])
        jax.block_until_ready(self.state)
        step_s = (time.perf_counter() - t0) / (CHECKED_STEPS - 1)
        x0 = jax.device_put(self.family.init_params(self.arch, self.wkey),
                            jax.tree.map(lambda a: a.sharding,
                                         self.state["x"]))
        last = program.last_readout(self.state, x0)
        del x0
        readout = {"loss": [float(v) for v in losses],
                   "grad": first["grad"], "g_w": first["g_w"],
                   "g_server": first["g_server"], "dx": last["dx"],
                   "agg": [first["agg"], last["agg"]]}
        return readout, step_s

    def reference(self, variant: str = "exact") -> dict:
        """The plain reference over the checked steps, from weights made
        anew from the seed."""
        return ef21_reference.run(
            self.family, self.arch, self.traffic,
            self.family.init_params(self.arch, self.wkey),
            self.pool[:CHECKED_STEPS], CHECKED_STEPS, variant)


def open_cell(root: Path, name: str):
    """(registry, cell, family, program), or None without the chips."""
    reg = Registry(root)
    cell = reg.cell(name)
    devices = jax.devices()
    if not has_chips(devices, cell.chips):
        print(f"chipbench: {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    if cell.chips != 1:
        raise SystemExit(f"chipbench: {cell.name} asks for {cell.chips} "
                         f"chips; the harness drives one")
    enable_compile_cache(root)
    family = reg.reference(cell.config)
    prog = program.Program(ROOT, cell.config, cell.traffic)
    check_layout(prog.param_layout(), family.leaf_specs(cell.config["arch"]),
                 cell.config["arch"]["dtype"])
    return reg, cell, family, prog


def main(argv=None, root: Path = ROOT) -> int:
    args = parse(argv)
    opened = open_cell(root, args.workload)
    if opened is None:
        return 2
    reg, cell, family, prog = opened
    arch, traffic, seq = cell.config["arch"], cell.traffic, \
        cell.config["seq"]
    run = Run(prog, family, cell, args.seed)
    readout, step_s = run.checked_steps()
    setup_s = time.perf_counter() - T_START

    tokens_per_step = traffic["n_workers"] * traffic["tokens_per_worker_step"]
    state, pool = run.state, run.pool
    run.state = None
    result: dict = {}
    if args.trace:
        n_traced = min(MAX_TRACED, max(MIN_TRACED,
                                       math.ceil(TRACE_SECONDS / step_s)))
        out_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            state, wlosses = traced_steps(prog, state, pool, n_traced,
                                          CHECKED_STEPS, out_dir)
            path = glob.glob(f"{out_dir}/**/*.trace.json.gz",
                             recursive=True)[0]
            tr = trc.read_trace(path)
        finally:
            shutil.rmtree(out_dir, ignore_errors=True)
        n_steps = n_traced
    else:
        state, n_steps, elapsed, wlosses = window_steps(
            prog, state, pool, args.seconds, CHECKED_STEPS)
    failed = sum(not math.isfinite(float(v)) for v in wlosses)
    footprint = prog.footprint()
    device = device_summary(footprint)
    print(memory_line(footprint), file=sys.stderr)
    del state, wlosses

    if args.trace:
        win = trc.window(tr)
        ctx = Ctx(tr, win, n_steps, cell.chips, tokens_per_step, traffic,
                  family.leaf_specs(arch),
                  family.train_flops_per_token(arch, seq),
                  reg.peak(device["kind"]))
        metrics = {}
        for m in cell.per_layer:
            v = reg.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        ops = ctx.device_ops()
        device["busy_s"] = sum(trc.busy_ns(o) for o in ops.values()) \
            / len(ops) / 1e9
        device["window_s"] = ctx.window_s()
        result["breakdown"] = breakdown(ctx)
    else:
        metrics = {"tokens_per_s": {"value": n_steps * tokens_per_step
                                    / elapsed, "unit": "tokens/s"},
                   "step_hbm_gib": {"value": footprint["peak"] / 2**30,
                                    "unit": "GiB"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        wanted = {m["name"] for m in cell.end_to_end}
        metrics = {k: v for k, v in metrics.items() if k in wanted}

    correct, checks = compare.judge(
        compare.numbers(readout, run.reference()), cell.limits)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    line = {"correct": correct, "attempted": n_steps, "failed": failed,
            "metrics": metrics, "device": device, **result,
            "checks": checks}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
