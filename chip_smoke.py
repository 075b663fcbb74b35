#!/usr/bin/env python3
"""Smoke run of the EF21-Muon training step on a TPU, at full width.

    python chip_smoke.py              # one chip: identity vs top10+natural
    python chip_smoke.py --chips 4    # four chips: the packed u8 wire on a
                                      # (4, 1) mesh vs the same steps on
                                      # one device

It trains nanogpt-124m at its published widths (12 layers, d_model 768,
vocab 50304, seq 1024, tied embeddings) from random weights made from
``--seed``, through ``Trainer`` and ``SyntheticLM``, with the kernel
choice the trainer resolves itself (the Pallas kernels on a TPU). It runs
in this one process and starts no other. Without a TPU it exits non-zero
before any phase.

One chip, for each arm: compile the step at a per-worker batch chosen
from the compiled step's ``memory_analysis()`` (4, halved until it fits),
take 3 warm-up and 5 timed steps, print the losses, compile and step
seconds, tokens/s, the process's peak device memory so far and whether
the compiled step holds a Pallas kernel (``tpu_custom_call``). Then take
one step from the same state with ``use_pallas=False`` and check the new
``x`` against the kernel path's: the kernels that touch the payload
(Natural encode, the bit-pack and narrow codecs) are bit-exact, so the
two differ only by the Newton-Schulz matmul precision, which moves an
entry of the bf16 ``x`` by at most a rounding flip. The bound ``X_TOL``
is two bf16 ulps of |x| < 0.25 and a fifth of the 2t jump of a sign
entry gone wrong. A spectral entry moves by about t / sqrt(768), below
one ulp, so the Newton-Schulz kernel is also checked on its own: on the
server's LMO inputs of that step, one stack per slice shape, its output
against the jnp chain's in relative Frobenius norm (``NS_RTOL``: rounding
of the five iterations sits far below it, a wrong tile of 24 far above).

Four chips: 4 workers of ``top10+natural`` on ``data``, a few steps on
the packed u8 wire, against the same 4-worker steps with ``mesh=None``
on device 0. Checked:

- the wire, in each program on its own: EF21 keeps the server's
  aggregate equal to the mean of the workers' estimates, so
  |g_server - mean_w g_w| / |g_server| stays at f32 rounding
  (``AGG_RTOL``). A worker's message lost or misplaced on the wire moves
  it by that worker's share (printed beside it);
- the losses of the two programs, step by step (``LOSS_RTOL``);
- the two ``x`` trajectories, per LMO kind: |x_mesh - x_one| over how
  far ``x`` moved, against a bound for that kind (``TRAJ_RTOL``).

The two programs batch the worker gradients differently and so differ by
rounding, which at the published bf16 moves TopK's kept set: an entry
within an ulp of the threshold may be kept by one program and dropped by
the other. Newton-Schulz, which sends every singular value towards 1,
magnifies such a change of its input. The first step's kept sets, server
aggregates and Newton-Schulz outputs of the two programs are printed to
show where the trajectories part. A negative control gives worker 1
worker 2's batch, so that worker 1 sends worker 2's message, and
requires the trajectory bounds to refuse it.

Any failed phase exits non-zero; the last line of a good run is the JSON
object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "nanogpt-124m"
ARMS = ("identity", "top10+natural")
# LMO radius t of every step; at 0.02 the loss of this model climbs
# within a few steps (sign steps of the tied embedding), at 0.005 it falls
RADIUS = 0.005
WARMUP, TIMED = 3, 5
MESH_STEPS = 3
# kernel vs reference: max |x_kernel - x_ref| and the Newton-Schulz
# output's relative Frobenius distance
X_TOL = 2e-3
NS_RTOL = 0.05
# four chips vs one device: per LMO kind, the bound on
# |x_mesh - x_one| / |x_one - x0| after MESH_STEPS steps, twice the
# readings on v5e (sign 0.0752, spectral 0.4252)
MESH_ARM = "top10+natural"
TRAJ_RTOL = {"sign": 0.15, "spectral": 0.6}
LOSS_RTOL = 2e-3
AGG_RTOL = 1e-5


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_summary() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu(n_chips: int) -> None:
    dev = device_summary()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev['platform']}); "
              f"this script only runs on the chip", file=sys.stderr)
        sys.exit(2)
    if dev["count"] < n_chips:
        print(f"chip_smoke: need {n_chips} TPU chips, JAX sees "
              f"{dev['count']}", file=sys.stderr)
        sys.exit(2)


def structs(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def max_abs_diff(a, b) -> tuple[float, str]:
    """Largest |a - b| over the leaves of two equal trees, and its leaf."""
    from repro.obs.metrics import leaf_names

    diffs = [float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                   - y.astype(jnp.float32))))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    i = int(np.argmax(diffs))
    return diffs[i], leaf_names(a)[i]


def tree_dist(a, b=None) -> float:
    """|a - b| (|a| without ``b``) over host copies of two equal trees."""
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b) if b is not None else [0.0] * len(la)
    return math.sqrt(sum(
        float(np.sum(np.square(np.asarray(x, np.float64)
                               - np.asarray(y, np.float64))))
        for x, y in zip(la, lb)))


def footprint(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            + ma.generated_code_size_in_bytes)


def fit_step(tr, state, cfg, n_workers: int, seq: int, seed: int,
             budget: int, start: int = 4):
    """Compile ``tr``'s step at the largest per-worker batch, from
    ``start`` halving, whose compiled footprint fits ``budget`` bytes.
    Returns (per-worker batch, jitted batch maker, compiled, seconds)."""
    from repro.configs.base import ShapeSpec
    from repro.data import SyntheticLM

    b = start
    while True:
        data = SyntheticLM(cfg, ShapeSpec("smoke", "train", seq,
                                          n_workers * b),
                           n_workers=n_workers, seed=seed)
        batch_at = jax.jit(data.batch_at)
        bstruct = jax.eval_shape(data.batch_at, 0)
        t0 = time.perf_counter()
        compiled = tr.jit_step(bstruct).lower(
            structs(state), bstruct, jnp.float32(RADIUS)).compile()
        secs = time.perf_counter() - t0
        need = footprint(compiled)
        print(f"  per-worker batch {b}: step footprint {need / 2**30:.2f} "
              f"GiB of {budget / 2**30:.2f} GiB budget "
              f"(compile {secs:.1f} s)", flush=True)
        if need <= budget:
            return b, batch_at, compiled, secs
        check(b > 1, "the step does not fit at per-worker batch 1")
        b //= 2


def spectral_stacks(tr, tree) -> dict:
    """The spectral leaves of ``tree`` as one [stack, m, n] array per
    slice shape (m <= n, as the bucketed LMO orients it)."""
    plan = tr.layer_plan()
    stacks: dict = {}
    for lp, g in zip(plan.leaves, plan.flatten(tree)):
        if lp.meta.lmo == "spectral":
            g = jnp.asarray(g).reshape((-1,) + lp.slice_shape)
            if g.shape[1] > g.shape[2]:
                g = jnp.swapaxes(g, 1, 2)
            stacks.setdefault(g.shape[1:], []).append(g)
    return {s: jnp.concatenate(gs) for s, gs in stacks.items()}


def ns(g, use_pallas: bool):
    from repro.kernels import newton_schulz_batched

    return jax.jit(functools.partial(newton_schulz_batched,
                                     use_pallas=use_pallas))(g)


def rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def check_ns(tr, g_server) -> None:
    """The batched Newton-Schulz kernel against the jnp chain on the
    server's LMO inputs."""
    for shape, g in spectral_stacks(tr, g_server).items():
        r = rel(ns(g, True), ns(g, False))
        print(f"  Newton-Schulz kernel vs reference on {tuple(g.shape)}: "
              f"relative Frobenius {r}, tolerance {NS_RTOL}", flush=True)
        check(r <= NS_RTOL, f"Newton-Schulz kernel differs from the "
              f"reference by {r} > {NS_RTOL} on slices {shape}")


def run_arm(model, cfg, arm: str, seq: int, seed: int, budget: int,
            n_workers: int = 2) -> dict:
    from repro.train.trainer import Trainer, TrainerConfig

    print(f"== arm {arm}: {n_workers} workers", flush=True)
    tcfg = TrainerConfig(n_workers=n_workers, w2s=arm, radius=RADIUS)
    tr = Trainer(model, tcfg)
    state = tr.init(jax.random.key(seed))
    n_params = sum(x.size for x in jax.tree.leaves(state["x"]))
    print(f"  device {jax.devices()[0].device_kind}  params {n_params}  "
          f"use_pallas {tr.opt.cfg.use_pallas}", flush=True)
    b, batch_at, step, compile_s = fit_step(tr, state, cfg, n_workers, seq,
                                            seed, budget)
    kernels = "tpu_custom_call" in step.as_text()
    print(f"  compiled step holds tpu_custom_call: {kernels}", flush=True)
    check(kernels, f"{arm}: no Pallas kernel in the compiled step")
    t = jnp.float32(RADIUS)
    batches = [batch_at(i) for i in range(WARMUP + TIMED + 1)]
    jax.block_until_ready(batches)

    for i in range(WARMUP):
        state, aux = step(state, batches[i], t)
        loss = float(aux["loss"])
        print(f"  warm-up step {i}: loss {loss}", flush=True)
        check(math.isfinite(loss), f"{arm}: non-finite loss at step {i}")
    losses = []
    t0 = time.perf_counter()
    for i in range(WARMUP, WARMUP + TIMED):
        state, aux = step(state, batches[i], t)
        losses.append(aux["loss"])
    jax.block_until_ready(state)
    step_s = (time.perf_counter() - t0) / TIMED
    for i, loss in zip(range(WARMUP, WARMUP + TIMED), losses):
        print(f"  timed step {i}: loss {float(loss)}", flush=True)
        check(math.isfinite(float(loss)),
              f"{arm}: non-finite loss at step {i}")
    tokens_s = n_workers * b * seq / step_s
    # a peak over the whole process: it covers every arm run before
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"  compile_s {compile_s}  step_s {step_s}  tokens/s {tokens_s}  "
          f"process peak_bytes_in_use so far {peak}", flush=True)

    # the same step from the same state through the jnp references
    ref = Trainer(model, dataclasses.replace(tcfg, use_pallas=False))
    ref_step = ref.jit_step(None).lower(
        structs(state), structs(batches[-1]), t).compile()
    check("tpu_custom_call" not in ref_step.as_text(),
          f"{arm}: the reference step holds a Pallas kernel")
    new_k, aux_k = step(state, batches[-1], t)
    x_k = new_k["x"]
    del new_k
    new_r, aux_r = ref_step(state, batches[-1], t)
    diff, leaf = max_abs_diff(x_k, new_r["x"])
    print(f"  kernel vs reference: max |dx| {diff} (leaf {leaf}), "
          f"tolerance {X_TOL}; loss {float(aux_k['loss'])} vs "
          f"{float(aux_r['loss'])}", flush=True)
    check(diff <= X_TOL, f"{arm}: kernel path x differs from the reference "
          f"by {diff} > {X_TOL} on {leaf}")
    check_ns(tr, new_r["g_server"])
    return {"arm": arm, "per_worker_batch": b, "compile_s": compile_s,
            "step_s": step_s, "tokens_s": tokens_s, "process_peak": peak,
            "x_diff": diff}


def u8_all_gathers(hlo_text: str) -> list[int]:
    """Operand bytes of every u8 all-gather (the packed wire buffers) in
    compiled HLO, as the wire lint counts them."""
    from repro.analysis.hlo_ir import parse_module
    from repro.analysis.rules import entry_u8_pairs

    return [int(p["bytes"]) for p in entry_u8_pairs(parse_module(hlo_text))
            if p["kind"] == "all-gather"]


@jax.jit
def aggregate_gap(state):
    """(|g_server - mean_w g_w|, |g_w[1]| / n_workers), each over
    |g_server|: the first is the wire's error, the second what losing
    worker 1's messages would have made it."""
    gs = jax.tree.leaves(state["g_server"])
    gw = jax.tree.leaves(state["g_w"])
    den = sum(jnp.sum(jnp.square(s)) for s in gs)
    gap = sum(jnp.sum(jnp.square(s - jnp.mean(w, axis=0)))
              for s, w in zip(gs, gw))
    lost = sum(jnp.sum(jnp.square(w[1] / w.shape[0])) for w in gw)
    return jnp.sqrt(gap / den), jnp.sqrt(lost / den)


def trajectory(tr, step, key, batches, st_put=None, b_put=None) -> dict:
    """MESH_STEPS steps from ``tr.init(key)``: losses, x before and
    after, the first step's server aggregate and worker estimates, and
    the aggregate gap after the last step (host copies)."""
    t = jnp.float32(RADIUS)
    state = tr.init(key)
    out = {"x0": jax.device_get(state["x"]), "losses": []}
    if st_put is not None:
        state = jax.device_put(state, st_put)
    for i, batch in enumerate(batches):
        if b_put is not None:
            batch = jax.device_put(batch, b_put)
        state, aux = step(state, batch, t)
        out["losses"].append(float(aux["loss"]))
        if i == 0:
            out["first"] = jax.device_get({k: state[k]
                                           for k in ("g_server", "g_w")})
    out["x"] = jax.device_get(state["x"])
    out["gap"], out["lost"] = (float(v) for v in aggregate_gap(state))
    return out


def by_kind(tr, tree) -> dict:
    plan = tr.layer_plan()
    kinds: dict = {}
    for lp, a in zip(plan.leaves, plan.flatten(tree)):
        kinds.setdefault(lp.meta.lmo, []).append(a)
    return kinds


def traj_rel(tr, run, ref) -> dict:
    """Per LMO kind: |x_run - x_ref| / |x_ref - x0|."""
    xr, xf, x0 = by_kind(tr, run["x"]), by_kind(tr, ref["x"]), \
        by_kind(tr, ref["x0"])
    return {k: tree_dist(xr[k], xf[k]) / tree_dist(xf[k], x0[k])
            for k in sorted(xf)}


def first_step_report(tr, run, ref) -> None:
    """Where the two programs part after one step from one state: the
    workers' kept sets, the server aggregate per LMO kind, and the
    Newton-Schulz outputs of the two aggregates (jnp chain, device 0)."""
    gw_r = jax.tree.leaves(run["first"]["g_w"])
    gw_f = jax.tree.leaves(ref["first"]["g_w"])
    for w in range(gw_f[0].shape[0]):
        both = sum(int(np.count_nonzero((a[w] != 0) & (b[w] != 0)))
                   for a, b in zip(gw_r, gw_f))
        either = sum(int(np.count_nonzero((a[w] != 0) | (b[w] != 0)))
                     for a, b in zip(gw_r, gw_f))
        print(f"  step 0, worker {w}: kept entries shared by both "
              f"programs {both / max(either, 1)} of {either}", flush=True)
    gr = by_kind(tr, run["first"]["g_server"])
    gf = by_kind(tr, ref["first"]["g_server"])
    for k in sorted(gf):
        print(f"  step 0, {k} leaves: |g_server mesh - one| / |g_server| "
              f"{tree_dist(gr[k], gf[k]) / tree_dist(gf[k])}", flush=True)
    sr = spectral_stacks(tr, run["first"]["g_server"])
    for shape, g in spectral_stacks(tr, ref["first"]["g_server"]).items():
        print(f"  step 0, spectral {tuple(g.shape)}: g_server relative "
              f"distance {rel(sr[shape], g)}, Newton-Schulz output "
              f"relative distance {rel(ns(sr[shape], False), ns(g, False))}",
              flush=True)


def run_mesh(model, cfg, seq: int, seed: int, budget: int,
             n_chips: int = 4) -> None:
    """``n_chips`` workers on a (n_chips, 1) mesh vs one device. The runs
    start from the same seeded init and never share a device's memory:
    each keeps only host copies of what it compares."""
    from jax.sharding import Mesh

    from repro.train.trainer import Trainer, TrainerConfig

    n = n_chips
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(n, 1),
                ("data", "model"))
    print(f"== mesh ({n}, 1): {n} workers of {MESH_ARM} vs mesh=None on "
          f"device 0", flush=True)
    # donated state: every worker's state on one chip is the tight fit
    tcfg = TrainerConfig(n_workers=n, w2s=MESH_ARM, radius=RADIUS,
                         donate=True)
    one = Trainer(model, tcfg)
    meshed = Trainer(model, tcfg, mesh=mesh)
    shapes = structs(one.state_shapes())
    # per-worker batch 2: the one-chip arms' 8192 tokens per step
    b, batch_at, step1, _ = fit_step(one, shapes, cfg, n, seq, seed,
                                     budget, start=2)
    bstruct = jax.eval_shape(batch_at, 0)
    st_sh, b_sh = meshed.shardings(bstruct)
    t0 = time.perf_counter()
    stepm = meshed.jit_step(bstruct).lower(
        shapes, bstruct, jnp.float32(RADIUS)).compile()
    print(f"  mesh step compile {time.perf_counter() - t0:.1f} s",
          flush=True)
    hlo = stepm.as_text()
    gathers = u8_all_gathers(hlo)
    wb = meshed.wire_budget()
    print(f"  u8 all-gathers in the compiled HLO: {len(gathers)}, operand "
          f"bytes {sorted(gathers)}; wire_budget: "
          f"{len(wb.w2s_sizes)} w2s {sorted(wb.w2s_sizes)} + "
          f"{len(wb.s2w_sizes)} s2w {sorted(wb.s2w_sizes)} "
          f"({wb.n_stages} stages)", flush=True)
    fails = [] if "tpu_custom_call" in hlo else [
        "no Pallas kernel in the mesh step"]

    key = jax.random.key(seed)
    batches = [batch_at(i) for i in range(MESH_STEPS)]
    runs = {"mesh": trajectory(meshed, stepm, key, batches, st_sh, b_sh),
            "one device": trajectory(one, step1, key, batches)}
    mesh_run, one_run = runs["mesh"], runs["one device"]
    for name, r in runs.items():
        print(f"  {name}: |g_server - mean_w g_w| / |g_server| {r['gap']} "
              f"(tolerance {AGG_RTOL}; worker 1's messages lost would "
              f"read {r['lost']})", flush=True)
        if not r["gap"] <= AGG_RTOL:
            fails.append(f"{name}: server aggregate off the "
                         f"workers' mean by {r['gap']} > {AGG_RTOL}")
        if not r["lost"] > AGG_RTOL:
            fails.append(f"{name}: a lost message would read "
                         f"{r['lost']}, within {AGG_RTOL}")
    for i, (lm, l1) in enumerate(zip(mesh_run["losses"],
                                     one_run["losses"])):
        r = abs(lm - l1) / abs(l1)
        print(f"  step {i}: loss mesh {lm}  one device {l1}  rel diff {r} "
              f"(tolerance {LOSS_RTOL})", flush=True)
        if not (math.isfinite(lm) and math.isfinite(l1) and r <= LOSS_RTOL):
            fails.append(f"loss at step {i}: mesh {lm}, one "
                         f"device {l1}")
    first_step_report(one, mesh_run, one_run)
    diff, leaf = max_abs_diff(mesh_run["x"], one_run["x"])
    print(f"  x after {MESH_STEPS} steps: max |x_mesh - x_one| {diff} "
          f"(leaf {leaf})", flush=True)
    for kind, r in traj_rel(one, mesh_run, one_run).items():
        print(f"  {kind} leaves: |x_mesh - x_one| / |x_one - x0| {r} "
              f"(tolerance {TRAJ_RTOL[kind]})", flush=True)
        if not r <= TRAJ_RTOL[kind]:
            fails.append(f"{kind} trajectory of the mesh differs "
                         f"by {r} > {TRAJ_RTOL[kind]}")
    # negative control: worker 1 gets worker 2's batch, so its message
    # is worker 2's
    swapped = [jax.tree.map(lambda a: a.at[1].set(a[2]), bt)
               for bt in batches]
    bad = traj_rel(one, trajectory(one, step1, key, swapped), one_run)
    for kind, r in bad.items():
        print(f"  negative control, worker 1 sends worker 2's message: "
              f"{kind} leaves {r} (tolerance {TRAJ_RTOL[kind]})",
              flush=True)
    if all(r <= TRAJ_RTOL[k] for k, r in bad.items()):
        fails.append("the trajectory bounds accept worker 1 sending "
                     "worker 2's message")
    check(not fails, "; ".join(fails))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    require_tpu(args.chips)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.api import build_model

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    model = build_model(cfg)
    seq = cfg.max_position
    budget = int(0.85 * jax.devices()[0].memory_stats()["bytes_limit"])
    try:
        if args.chips == 4:
            run_mesh(model, cfg, seq, args.seed, budget)
        else:
            for arm in ARMS:
                run_arm(model, cfg, arm, seq, args.seed, budget)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_summary()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
