"""Training CLI.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --reduced --steps 50 --workers 2 --w2s top10 --radius 0.01

Runs the distributed EF21-Muon trainer on the synthetic Zipf-Markov
pipeline, logs loss + w2s wire bytes, and optionally checkpoints. It
takes ``TrainerConfig``'s defaults for kernels and recomputation: the
Pallas kernels on a TPU and the jnp references elsewhere, with remat on.
The persistent compilation cache follows ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.core.schedule import warmup_linear_decay
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.obs.sink import MetricsWriter, run_manifest
from repro.obs.trace import span, span_summary
from repro.train.checkpoint import load_checkpoint, save_checkpoint
from repro.train.faults import parse_faults
from repro.train.supervisor import Supervisor, SupervisorConfig
from repro.train.trainer import Trainer, TrainerConfig


def _ef_summary_rows(metrics: dict, limit: int = 12) -> list[dict]:
    """Per-layer EF21 error rows from one step's metric dict, largest
    ``ef/err_norm`` first (the layers where compression bites hardest)."""
    rows = []
    for name, v in metrics.items():
        if not name.startswith("ef/err_norm/"):
            continue
        leaf = name[len("ef/err_norm/"):]
        rows.append({
            "leaf": leaf, "err_norm": v,
            "rel_err": metrics.get(f"ef/rel_err/{leaf}"),
            "momentum_norm": metrics.get(f"ef/momentum_norm/{leaf}"),
        })
    rows.sort(key=lambda r: -(r["err_norm"] or 0.0))
    return rows[:limit]


def _print_tables(spans: list[dict], ef_rows: list[dict]) -> None:
    if spans:
        print("-- host phase timings --")
        print(f"{'span':32s} {'count':>6s} {'total_s':>9s} {'max_s':>9s}")
        for r in spans:
            print(f"{r['name']:32s} {r['count']:6d} "
                  f"{r['total_s']:9.4f} {r['max_s']:9.4f}")
    if ef_rows:
        print("-- EF21 error by layer (final step, worst first) --")
        print(f"{'leaf':28s} {'err_norm':>10s} {'rel_err':>8s} "
              f"{'momentum':>10s}")
        for r in ef_rows:
            rel = r["rel_err"]
            mom = r["momentum_norm"]
            print(f"{r['leaf']:28s} {r['err_norm']:10.4g} "
                  f"{(f'{rel:8.3f}' if rel is not None else '       -')} "
                  f"{(f'{mom:10.4g}' if mom is not None else '         -')}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--beta", type=float, default=0.5)
    ap.add_argument("--w2s", default="top10")
    ap.add_argument("--s2w", default="identity")
    ap.add_argument("--radius", type=float, default=0.01)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write schema-versioned JSONL metrics here "
                         "(implies in-graph metrics collection, §10)")
    ap.add_argument("--trace-spans", action="store_true",
                    help="named-scope the step phases for xprof captures")
    ap.add_argument("--participation", default="full", metavar="SPEC",
                    help="elastic worker participation (§11): 'full', "
                         "'bernoulli(p)' or 'round_robin(k)'")
    ap.add_argument("--participation-seed", type=int, default=0)
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="chaos schedule, e.g. "
                         "'drop:w=1:steps=5-10,nan:w=0:steps=7,"
                         "flip:steps=4:bits=8' (repro.train.faults)")
    ap.add_argument("--donate", action="store_true",
                    help="donate the optimizer state to the jitted step "
                         "(in-place buffer reuse instead of double-"
                         "buffering; the §12 donation-audit rule "
                         "certifies the aliasing)")
    ap.add_argument("--resync", type=int, default=0, metavar="R",
                    help="desynchronized-worker rejoin (§13): keep "
                         "per-worker W estimates + an R-deep replay ring "
                         "of packed s2w rounds; 0 compiles it out. "
                         "Requires a compressing --s2w")
    ap.add_argument("--supervise", action="store_true",
                    help="run the loop under the §13 supervisor "
                         "(per-step timeout, bounded retry, checkpoint-"
                         "reload recovery)")
    ap.add_argument("--step-timeout", type=float, default=None,
                    metavar="SEC", help="supervisor per-step watchdog")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="supervisor re-dispatches per step")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    metavar="N", help="periodic last-good checkpoint to "
                         "--checkpoint every N steps (supervisor "
                         "recovery granularity)")
    args = ap.parse_args()
    if args.supervise and args.donate:
        print("warning: --supervise needs the input state intact for "
              "retries; disabling --donate")
        args.donate = False
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    shape = ShapeSpec("cli", "train", args.seq, args.batch)
    data = SyntheticLM(cfg, shape, n_workers=args.workers, seed=args.seed)
    faults = (parse_faults(args.faults, args.workers, seed=args.seed)
              if args.faults else None)
    tcfg = TrainerConfig(
        n_workers=args.workers, beta=args.beta, w2s=args.w2s, s2w=args.s2w,
        metrics=args.metrics_out is not None,
        trace_spans=args.trace_spans, participation=args.participation,
        participation_seed=args.participation_seed, faults=faults,
        donate=args.donate, resync=args.resync)
    tr = Trainer(model, tcfg)
    state = tr.init(jax.random.key(args.seed))
    start = 0
    if args.resume:
        state, start = load_checkpoint(args.resume, state)
        print(f"resumed from {args.resume} @ step {start}")
    # jit through the trainer so --donate's donate_argnums applies (the
    # input state is consumed per step; the loop rebinds it anyway)
    step_fn = tr.jit_step(None)
    sched = warmup_linear_decay(args.radius, args.warmup, args.steps)
    # wire accounting straight from the LayerPlan (Table 2 source of
    # truth) — both directions plus the two-way total (§9)
    plan = tr.layer_plan()
    dt = tr.opt.cfg.wire_dtype
    wire = plan.w2s_bytes_per_worker(dt)
    dense = plan.dense_bytes(dt)
    buf = plan.wire_layout(dt).total_nbytes
    s2w_wire = (plan.s2w_bytes_per_round(dt)
                if args.s2w != "identity" else 0)
    s2w_buf = (plan.wire_layout(dt, direction="s2w").total_nbytes
               if args.s2w != "identity" else 0)
    stages = plan.stage_plan(wire_stages=tr.opt.cfg.wire_stages).n_stages
    print(f"arch={cfg.name} params="
          f"{sum(p.size for p in jax.tree.leaves(state['x']))} "
          f"w2s_bytes/worker={wire} ({wire / dense:.3f} of dense) "
          f"wire_buffer={buf} ({buf / dense:.3f} of dense) "
          f"s2w_bytes/round={s2w_wire} s2w_wire_buffer={s2w_buf} "
          f"two_way_wire={buf + s2w_buf} "
          f"wire_stages={stages}")
    writer = None
    if args.metrics_out:
        writer = MetricsWriter(
            args.metrics_out,
            manifest=run_manifest(tcfg, None, extra={"arch": cfg.name}))
    sup = None
    if args.supervise:
        sup = Supervisor(
            SupervisorConfig(
                step_timeout_s=args.step_timeout,
                max_retries=args.max_retries,
                checkpoint_path=args.checkpoint,
                checkpoint_every=args.checkpoint_every),
            writer=writer, state_like=state)
        if writer is not None:
            writer.write("recovery", step=start, event="resume" if
                         args.resume else "start", attempt=0)
    resync_replayed = resync_full = last_lag = 0
    last_metrics: dict = {}
    aux = {"loss": float("nan")}   # resumed-at-end runs skip the loop
    t0 = time.time()
    try:
        i = start
        while i < args.steps:
            if faults is not None:
                # simulated power loss (crash:step=s): fresh runs only,
                # so the --resume run sails past the crash step
                faults.host_crash(i, start_step=start)
            with span("data/batch"):
                batch = data.batch_at(i)
            # step i's dispatch (supervised: its retries and checkpoint)
            # as one step of an operator's profiler capture
            with jax.profiler.StepTraceAnnotation("train_step", step_num=i):
                if sup is not None:
                    result, rs_state, rs_step = sup.run_step(
                        step_fn, state, batch, sched(i), step=i,
                        faults=faults)
                    if result is None:
                        # checkpoint-reload recovery: rewind the loop to
                        # the last-good generation and re-step from there
                        state, i = rs_state, rs_step
                        print(f"recovered from {args.checkpoint} "
                              f"@ step {i}", flush=True)
                        continue
                    state, aux = result
                    sup.maybe_checkpoint(state, i)
                else:
                    state, aux = step_fn(state, batch, sched(i))
            if "resync_replayed" in aux:
                resync_replayed += int(aux["resync_replayed"])
                resync_full += int(aux["resync_full"])
                last_lag = int(aux["version_lag_max"])
            if i % args.log_every == 0 or i == args.steps - 1:
                row = {"step": i, "loss": round(float(aux["loss"]), 4),
                       "radius": round(float(sched(i)), 5),
                       "wall_s": round(time.time() - t0, 1)}
                if "n_participants" in aux:
                    row["n_participants"] = int(aux["n_participants"])
                print(json.dumps(row), flush=True)
                if writer is not None:
                    last_metrics = aux["metrics"].host_floats()
                    if sup is not None:
                        last_metrics["supervisor/retries"] = float(
                            sup.retries)
                    writer.write("step", metrics=last_metrics, **row)
            i += 1
        if args.checkpoint:
            save_checkpoint(args.checkpoint, state, step=args.steps)
            print(f"saved {args.checkpoint}")
        spans = span_summary()
        ef_rows = _ef_summary_rows(last_metrics)
        _print_tables(spans, ef_rows)
        summary = {"final_loss": round(float(aux["loss"]), 4),
                   "resync_replayed": resync_replayed,
                   "resync_full": resync_full,
                   "version_lag_max": last_lag,
                   "supervisor_retries": sup.retries if sup else 0,
                   "supervisor_reloads": sup.reloads if sup else 0}
        # single greppable line: the chaos-soak CI job's assertion hook
        print("RESYNC_SUMMARY " + json.dumps(summary), flush=True)
        if writer is not None:
            for r in spans:
                writer.write("span", **r)
            writer.write("summary", spans=spans, ef_summary=ef_rows,
                         **summary)
    finally:
        if writer is not None:
            writer.close()
            print(f"metrics written to {args.metrics_out}")


if __name__ == "__main__":
    main()
