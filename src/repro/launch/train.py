"""Training launcher over the unified engine API.

Runs any assigned architecture (full or --reduced) with a pluggable
delay-compensation strategy (repro.engine.strategies registry). On this CPU
host the practical entry points are the reduced configs (examples/, smoke
tests); on a real TPU slice the same driver runs the production mesh via
--mesh prod / prod-multipod.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 200 --mode ssgd --strategy guided_fused --rho 10 --log-every 10 \
      --ckpt-dir /tmp/run1 --ckpt-every 50

Preempted? The same command plus --resume restarts bit-exactly from the
latest manifest entry (full state: params AND the guided compensation state —
see DESIGN.md §8). Checkpointing is owned by the Trainer, which snapshots
asynchronously off the hot path and installs a SIGTERM-safe final save; this
launcher only sets the knobs. (It used to save `{"params": params}` itself
from inside on_step — buffers that the next jit dispatch donates, and a
snapshot that silently dropped the entire GuidedState.)

Any strategy registered with @register_compensator is selectable here by name
without touching this file or the train step.

Multi-process async training (repro.dist, DESIGN.md §10): --backend dist runs
a REAL parameter server — a chief process owning the versioned store plus
--dist-workers gradient-pushing worker processes — on the paper's tabular
datasets:

  PYTHONPATH=src python -m repro.launch.train --backend dist --dataset pima \
      --mode asgd --strategy dc_asgd --dist-mode live --dist-workers 4 \
      --epochs 20 --dist-events restart:0@50

--role splits the same run across terminals/hosts: `--role chief` starts only
the store+listener (printing the address), `--role worker --addr host:port`
runs one worker process (equivalent to `python -m repro.dist.worker`).
"""
from __future__ import annotations

import argparse
import json
import time

from repro.engine import ExperimentSpec, Trainer, build_ctx, compensator_names  # noqa: F401
from repro.engine.spec import SCHEDULES

# build_ctx re-exported for back-compat (serve and older scripts imported it here)


def parse_dist_events(text: str) -> tuple:
    """'op:wid@version,...' -> ((op, wid, version), ...); e.g.
    'restart:0@50,join:0@80' kills+respawns worker 0 at store version 50 and
    joins an elastic worker at 80."""
    events = []
    for part in filter(None, (p.strip() for p in text.split(","))):
        try:
            op, rest = part.split(":", 1)
            wid, at = rest.split("@", 1)
            events.append((op, int(wid), int(at)))
        except ValueError:
            raise SystemExit(
                f"bad --dist-events entry {part!r}; want op:wid@version "
                f"(e.g. restart:0@50)") from None
    return tuple(events)


def _resolve_strategy_mode(args):
    strategy = args.strategy
    mode = args.mode
    if mode == "dc_asgd":  # legacy spelling: execution mode asgd + Taylor strategy
        mode = "asgd"
        strategy = strategy or ("dc_asgd_guided" if args.guided else "dc_asgd")
    if not strategy:
        strategy = "guided_fused" if args.guided else "none"
    return strategy, mode


def dist_spec_from_args(args) -> ExperimentSpec:
    strategy, mode = _resolve_strategy_mode(args)
    return ExperimentSpec(
        backend="dist",
        mode=mode,
        strategy=strategy,
        rho=args.rho,
        optimizer=args.optimizer,
        lr=args.lr,
        seed=args.seed,
        epochs=args.epochs,
        batch_size=args.batch_size,
        topology=args.topology,
        workers=args.dist_workers,
        dist_mode=args.dist_mode,
        delayed_avg=args.delayed_avg,
        dist_drop_rate=args.drop_rate,
        dist_time_scale=args.time_scale,
        dist_events=parse_dist_events(args.dist_events),
        dist_timeout=args.dist_timeout,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        keep_last=args.keep_last,
    )


def spec_from_args(args) -> ExperimentSpec:
    strategy, mode = _resolve_strategy_mode(args)
    overrides = []
    if args.layers:
        overrides.append(("n_layers", args.layers))
    if args.d_model:
        overrides.append(("d_model", args.d_model))
    if args.d_ff:
        overrides.append(("d_ff", args.d_ff))
    return ExperimentSpec(
        backend="mesh",
        arch=args.arch,
        reduced=args.reduced,
        model_overrides=tuple(overrides),
        mode=mode,
        strategy=strategy,
        rho=args.rho,
        optimizer=args.optimizer,
        lr=args.lr,
        schedule=args.schedule,
        steps=args.steps,
        seq_len=args.seq,
        global_batch=args.batch,
        mesh=args.mesh,
        workers=args.workers,
        micro=args.micro,
        chunk_steps=args.chunk_steps,
        prefetch=args.prefetch,
        seed=args.seed,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        keep_last=args.keep_last,
    )


def run_dist(args):
    """The --backend dist path: real multi-process async training on the
    paper's tabular datasets. Returns the launcher's result dict."""
    from repro.data import load_dataset, train_test_split
    from repro.dist import launcher

    spec = dist_spec_from_args(args)
    X, y, n_classes = load_dataset(args.dataset, seed=0)
    Xtr, ytr, Xte, yte = train_test_split(X, y, seed=spec.seed)
    t0 = time.time()
    res = launcher.run_local(spec, Xtr, ytr, n_classes, Xte, yte,
                             spawn=args.role == "auto", port=args.port)
    dt = time.time() - t0
    d = res["dist"]
    print(f"dist[{spec.dist_mode}] {args.dataset}: {res['n_steps']} server steps "
          f"in {dt:.1f}s ({res['n_steps'] / max(dt, 1e-9):.1f} steps/s), "
          f"val_loss {res['val_loss']:.4f}, test_acc "
          f"{res.get('test_accuracy', float('nan')):.4f}")
    print(f"observed staleness histogram: {res['staleness_hist']}")
    print(f"workers {d['n_workers']}, drops {d['drops']}, late {d['late']}, "
          f"exits {d['worker_exits']}, joins {d['joins']}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"n_steps": res["n_steps"], "val_loss": res["val_loss"],
                       "test_accuracy": res.get("test_accuracy"),
                       "staleness_hist": {str(k): v for k, v in res["staleness_hist"].items()},
                       "dist": d, "wall_time_s": dt}, f, indent=1)
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="mesh", choices=["mesh", "dist"],
                    help="mesh: jitted SPMD trainer (default); dist: real "
                         "multi-process async parameter server (repro.dist)")
    ap.add_argument("--arch", default="",
                    help="model architecture (required for --backend mesh)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0, help="override n_layers")
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mode", default="ssgd", choices=["seq", "ssgd", "asgd", "dc_asgd"])
    ap.add_argument("--guided", action="store_true",
                    help="shorthand for --strategy guided_fused")
    ap.add_argument("--strategy", default="",
                    help=f"delay-compensation strategy; registered: {', '.join(compensator_names())}")
    ap.add_argument("--rho", type=int, default=10)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--lr", type=float, default=0.05)
    # choices come from the spec's canonical tuple: cosine was supported by
    # ExperimentSpec/Trainer all along but rejected here by a stale hardcoded list
    ap.add_argument("--schedule", default="constant", choices=list(SCHEDULES))
    ap.add_argument("--mesh", default="local", choices=["local", "host", "prod", "prod-multipod"])
    ap.add_argument("--workers", type=int, default=0, help="logical worker count c (local mesh)")
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--chunk-steps", type=int, default=1,
                    help="train steps per dispatch: one compiled loop over K "
                         "steps (bit-exact for every K; big win when per-step "
                         "compute is small — see BENCH_train.json)")
    ap.add_argument("--prefetch", action="store_true",
                    help="double-buffered async host->device batch staging "
                         "(overlaps generation + H2D with the in-flight chunk)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--keep-last", type=int, default=3,
                    help="checkpoint retention (manifest prunes older snapshots; 0 keeps all)")
    ap.add_argument("--resume", action="store_true",
                    help="resume bit-exactly from the latest manifest entry in --ckpt-dir")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default="")
    # ------------------------------------------------ dist backend (repro.dist)
    ap.add_argument("--role", default="auto", choices=["auto", "chief", "worker"],
                    help="auto: chief spawns its own workers; chief: listen "
                         "only (workers launched separately); worker: run one "
                         "worker against --addr")
    ap.add_argument("--addr", default="",
                    help="chief address host:port (--role worker)")
    ap.add_argument("--port", type=int, default=0,
                    help="chief listen port (0 = ephemeral)")
    ap.add_argument("--dataset", default="pima",
                    help="tabular dataset for --backend dist (repro.data)")
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--topology", default="",
                    help="delay/worker-speed topology ('' = mode default)")
    ap.add_argument("--dist-mode", default="replay", choices=["replay", "live"],
                    help="replay: deterministic schedule-granted interleaving "
                         "(parity oracle); live: free-running asynchrony with "
                         "observed staleness + fault injection")
    ap.add_argument("--dist-workers", type=int, default=0,
                    help="worker processes (0 = the schedule's c = rho)")
    ap.add_argument("--delayed-avg", action="store_true",
                    help="DaSGD-style delayed averaging: overlap push/pull "
                         "with the next local step, merge on reply (live)")
    ap.add_argument("--drop-rate", type=float, default=0.0,
                    help="fraction of pushes the chief drops (live)")
    ap.add_argument("--time-scale", type=float, default=0.0,
                    help="seconds per sampled compute-time unit (live; 0 = "
                         "full speed)")
    ap.add_argument("--dist-events", default="",
                    help="fault plan op:wid@version,... with op in "
                         "kill|restart|join (live), e.g. restart:0@50")
    ap.add_argument("--dist-timeout", type=float, default=120.0,
                    help="watchdog: max seconds without store progress")
    args = ap.parse_args(argv)
    from repro.common.cache import enable_compile_cache

    enable_compile_cache()

    if args.role == "worker":
        from repro.dist.worker import main as worker_main

        if not args.addr:
            raise SystemExit("--role worker needs --addr host:port")
        return worker_main(["--addr", args.addr])
    if args.backend == "dist":
        return run_dist(args)
    if not args.arch:
        raise SystemExit("--backend mesh needs --arch")

    spec = spec_from_args(args)
    trainer = Trainer.from_spec(spec)

    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--resume needs --ckpt-dir")
        from repro.checkpoint import latest_step

        at = latest_step(args.ckpt_dir)
        print(f"resuming from step {at} in {args.ckpt_dir}" if at is not None
              else f"no checkpoint in {args.ckpt_dir}; starting fresh")

    history = []
    t0 = time.time()

    def on_step(step, m, params):
        # m holds the chunk's raw device metrics, stacked (k,), with step =
        # the chunk's LAST step index; step_records only forces the host
        # sync when a log step falls inside the chunk (empty selection -> no
        # transfer)
        from repro.engine.trainloop import step_records

        k = m["loss"].shape[0]
        first = step - k + 1
        logged = [i for i in range(k)
                  if (first + i) % args.log_every == 0 or first + i == args.steps - 1]
        for rec in step_records(m, first, logged):
            history.append(rec)
            print(f"step {rec['step']:5d} loss {rec['loss']:.4f} "
                  f"worker_var {rec['worker_var']:.2e} "
                  f"corr_w {rec['corr_w']:.2f} ({time.time()-t0:.1f}s)")
        if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            print(f"checkpoint enqueued at step {step + 1}")

    # the launcher keeps its own log-step history; don't retain per-step
    # metrics. Checkpointing (periodic async snapshots + the final/SIGTERM
    # full-state save) is the Trainer's: spec.ckpt_dir/ckpt_every/keep_last.
    report = trainer.fit(on_step=on_step, keep_history=False, resume=args.resume)

    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=1)
    if report.interrupted:
        print(f"interrupted by SIGTERM at step {report.start_step + report.n_steps}; "
              f"full state saved to {args.ckpt_dir} — rerun with --resume")
    if report.warm_steps:
        print(f"throughput: {report.steps_per_s:.1f} steps/s warm "
              f"(first dispatch incl. jit compile: {report.compile_time_s:.2f}s)")
    if history:
        print(f"done: final loss {history[-1]['loss']:.4f}")
    else:  # resumed at (or past) the final step: nothing left to run
        print(f"done: no steps to run (resumed at step {report.start_step})")
    return history


if __name__ == "__main__":
    main()
