"""`Trainer` — one facade over the three backends, `Report` — one result type.

    spec = ExperimentSpec(backend="sim", mode="ssgd", strategy="guided_fused")
    report = Trainer.from_spec(spec).fit((Xtr, ytr, n_classes, Xte, yte))
    report.test_accuracy, report.history

    spec = ExperimentSpec(backend="scan", mode="asgd", strategy="dc_asgd",
                          topology="heavy_tail", n_seeds=30)
    report = Trainer.from_spec(spec).fit((Xtr, ytr, n_classes, Xte, yte))
    report.wall_time_s, report.steps_per_s          # (timing on every backend)

    spec = ExperimentSpec(backend="mesh", arch="yi_9b", strategy="guided_fused")
    report = Trainer.from_spec(spec).fit()          # synthetic LM stream
    report.final_loss, report.history

The mesh path runs the strategy-driven step from `repro.engine.mesh` in
chunks (`repro.engine.trainloop`) and is numerically identical, step for
step, to an independent loop over that step (tests/test_engine.py locks this
in).
The sim path drives the literal numpy parameter server; the scan path drives
the jitted `repro.engine.delaysim` simulator, which reproduces the sim's
trajectories to float64 round-off (tests/test_delaysim.py). Either way the
caller never touches `PSConfig`, `GuidedConfig`, `train_ps` or
`build_train_step`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterable, Optional

from repro.engine.spec import ExperimentSpec


@dataclasses.dataclass
class Report:
    """Common result of a Trainer.fit run on either backend.

    history: per-step dicts on the mesh backend ({step, loss, worker_var,
    corr_w}); per-arrival (t, avg_err) pairs on the sim backend.
    """

    backend: str
    spec: ExperimentSpec
    history: list
    final: dict
    model: Any = None          # sim/scan: LogisticRegression (scan n_seeds>1:
                               # list of them); mesh: params pytree
    state: Any = None          # mesh: final GuidedState
    wall_time_s: float = 0.0   # wall time of fit() (incl. jit compile)
    steps_per_s: float = 0.0   # WARM throughput: server steps (x seeds on
                               # scan) per second — warm_steps/warm_time_s
                               # when the mesh loop measured them; falls
                               # back to n_steps/wall_time_s otherwise
    compile_time_s: float = 0.0  # sum of compiling dispatches (first
                               # occurrence of each chunk shape, incl. the
                               # steps they cover; mesh; 0 when unmeasured)
    warm_steps: int = 0        # steps outside compiling dispatches (mesh) —
                               # the numerator of the warm steps_per_s
    warm_time_s: float = 0.0   # wall time of the warm dispatches alone (the
                               # loop span minus compiling windows): setup,
                               # restore, and teardown never land in it
    n_steps: int = 0           # server steps this fit actually ran (per seed);
                               # from the schedule/server counter, NOT history
                               # record count — resume/history granularity safe
    start_step: int = 0        # mesh: step resumed from (0 = fresh run)
    interrupted: bool = False  # mesh: SIGTERM cut the run short (state saved)
    staleness_hist: dict = dataclasses.field(default_factory=dict)
                               # dist: OBSERVED staleness -> count over every
                               # applied update (applied_version - read_version)
    dist: dict = dataclasses.field(default_factory=dict)
                               # dist: run diagnostics (mode, n_workers, drops,
                               # late, worker_exits, joins; with the
                               # resilience layer armed also rejections/
                               # rollbacks/supervisor counters)
    resilience: dict = dataclasses.field(default_factory=dict)
                               # mesh: sentinel outcome ({sentinel,
                               # rejected_steps}) when spec.sentinel is set

    @property
    def final_loss(self) -> Optional[float]:
        if self.backend == "mesh":
            return self.final.get("loss")
        return self.final.get("train_loss")

    @property
    def val_loss(self) -> Optional[float]:
        return self.final.get("val_loss")

    @property
    def test_accuracy(self) -> Optional[float]:
        return self.final.get("test_accuracy")


class Trainer:
    """Facade dispatching an ExperimentSpec to its backend.

    Construction is cheap and side-effect free; model init / jit / data
    loading happen inside fit(). `trainer.strategy` is the resolved
    DelayCompensator instance (mesh backend).
    """

    def __init__(self, spec: ExperimentSpec):
        self.spec = spec
        self.strategy = None
        if spec.backend == "mesh":
            from repro.engine.mesh import resolve_strategy

            # resolve eagerly so unknown names fail at from_spec, not mid-fit
            self.strategy = resolve_strategy(spec.to_guided_config(), spec.strategy)
        elif spec.backend in ("scan", "dist"):
            from repro.engine.strategies import get_compensator

            self.strategy = get_compensator(spec.strategy, spec.to_guided_config())
        else:
            spec.to_ps_config()  # validates mode/strategy for the simulator

    @classmethod
    def from_spec(cls, spec: ExperimentSpec) -> "Trainer":
        return cls(spec)

    # ------------------------------------------------------------------ fit
    def fit(self, data=None, steps: Optional[int] = None,
            on_step: Optional[Callable] = None, keep_history: bool = True,
            resume: bool = False) -> Report:
        """Run the experiment.

        sim backend: `data` is (X, y, n_classes[, Xtest, ytest]).
        mesh backend: `data` is an iterable of batch dicts (or None for the
        synthetic LM stream); `steps` overrides spec.steps; `on_step(step,
        metrics, params)` fires after every dispatch — a chunk of k <=
        spec.chunk_steps steps (DESIGN.md §9) — with the RAW device metrics
        dict (loss, worker_loss_var, corr_weight_sum, lr, step) stacked to
        (k,), and step = the chunk's last step index. Reading a value forces
        a host sync, so cheap callbacks only touch them on their own logging
        cadence. The `params` handed to on_step are donated to the next
        dispatch — read or save them synchronously inside the callback;
        retaining them raises "Array has been deleted". Report.history is
        materialized after the loop so the hot path never blocks on
        device->host transfers; long launcher runs that keep their own
        log-step records pass keep_history=False to retain (and sync) only
        the final step. Any chunk_steps gives the same run bit for bit;
        spec.prefetch=True stages the next chunk's batches on a background
        thread while the current chunk computes.

        Checkpointing (mesh backend, DESIGN.md §8): spec.ckpt_dir enables
        full-state snapshots — params AND GuidedState (opt state, consistency
        scores, w_stale ring, strategy extra, step) plus the data-stream
        cursor — written asynchronously every spec.ckpt_every steps and once
        at loop exit (SIGTERM included: the handler finishes the in-flight
        chunk, snapshots, and returns with Report.interrupted=True).
        resume=True restarts from the latest manifest entry in spec.ckpt_dir
        bit-exactly: train(N) == train(k) + resume(N-k), leaf for leaf (a
        missing/empty ckpt_dir starts fresh). When resuming with an explicit
        `data` iterable, the already-consumed prefix is skipped — pass the
        same stream an uninterrupted run would have seen.
        """
        t0 = time.perf_counter()
        if self.spec.backend in ("sim", "scan", "dist"):
            if steps is not None or on_step is not None:
                raise ValueError(
                    "steps/on_step apply to the mesh backend; the sim/scan/"
                    "dist backends run the paper's epoch protocol (set "
                    "spec.epochs)"
                )
            if resume:
                raise ValueError(
                    "resume applies to the mesh backend; sim/scan/dist runs "
                    "are single fit calls with nothing to resume into"
                )
            report = {"sim": self._fit_sim, "scan": self._fit_scan,
                      "dist": self._fit_dist}[self.spec.backend](data)
            n_total = report.n_steps * self.spec.n_seeds
        else:
            report = self._fit_mesh(data, steps, on_step, keep_history, resume)
            n_total = report.n_steps
        report.wall_time_s = time.perf_counter() - t0
        if report.warm_steps > 0 and report.warm_time_s > 0:
            # warm throughput: compiling dispatches AND the out-of-loop setup
            # (init, restore, teardown) are split out so BENCH numbers stop
            # averaging compilation into the steady state
            report.steps_per_s = report.warm_steps / report.warm_time_s
        else:
            report.steps_per_s = n_total / max(report.wall_time_s, 1e-9)
        return report

    def _fit_sim(self, data) -> Report:
        from repro.core.parameter_server import train_ps

        if data is None:
            raise ValueError("sim backend needs data=(X, y, n_classes[, Xtest, ytest])")
        X, y, n_classes, *rest = data
        Xtest, ytest = (rest + [None, None])[:2]
        res = train_ps(X, y, n_classes, self.spec.to_ps_config(), Xtest, ytest)
        final = {k: res[k] for k in ("train_loss", "val_loss", "test_accuracy") if k in res}
        return Report(backend="sim", spec=self.spec, history=res["history"],
                      final=final, model=res["model"],
                      n_steps=res.get("n_steps", len(res["history"])))

    def _fit_scan(self, data) -> Report:
        """The jitted lax.scan delay simulator (repro.engine.delaysim): same
        data contract and Report shape as the sim backend; n_seeds > 1 turns
        the final metrics into (n_seeds,) arrays (one vmapped compile)."""
        from repro.engine import delaysim

        if data is None:
            raise ValueError("scan backend needs data=(X, y, n_classes[, Xtest, ytest])")
        X, y, n_classes, *rest = data
        Xtest, ytest = (rest + [None, None])[:2]
        res = delaysim.run(self.spec, X, y, n_classes, Xtest, ytest,
                           strategy=self.strategy)
        final = {k: res[k] for k in ("train_loss", "val_loss", "test_accuracy") if k in res}
        return Report(backend="scan", spec=self.spec, history=res["history"],
                      final=final, model=res["model"],
                      n_steps=res.get("n_steps", len(res["history"])))

    def _fit_dist(self, data) -> Report:
        """The real multi-process async parameter server (repro.dist): same
        data contract as sim/scan. Report additionally carries the OBSERVED
        staleness histogram and the dist diagnostics (drops, worker exits,
        elastic joins) — the quantities the simulators can only assume."""
        from repro.dist import launcher

        if data is None:
            raise ValueError("dist backend needs data=(X, y, n_classes[, Xtest, ytest])")
        X, y, n_classes, *rest = data
        Xtest, ytest = (rest + [None, None])[:2]
        res = launcher.run_local(self.spec, X, y, n_classes, Xtest, ytest,
                                 strategy=self.strategy)
        final = {k: res[k] for k in ("train_loss", "val_loss", "test_accuracy") if k in res}
        return Report(backend="dist", spec=self.spec, history=res["history"],
                      final=final, model=res["model"], n_steps=res["n_steps"],
                      staleness_hist=res["staleness_hist"], dist=res["dist"])

    def _fit_mesh(self, data, steps, on_step, keep_history=True, resume=False) -> Report:
        from repro.engine import trainloop

        return trainloop.fit(self.spec, self.strategy, data=data, steps=steps,
                             on_step=on_step, keep_history=keep_history,
                             resume=resume)
