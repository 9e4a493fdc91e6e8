"""Quickstart: train a reduced assigned architecture with guided SSGD.

Shows the three moving parts of the framework in ~30 lines:
  1. an ExperimentSpec naming the experiment (arch, mode, strategy),
  2. the DelayCompensator strategy registry (the paper's contribution is
     `guided_fused`; swap the string for `dc_asgd`, `gap_aware`, ...),
  3. the Trainer facade running the jitted train step with per-worker
     consistency tracking.

This demo uses the mesh (transformer) backend. The same spec vocabulary runs
the paper-scale simulators: `backend="scan"` is the jitted delay simulator
(multi-seed sweeps via `n_seeds`, delay topologies via `topology`; the
benchmarks accept `--backend scan|sim`), `backend="sim"` the numpy reference.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
from repro.engine import ExperimentSpec, Trainer

spec = ExperimentSpec(
    backend="mesh",
    arch="yi_9b",            # any of the 10 assigned archs
    reduced=True,
    mode="ssgd",
    strategy="guided_fused",  # gSSGD, paper defaults
    rho=5,
    workers=4,               # the paper's c (= data-parallel workers on a real mesh)
    lr=1e-2,
    steps=20,
    seq_len=32,
    global_batch=8,
)


def on_step(step, m, params):
    # one call per dispatch, metrics stacked over its steps: (1,) at the
    # default chunk_steps=1
    corr_w = float(m["corr_weight_sum"][0])
    if step % 5 == 0 or corr_w > 0:
        print(
            f"step {step:3d} loss={float(m['loss'][0]):.4f} "
            f"worker_var={float(m['worker_loss_var'][0]):.2e} "
            f"guided_correction={'FIRED' if corr_w > 0 else '-'}"
        )


report = Trainer.from_spec(spec).fit(on_step=on_step)
print("scores per worker:", [round(float(s), 2) for s in report.state.score])
