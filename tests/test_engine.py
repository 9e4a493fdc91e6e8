"""Tests for the unified repro.engine API: spec round-trips, the
DelayCompensator registry, and step-for-step parity of the Trainer mesh path
with an independent loop over engine.mesh.build_train_step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.guided import GuidedConfig
from repro.core.parameter_server import ALGO_NAMES, PSConfig
from repro.engine import (
    ALGOS,
    DelayCompensator,
    ExperimentSpec,
    Trainer,
    compensator_names,
    get_compensator,
    register_compensator,
    strategy_name_for,
)


# ------------------------------------------------------------- spec round-trip


@pytest.mark.parametrize("mode", ["seq", "ssgd", "asgd"])
@pytest.mark.parametrize("guided", [False, True])
@pytest.mark.parametrize("optimizer", ["sgd", "rmsprop", "adagrad"])
def test_ps_config_roundtrip(mode, guided, optimizer):
    cfg = PSConfig(mode=mode, guided=guided, optimizer=optimizer,
                   lr=0.11, epochs=7, rho=5, batch_size=8, max_consistent=3, seed=9)
    spec = ExperimentSpec.from_ps_config(cfg)
    assert spec.backend == "sim"
    assert spec.to_ps_config() == cfg


@pytest.mark.parametrize("mode,guided,correction", [
    ("seq", False, "fused"),
    ("ssgd", True, "fused"),
    ("ssgd", True, "two_pass"),
    ("asgd", False, "fused"),
    ("asgd", True, "fused"),
    ("dc_asgd", False, "fused"),
    ("dc_asgd", True, "fused"),
])
def test_guided_config_roundtrip(mode, guided, correction):
    gcfg = GuidedConfig(mode=mode, guided=guided, correction=correction,
                        rho=7, max_consistent=2, staleness=3, dc_lambda=0.1)
    spec = ExperimentSpec.from_guided_config(gcfg)
    assert spec.backend == "mesh"
    back = spec.to_guided_config()
    # guided=False leaves correction at its default; compare semantic fields
    assert back.mode == gcfg.mode
    assert back.guided == gcfg.guided
    assert back.rho == gcfg.rho
    assert back.max_consistent == gcfg.max_consistent
    assert back.staleness == gcfg.staleness
    assert back.dc_lambda == gcfg.dc_lambda
    if gcfg.guided:
        assert back.correction == gcfg.correction


def test_algo_table_matches_parameter_server():
    """Spec's algorithm table lowers to the exact PSConfig of every paper name."""
    inv = {v: k for k, v in ALGO_NAMES.items()}
    for name, (mode, guided, opt) in inv.items():
        spec = ExperimentSpec.for_algo(name)
        cfg = spec.to_ps_config()
        assert (cfg.mode, cfg.guided, cfg.optimizer) == (mode, guided, opt), name
    assert set(inv) <= set(ALGOS)


def test_sim_rejects_mesh_only_strategy():
    with pytest.raises(ValueError, match="parameter-server"):
        ExperimentSpec(backend="sim", mode="asgd", strategy="dc_asgd").to_ps_config()


def test_for_algo_defaults_every_name_to_a_runnable_backend():
    for name in ALGOS:
        spec = ExperimentSpec.for_algo(name)
        Trainer.from_spec(spec)  # must validate, whatever backend it picked
    assert ExperimentSpec.for_algo("DC-ASGD").backend == "mesh"
    assert ExperimentSpec.for_algo("gSSGD").backend == "sim"


def test_strategy_name_is_authoritative_over_gcfg_flags():
    """Explicitly selecting guided_fused must correct even when the
    GuidedConfig flags would say otherwise (no silent no-op)."""
    import jax.numpy as jnp

    from repro.engine import get_compensator

    gcfg = GuidedConfig(mode="ssgd", guided=False, rho=1, correction="two_pass")
    strat = get_compensator("guided_fused", gcfg)
    state_like = type("S", (), {})()
    state_like.step = jnp.asarray(0)
    state_like.score = jnp.asarray([3.0, 1.0])
    w = np.asarray(strat.correction_weights(state_like, 2))
    assert w.sum() > 0  # rho=1: every step is a window end


# ----------------------------------------------------------------- registry


def test_registry_lookup_and_unknown_name():
    gcfg = GuidedConfig()
    stale_gcfg = GuidedConfig(mode="asgd")  # gap_aware requires stale weights
    for name in ("none", "guided_fused", "guided_two_pass", "dc_asgd",
                 "dc_asgd_guided", "gap_aware"):
        assert name in compensator_names()
        got = get_compensator(name, stale_gcfg if name == "gap_aware" else gcfg)
        assert got.name == name
    with pytest.raises(KeyError, match="registered:"):
        get_compensator("does_not_exist", gcfg)


def test_strategy_name_for_legacy_flags():
    assert strategy_name_for(GuidedConfig(guided=False)) == "none"
    assert strategy_name_for(GuidedConfig(guided=True, correction="fused")) == "guided_fused"
    assert strategy_name_for(GuidedConfig(guided=True, correction="two_pass")) == "guided_two_pass"
    assert strategy_name_for(GuidedConfig(mode="dc_asgd", guided=False)) == "dc_asgd"
    assert strategy_name_for(GuidedConfig(mode="dc_asgd", guided=True)) == "dc_asgd_guided"


def test_gap_aware_rejects_modes_without_stale_weights():
    with pytest.raises(ValueError, match="asgd"):
        get_compensator("gap_aware", GuidedConfig(mode="ssgd"))
    with pytest.raises(ValueError, match="asgd"):
        Trainer.from_spec(ExperimentSpec(backend="mesh", mode="ssgd", strategy="gap_aware"))


def test_engine_import_stays_numpy_light():
    """Sim-only scripts must not pay the jax import cost (lazy re-exports)."""
    import subprocess, sys
    code = (
        "import sys\n"
        "from repro.engine import ExperimentSpec, Trainer\n"
        "spec = ExperimentSpec.for_algo('gSSGD')\n"
        "Trainer.from_spec(spec)\n"
        "assert 'jax' not in sys.modules, 'jax imported on the sim-only path'\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-2000:]


def test_cli_dc_asgd_guided_combo_keeps_guided_hooks():
    """--mode dc_asgd --guided must lower to the composed strategy (the legacy
    flags applied BOTH the Taylor compensation and the fused replay)."""
    import argparse
    from repro.launch.train import spec_from_args, main as train_main  # noqa: F401

    ns = argparse.Namespace(
        arch="yi_9b", reduced=True, layers=0, d_model=0, d_ff=0, steps=4, seq=16,
        batch=4, mode="dc_asgd", guided=True, strategy="", rho=2, optimizer="sgd",
        lr=0.01, schedule="constant", mesh="local", workers=2, micro=1,
        chunk_steps=1, prefetch=False, seed=0,
        ckpt_dir="", ckpt_every=0, keep_last=3,
    )
    spec = spec_from_args(ns)
    assert spec.strategy == "dc_asgd_guided" and spec.mode == "asgd"
    gcfg = spec.to_guided_config()
    assert gcfg.mode == "dc_asgd" and gcfg.guided and gcfg.correction == "fused"


def test_register_custom_strategy_selectable_by_name():
    @register_compensator("test_half_grads")
    class HalfGrads(DelayCompensator):
        def compensate_grads(self, grads, params, state):
            return jax.tree.map(lambda g: g * 0.5, grads)

    gcfg = GuidedConfig(mode="ssgd", guided=False)
    got = get_compensator("test_half_grads", gcfg)
    assert isinstance(got, HalfGrads)
    g = got.compensate_grads({"w": jnp.ones(2)}, None, None)
    np.testing.assert_allclose(np.asarray(g["w"]), 0.5)


def test_custom_strategy_with_array_extra_state():
    """A plugin whose init() returns a bare array (not a tuple) must train:
    the extra state threads through GuidedState across steps."""

    @register_compensator("test_grad_norm_ema")
    class GradNormEma(DelayCompensator):
        def init(self, params, n_workers):
            return jnp.zeros(())

        def update_extra(self, state, grads):
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree.leaves(grads)))
            return 0.9 * state.extra + 0.1 * gn

    spec = ExperimentSpec(
        backend="mesh", arch="yi_9b", reduced=True, mode="ssgd",
        strategy="test_grad_norm_ema", rho=2, lr=1e-2, seed=0, steps=3,
        seq_len=16, global_batch=4, workers=2,
    )
    report = Trainer.from_spec(spec).fit()
    assert float(report.state.extra) > 0.0  # EMA accumulated across steps
    assert all(np.isfinite(h["loss"]) for h in report.history)


# --------------------------------------------------------------- mesh parity


def _legacy_losses(cfg, gcfg, n_steps, batches):
    """An independent loop over `engine.mesh`'s step: its own init, the
    strategy the GuidedConfig flags imply, a constant lr, and one dispatch of
    the chunk program per batch (a block of one)."""
    from repro.engine import mesh as M
    from repro.engine.trainloop import build_chunk_step
    from repro.optim import constant, get_optimizer
    from repro.sharding.rules import LOCAL_CTX

    opt = get_optimizer("sgd")
    params, _, gstate = M.init_train_state(jax.random.PRNGKey(3), cfg, gcfg, opt, n_workers=2)
    step = jax.jit(build_chunk_step(
        M.build_train_step(cfg, gcfg, opt, LOCAL_CTX, constant(1e-2), n_workers=2)))
    losses = []
    for b in batches:
        params, gstate, m = step(params, gstate, jax.tree.map(lambda x: x[None], b))
        losses.append(float(m["loss"][0]))
    return losses


@pytest.mark.parametrize("strategy,mode", [
    ("guided_fused", "ssgd"),
    ("dc_asgd", "asgd"),
    ("dc_asgd_guided", "asgd"),
])
def test_trainer_matches_legacy_step_for_step(strategy, mode):
    """Trainer.from_spec on the mesh path reproduces the losses of an
    independent loop over engine.mesh.build_train_step."""
    from repro.data import make_batch_for

    spec = ExperimentSpec(
        backend="mesh", arch="yi_9b", reduced=True, mode=mode, strategy=strategy,
        rho=2, lr=1e-2, seed=3, steps=5, seq_len=16, global_batch=4, workers=2,
        optimizer="sgd", schedule="constant",
    )
    cfg = spec.model_config()
    batches = [
        {k: jnp.asarray(v) for k, v in make_batch_for(cfg, 16, 4, seed=i).items()}
        for i in range(5)
    ]
    legacy = _legacy_losses(cfg, spec.to_guided_config(), 5, batches)
    report = Trainer.from_spec(spec).fit(data=[dict(b) for b in batches])
    got = [h["loss"] for h in report.history]
    np.testing.assert_allclose(got, legacy, rtol=0, atol=0)
    assert report.backend == "mesh"
    assert report.final_loss == got[-1]
    assert report.state is not None


def test_gap_aware_runs_and_dampens():
    """The plugin strategy runs end-to-end and differs from plain ASGD."""
    base = ExperimentSpec(
        backend="mesh", arch="yi_9b", reduced=True, mode="asgd", strategy="none",
        rho=2, staleness=2, lr=5e-2, seed=0, steps=6, seq_len=16, global_batch=4,
        workers=2, optimizer="sgd", schedule="constant",
    )
    r_plain = Trainer.from_spec(base).fit()
    r_gap = Trainer.from_spec(base.replace(strategy="gap_aware")).fit()
    a = [h["loss"] for h in r_plain.history]
    b = [h["loss"] for h in r_gap.history]
    assert a[0] == b[0]  # first step: w_stale == params, no gap yet
    assert a[2:] != b[2:]  # dampening changes the trajectory once a gap exists
    assert all(np.isfinite(b))


def test_trainer_sim_backend_matches_train_ps():
    from repro.core.parameter_server import algo_config, train_ps
    from repro.data import load_dataset, train_test_split

    X, y, k = load_dataset("cancer", seed=0)
    Xtr, ytr, Xte, yte = train_test_split(X, y, seed=2)
    legacy = train_ps(Xtr[:200], ytr[:200], k, algo_config("gSSGD", epochs=2, seed=2), Xte, yte)
    rep = Trainer.from_spec(ExperimentSpec.for_algo("gSSGD", epochs=2, seed=2)).fit(
        (Xtr[:200], ytr[:200], k, Xte, yte))
    assert rep.test_accuracy == legacy["test_accuracy"]
    assert rep.val_loss == legacy["val_loss"]
    assert rep.history == legacy["history"]


def test_mesh_global_batch_divisibility_is_a_real_exception():
    """global_batch % workers != 0 must raise a ValueError naming the spec
    fields (it was an assert, which vanishes under `python -O`)."""
    spec = ExperimentSpec(backend="mesh", arch="minicpm_2b", reduced=True,
                          steps=1, seq_len=8, global_batch=7, workers=2)
    with pytest.raises(ValueError, match=r"global_batch=7.*c=2"):
        Trainer.from_spec(spec).fit()


# ------------------------------------- fused whole-update on the mesh path


@pytest.mark.parametrize("optname,strategy,mode", [
    ("momentum", "guided_fused", "ssgd"),
    ("adam", "dc_asgd", "asgd"),
    ("sgd", "dc_asgd_guided", "asgd"),
])
def test_mesh_fused_update_matches_two_phase(optname, strategy, mode):
    """The fused whole-update dispatch (DESIGN.md §11) must reproduce the
    two-phase compensate_grads + opt.update + tree_add path step for step.
    Forcing hypers=None disables fused selection, giving the control arm."""
    from repro.data import make_batch_for
    from repro.engine import mesh as M
    from repro.optim import constant, get_optimizer

    spec = ExperimentSpec(
        backend="mesh", arch="yi_9b", reduced=True, mode=mode, strategy=strategy,
        rho=2, lr=1e-2, seed=3, steps=4, seq_len=16, global_batch=4, workers=2,
        optimizer=optname, schedule="constant",
    )
    cfg = spec.model_config()
    gcfg = spec.to_guided_config()
    batches = [
        {k: jnp.asarray(v) for k, v in make_batch_for(cfg, 16, 4, seed=i).items()}
        for i in range(4)
    ]

    def losses(opt):
        params, _, gstate = M.init_train_state(
            jax.random.PRNGKey(3), cfg, gcfg, opt, n_workers=2,
            strategy=strategy)
        step = jax.jit(M.build_train_step(
            cfg, gcfg, opt, M.build_ctx("local"), constant(1e-2),
            n_workers=2, strategy=strategy))
        out = []
        for b in batches:
            params, gstate, m = step(params, gstate, b)
            out.append(float(m["loss"]))
        return out

    opt = get_optimizer(optname)
    assert opt.hypers is not None  # fused arm actually selectable
    fused = losses(opt)
    two_phase = losses(opt._replace(hypers=None))  # forces the legacy path
    np.testing.assert_allclose(fused, two_phase, rtol=1e-6, atol=2e-6)
