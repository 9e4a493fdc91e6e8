"""Tests for the paper's core: consistency statistics, guided correction,
staleness/DC-ASGD, and the literal parameter-server simulation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypcompat import given, settings, st  # hypothesis, or skip-stubs when absent

from repro.core import guided as G
from repro.core.consistency import consistency_increment
from repro.core.parameter_server import (
    ALGO_NAMES,
    LogisticRegression,
    PSConfig,
    algo_config,
    train_ps,
)
from repro.data import load_dataset, train_test_split


# ------------------------------------------------------------- consistency


@given(
    st.lists(st.floats(0.1, 10.0), min_size=4, max_size=4),
    st.lists(st.floats(0.1, 10.0), min_size=4, max_size=4),
    st.floats(0.1, 10.0),
    st.floats(0.1, 10.0),
)
@settings(max_examples=50, deadline=None)
def test_consistency_increment_bounds(wl, pwl, al, pal):
    inc = consistency_increment(jnp.asarray(wl), jnp.asarray(pwl), jnp.asarray(al), jnp.asarray(pal))
    inc = np.asarray(inc)
    assert np.all(inc >= 0) and np.all(inc <= 1.1 + 1e-6)
    # increments are positive only where both deltas are negative
    both = (np.asarray(wl) < np.asarray(pwl)) & (al < pal)
    assert np.all((inc > 0) == both)


@given(st.lists(st.floats(0.0, 5.0), min_size=3, max_size=16))
@settings(max_examples=50, deadline=None)
def test_correction_weights_properties(scores):
    gcfg = G.GuidedConfig(max_consistent=4)
    w = np.asarray(G.correction_weights(jnp.asarray(scores, jnp.float32), gcfg))
    assert np.all(w >= -1e-6)
    s = w.sum()
    assert abs(s - 1.0) < 1e-5 or abs(s) < 1e-6  # normalized or all-zero
    assert (w > 0).sum() <= 4  # paper: at most 4 replayed batches
    if s > 0:  # the top scorer is always selected
        assert w[int(np.argmax(scores))] > 0


def test_correction_weights_zero_scores():
    gcfg = G.GuidedConfig()
    w = G.correction_weights(jnp.zeros(8), gcfg)
    assert float(jnp.sum(w)) == 0.0


def test_dc_asgd_compensation_formula():
    g = {"w": jnp.asarray([1.0, -2.0])}
    p = {"w": jnp.asarray([0.5, 0.5])}
    pb = {"w": jnp.asarray([0.0, 1.0])}
    out = G.compensate_dc_asgd(g, p, pb, lam=0.1)
    expect = np.array([1.0 + 0.1 * 1.0 * 0.5, -2.0 + 0.1 * 4.0 * (-0.5)])
    np.testing.assert_allclose(np.asarray(out["w"]), expect, atol=1e-6)


def test_stale_refresh_period():
    gcfg = G.GuidedConfig(mode="asgd", staleness=3)
    params = {"w": jnp.ones(2)}
    from repro.optim import sgd

    state = G.guided_init(gcfg, params, sgd(), 4)
    for step in range(7):
        state = state._replace(step=jnp.asarray(step))
        new_params = {"w": jnp.full(2, float(step + 10))}
        ws = G.refresh_stale(state, gcfg, new_params)
        if step % 3 == 0:
            np.testing.assert_allclose(np.asarray(ws["w"]), step + 10)
        state = state._replace(w_stale=ws)


def test_window_end_every_rho():
    gcfg = G.GuidedConfig(rho=5)
    ends = [bool(G.is_window_end(jnp.asarray(s), gcfg)) for s in range(11)]
    assert ends == [False, False, False, False, True] * 2 + [False]


# ------------------------------------------------- literal parameter server


def test_logreg_gradient_matches_finite_difference():
    rng = np.random.default_rng(0)
    m = LogisticRegression(4, 3, rng)
    X = rng.standard_normal((16, 4))
    y = rng.integers(0, 3, 16)
    g = m.grad(X, y)
    eps = 1e-6
    for idx in [(0, 0), (2, 1), (4, 2)]:
        W2 = m.W.copy()
        W2[idx] += eps
        fd = (m.loss(X, y, W2) - m.loss(X, y)) / eps
        assert abs(fd - g[idx]) < 1e-4


def test_ssgd_with_one_worker_equals_seq():
    """c=1 synchronous == sequential SGD (identical update sequence)."""
    X, y, k = load_dataset("cancer", seed=0)
    Xtr, ytr, Xte, yte = train_test_split(X, y, seed=0)
    a = train_ps(Xtr, ytr, k, PSConfig(mode="seq", epochs=2, seed=3, rho=1), Xte, yte)
    b = train_ps(Xtr, ytr, k, PSConfig(mode="ssgd", epochs=2, seed=3, rho=1), Xte, yte)
    np.testing.assert_allclose(a["model"].W, b["model"].W, atol=1e-10)


def test_guided_replay_changes_trajectory_only_at_windows():
    """With rho larger than total updates, g-variants == plain variants."""
    X, y, k = load_dataset("new_thyroid", seed=0)
    Xtr, ytr, Xte, yte = train_test_split(X, y, seed=0)
    big_rho = 10 ** 6
    a = train_ps(Xtr, ytr, k, PSConfig(mode="seq", guided=False, epochs=1, seed=1, rho=big_rho), Xte, yte)
    b = train_ps(Xtr, ytr, k, PSConfig(mode="seq", guided=True, epochs=1, seed=1, rho=big_rho), Xte, yte)
    np.testing.assert_allclose(a["model"].W, b["model"].W, atol=1e-12)


def test_asgd_applies_every_gradient_once():
    X, y, k = load_dataset("haberman", seed=0)
    Xtr, ytr, Xte, yte = train_test_split(X, y, seed=0)
    out = train_ps(Xtr, ytr, k, PSConfig(mode="asgd", epochs=1, seed=0), Xte, yte)
    n_batches = (len(Xtr) - max(8, int(0.2 * len(Xtr)))) // 16
    assert len(out["history"]) == n_batches


def test_all_algo_names_run():
    X, y, k = load_dataset("cancer", seed=0)
    Xtr, ytr, Xte, yte = train_test_split(X, y, seed=0)
    for name in ALGO_NAMES.values():
        out = train_ps(Xtr[:200], ytr[:200], k, algo_config(name, epochs=1, seed=0), Xte, yte)
        assert 0.0 <= out["test_accuracy"] <= 1.0, name
        assert np.isfinite(out["val_loss"]), name


# ------------------------------------------------------ distributed (fused)


def test_fused_correction_equals_manual_weighted_gradient():
    """grad(mean + sum w_i L_i) == mean-grad + sum w_i grad(L_i)."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.models.module import split_params
    from repro.data import make_batch_for

    cfg = get_config("yi_9b").reduced()
    params, _ = split_params(T.model_init(jax.random.PRNGKey(0), cfg))
    batch = {k: jnp.asarray(v) for k, v in make_batch_for(cfg, 16, 4, seed=0).items()}
    c = 4
    w = jnp.asarray([0.0, 0.7, 0.3, 0.0])

    def total(p):
        per_ex, aux, _ = T.forward_train(p, batch, cfg)
        E = per_ex.reshape(c, -1).mean(1)
        return E.mean() + (w * E).sum()

    def worker_loss(p, i):
        per_ex, aux, _ = T.forward_train(p, batch, cfg)
        return per_ex.reshape(c, -1).mean(1)[i]

    g_total = jax.grad(total)(params)
    g_mean = jax.grad(lambda p: jax.tree.map(lambda x: x, total(p)) * 0 + sum(
        worker_loss(p, i) for i in range(c)) / c)(params)
    g1 = jax.grad(lambda p: worker_loss(p, 1))(params)
    g2 = jax.grad(lambda p: worker_loss(p, 2))(params)
    leaf = lambda t: np.asarray(jax.tree.leaves(t)[0], np.float32)
    np.testing.assert_allclose(
        leaf(g_total), leaf(g_mean) + 0.7 * leaf(g1) + 0.3 * leaf(g2), atol=1e-4, rtol=1e-3
    )


def test_train_step_guided_correction_fires_at_window_end():
    from repro.configs import get_config
    from repro.optim import constant, get_optimizer
    from repro.data import make_batch_for
    from repro.engine import mesh as M
    from repro.sharding.rules import LOCAL_CTX

    cfg = get_config("yi_9b").reduced()
    gcfg = G.GuidedConfig(mode="ssgd", guided=True, rho=3)
    opt = get_optimizer("sgd")
    params, _, gstate = M.init_train_state(jax.random.PRNGKey(0), cfg, gcfg, opt, n_workers=2)
    step = jax.jit(M.build_train_step(cfg, gcfg, opt, LOCAL_CTX, constant(1e-2), n_workers=2))
    corr = []
    batch = {k: jnp.asarray(v) for k, v in make_batch_for(cfg, 16, 4, seed=0).items()}
    for i in range(7):
        params, gstate, m = step(params, gstate, batch)
        corr.append(float(m["corr_weight_sum"]))
    # correction fires exactly when (step % rho == rho-1), after warmup window
    assert corr[0] == 0.0 and corr[1] == 0.0
    fired = [i for i, c in enumerate(corr) if c > 0]
    assert all((i + 1) % 3 == 0 for i in fired)
    assert len(fired) >= 1  # scores accumulate -> correction actually fires
