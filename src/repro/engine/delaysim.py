"""Jitted vectorized delay-simulation backend (`ExperimentSpec(backend="scan")`).

The numpy reference (`core.parameter_server.train_ps`) is an event-driven
Python loop: per arrival it recomputes the verification loss, applies the
update, and runs the guided bookkeeping — sequential by construction, which is
exactly the bottleneck the paper tells us to parallelize. This module replaces
it with three orthogonal pieces:

  1. **DelaySchedule** (core.parameter_server): the delay topology — which
     mini-batch arrives at each server step and how stale the weights its
     gradient was computed at are — is *precomputed* by replaying the
     reference loop's rng protocol with the gradient math elided. seq/ssgd/
     asgd become pure schedule generators, and because the schedule is data
     (not control flow), new topologies are one sampler each: constant-delay,
     heavy-tail (Pareto), straggler, heterogeneous-worker (TOPOLOGY_SAMPLERS).
  2. **One jitted lax.scan** over the arrival table. A ring buffer of the last
     `max_staleness+1` weight states serves stale fetches; the fused Pallas
     `guided_update` kernel is the apply path (compiled on gpu/tpu, interpret
     on cpu); the guided consistency scoring and window replay run through the
     `DelayCompensator` registry's scan-sim hooks (sim_score / sim_replay /
     compensate_grads) — the same strategy objects the mesh backend plugs in,
     so dc_asgd and gap_aware now run at paper scale too.
  3. **jax.vmap over the seed axis**: `n_seeds=k` sweeps seeds
     spec.seed..spec.seed+k-1 in ONE compile, the way the paper's 30-run
     protocol is meant to be executed (see benchmarks/run.py BENCH_delaysim).

Parity: with the default topologies the scan trajectory reproduces train_ps
to float64 round-off, locked in by tests/test_delaysim.py (the numpy loop
stays as the reference). Everything runs in float64 via a scoped jax.enable_x64
(f32 on TPU, where x64 is unsupported — parity is a CPU/GPU property).
"""
from __future__ import annotations

from collections import OrderedDict
from contextlib import nullcontext

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.parameter_server import (  # noqa: F401  (DelaySchedule re-export)
    DelaySchedule,
    LogisticRegression,
    prepare_run,
)
from repro.engine.spec import ExperimentSpec
from repro.engine.strategies import DelayCompensator, get_compensator
from repro.kernels.guided_update.ops import FUSED_ACC_ARITY, fused_update_for

# ------------------------------------------------------------- topologies
# Hoisted to repro.common.topologies (one source of truth shared with the
# dist fault injector); re-exported here for compat.
from repro.common.topologies import TOPOLOGY_SAMPLERS  # noqa: F401, E402


def _x64():
    """Scoped float64: the paper-scale sim matches the numpy reference to
    round-off. TPUs have no f64 — there the scan runs in f32 (no parity
    guarantee, same algorithm)."""
    return jax.enable_x64() if jax.default_backend() != "tpu" else nullcontext()


# ------------------------------------------------------- model math (jax)
# Literal transcriptions of core.parameter_server.LogisticRegression so the
# float64 scan reproduces the reference arithmetic. Labels arrive as one-hot
# masks precomputed outside the scan: `(z * y_oh).sum(1)` selects the own
# logit exactly (the masked terms are exact float zeros) without the
# per-step gathers XLA CPU scalarizes.


def _loss(W, Xa, y_oh):
    z = Xa @ W
    z = z - z.max(axis=1, keepdims=True)
    lse = jnp.log(jnp.exp(z).sum(axis=1))
    own = (z * y_oh).sum(axis=1)
    return jnp.mean(lse - own)


def _grad(W, Xa, y_oh):
    z = Xa @ W
    z = z - z.max(axis=1, keepdims=True)
    p = jnp.exp(z)
    p = p / p.sum(axis=1, keepdims=True)
    p = p - y_oh
    return Xa.T @ p / Xa.shape[0]


def _aug(X):
    return np.concatenate([X, np.ones((X.shape[0], 1))], axis=1)


# ------------------------------------------------------------ scan runner


# _shim_state moved to repro.engine.strategies.sim_shim_state: the dist
# chief drives the same hooks on live pushes and needs the identical shim.
from repro.engine.strategies import sim_shim_state as _shim_state  # noqa: E402


# Bounded LRU of jitted runners. Every distinct (shapes, strategy, config)
# key pins a compiled executable; an unbounded dict made long parameter
# sweeps (rho/k ablations, multi-dataset tables) leak one compile per
# configuration forever. 8 keeps the warm-reuse benefit within a sweep while
# bounding the pinned-compile footprint; benchmarks additionally call
# clear_runners() between sweeps.
_RUNNERS: OrderedDict = OrderedDict()
_RUNNERS_MAX = 8


def clear_runners() -> None:
    """Drop every cached jitted runner (and its pinned compiled executable).
    Benchmarks call this between sweeps so one workload's compiles don't stay
    resident through the next."""
    _RUNNERS.clear()


def _build_runner(key, strategy: DelayCompensator, T: int, n_classes: int,
                  R: int, rho: int, c: int, optimizer: str, fused_dc: bool,
                  beta: float, eps: float):
    """Compile (LRU-cached) the vmapped scan for one static configuration.
    `beta`/`eps` are python floats, baked into the trace (same values the
    reference loop uses, so the f64 parity regime is unchanged)."""
    if key in _RUNNERS:
        _RUNNERS.move_to_end(key)
        return _RUNNERS[key]
    guided = strategy.sim_guided

    # Whole-update apply path (DESIGN.md §11): the strategy registry selects
    # the optimizer-fused kernel via sim_kernel — compensation (lam), the
    # accumulator recurrence and the weight apply in ONE dispatch. None means
    # two-phase: compensate_grads already ran in the scan body (fused_dc is
    # False there), so the same kernel applies plain with the traced lam=0.
    # adagrad keeps its 3-op inline XLA form (no fused kernel; the lam fold
    # stays inline exactly as before, preserving the dc_asgd f64 ordering).
    hypers = {"rmsprop": dict(beta=beta, eps=eps),
              "momentum": dict(beta=0.9),
              "adam": dict(b1=0.9, b2=0.999, eps=eps)}.get(optimizer, {})
    kern = None
    if optimizer != "adagrad":
        kern = strategy.sim_kernel(optimizer, impl="kernel", **hypers)
        if kern is None:
            kern = fused_update_for(optimizer, impl="kernel", **hypers)
    n_acc = 1 if optimizer == "adagrad" else FUSED_ACC_ARITY[optimizer]

    def apply_update(W, g, Wf, acc, i, lr, lam):
        if optimizer == "adagrad":
            (r,) = acc
            gt = g + lam * g * g * (W - Wf)
            r = r + gt * gt
            return W - lr * gt / jnp.sqrt(r + eps), (r,)
        # i+1 = the already-incremented adam step; ignored by the others
        return kern(W, g, Wf, acc, i + 1, lr, lam)

    def one_seed(W0, Xa_all, rows, yb, Xv, yv, stale, lr, lam):
        P, k = W0.shape
        rho_w = max(rho, 1)
        # hoisted out of the scan: batch gather (T*bs rows) + one-hot labels
        Xb = jnp.take(Xa_all, rows.reshape(-1), axis=0).reshape(*rows.shape, P)
        yb_oh = jax.nn.one_hot(yb, k, dtype=W0.dtype)
        yv_oh = jax.nn.one_hot(yv, k, dtype=W0.dtype)

        def step(carry, xs):
            W, ring, acc, prev_avg, wscore, wgrads = carry
            i, Xa, yoh, s = xs
            Wf = jnp.take(ring, jnp.mod(i - s, R), axis=0)
            g = _grad(Wf, Xa, yoh)
            if not fused_dc:
                g = strategy.compensate_grads(g, W, _shim_state(i, Wf, prev_avg, c))
            loss_before = _loss(W, Xa, yoh) if guided else 0.0
            W2, acc2 = apply_update(W, g, Wf, acc, i, lr, lam)
            avg = _loss(W2, Xv, yv_oh)
            if guided:
                d_avg = avg - prev_avg
                d_own = _loss(W2, Xa, yoh) - loss_before
                sc = strategy.sim_score(d_own, d_avg, prev_avg)
                pos = jnp.mod(i, rho_w)
                wscore = wscore.at[pos].set(sc)
                wgrads = wgrads.at[pos].set(g)
                end = jnp.equal(jnp.mod(i + 1, rho_w), 0)
                W3 = jnp.where(end, strategy.sim_replay(W2, wscore, wgrads, lr), W2)
                wscore = jnp.where(end, jnp.zeros_like(wscore), wscore)
            else:
                W3 = W2
            ring = ring.at[jnp.mod(i + 1, R)].set(W3)
            return (W3, ring, acc2, avg, wscore, wgrads), avg

        carry0 = (
            W0,
            jnp.tile(W0[None], (R, 1, 1)),
            tuple(jnp.zeros_like(W0) for _ in range(n_acc)),
            jnp.asarray(jnp.inf, W0.dtype),
            jnp.zeros((rho_w,), W0.dtype),
            jnp.zeros((rho_w, P, k), W0.dtype),
        )
        xs = (jnp.arange(T, dtype=jnp.int32), Xb, yb_oh, stale)
        carry, avgs = jax.lax.scan(step, carry0, xs)
        return carry[0], avgs

    # lint: allow[missing-donate] runner is LRU-cached and re-invoked; inputs must survive the call
    fn = jax.jit(jax.vmap(one_seed, in_axes=(0, 0, 0, 0, 0, 0, 0, None, None)))
    _RUNNERS[key] = fn
    while len(_RUNNERS) > _RUNNERS_MAX:
        _RUNNERS.popitem(last=False)
    return fn


# ------------------------------------------------------------- entry point


def run(spec: ExperimentSpec, X, y, n_classes: int, Xtest=None, ytest=None,
        strategy: DelayCompensator = None) -> dict:
    """Run `spec` on the scan backend. Same contract as train_ps (plus seed
    vectorization): returns train/val losses, per-arrival (t, avg_err)
    history, final model(s) and optional test accuracy. n_seeds == 1 returns
    scalars; n_seeds > 1 returns (n_seeds,) arrays and a list of per-seed
    models. `strategy` reuses an already resolved DelayCompensator (the
    Trainer's); None resolves spec.strategy from the registry."""
    gcfg = spec.to_guided_config()
    if strategy is None:
        strategy = get_compensator(spec.strategy, gcfg)
    topology = spec.resolved_topology
    try:
        sampler = TOPOLOGY_SAMPLERS[topology]
    except KeyError:
        raise KeyError(
            f"unknown topology {topology!r}; known: {', '.join(TOPOLOGY_SAMPLERS)}"
        ) from None

    preps = [
        prepare_run(X, y, n_classes, spec.to_schedule_config(seed=s),
                    delay_sampler=sampler, topology=topology)
        for s in range(spec.seed, spec.seed + spec.n_seeds)
    ]
    schedules = [p[3] for p in preps]
    T = schedules[0].n_steps
    if not all(s.n_steps == T for s in schedules):
        # a real exception, not an assert: this guards the vmapped stacking of
        # per-seed arrival tables and must survive `python -O`
        counts = {spec.seed + i: s.n_steps for i, s in enumerate(schedules)}
        raise ValueError(
            f"seeds disagree on arrival count under mode={spec.mode!r} "
            f"topology={spec.resolved_topology!r} epochs={spec.epochs} "
            f"batch_size={spec.batch_size}: per-seed n_steps {counts}; the "
            f"scan backend needs equal-length schedules to vmap "
            f"n_seeds={spec.n_seeds} (run seeds separately or use backend='sim')"
        )
    if T == 0:
        # n_train < batch_size yields zero arrivals; mirror train_ps (which
        # returns the untouched init) instead of tracing an empty scan
        return _empty_result(spec, preps, Xtest, ytest)
    r_needed = max(s.max_staleness for s in schedules) + 1
    # bucket the ring size: fewer recompiles across runs/modes (a few unused
    # slots of a (R, P, k) ring are free next to one saved jit compile)
    R = max(16, 1 << (r_needed - 1).bit_length())

    W0 = np.stack([p[0] for p in preps])
    Xtr = [p[1][0] for p in preps]
    ytr = [p[1][1] for p in preps]
    Xa_all = np.stack([_aug(x) for x in Xtr])          # (S, n_train, P)
    rows = np.stack([s.batch_rows for s in schedules])  # (S, T, bs)
    yb = np.stack([ytr[i][schedules[i].batch_rows] for i in range(len(preps))])
    Xv = np.stack([_aug(p[2][0]) for p in preps])
    yv = np.stack([p[2][1] for p in preps])
    stale = np.stack([s.staleness for s in schedules])

    fused_lam = strategy.sim_kernel_lambda()
    # the key carries every static the trace can bake in: shapes, the strategy
    # class AND its GuidedConfig (hook implementations may close over any of
    # its fields), the optimizer branch and the backend's dtype regime
    key = (
        type(strategy).__module__, type(strategy).__qualname__, spec.strategy,
        gcfg, T, n_classes, W0.shape[1], Xa_all.shape[1], rows.shape[2],
        Xv.shape[1], R, spec.rho, spec.max_consistent, spec.optimizer,
        bool(fused_lam), float(spec.rmsprop_beta), float(spec.eps),
        spec.n_seeds, jax.default_backend() == "tpu",
    )
    with _x64():
        fn = _build_runner(key, strategy, T, n_classes, R, spec.rho,
                           schedules[0].n_workers, spec.optimizer, bool(fused_lam),
                           float(spec.rmsprop_beta), float(spec.eps))
        Wf, avgs = fn(
            jnp.asarray(W0),
            jnp.asarray(Xa_all), jnp.asarray(rows, jnp.int32), jnp.asarray(yb, jnp.int32),
            jnp.asarray(Xv), jnp.asarray(yv, jnp.int32), jnp.asarray(stale, jnp.int32),
            jnp.asarray(float(spec.lr)), jnp.asarray(float(fused_lam)),
        )
        Wf = np.asarray(Wf)
        avgs = np.asarray(avgs)

    out = _final_metrics(spec, preps, Wf, Xtest, ytest)
    out["history"] = [(t + 1, float(avgs[0, t]) if spec.n_seeds == 1 else avgs[:, t])
                      for t in range(T)]
    out["n_steps"] = T
    out["schedule"] = schedules[0] if spec.n_seeds == 1 else schedules
    return out


def _final_metrics(spec: ExperimentSpec, preps, Wf, Xtest, ytest) -> dict:
    """train/val losses, per-seed models and test accuracy from the final
    weights, computed with the numpy reference model (identical arithmetic).
    n_seeds == 1 unwraps to scalars / a single model."""
    models = [LogisticRegression.from_weights(Wf[i]) for i in range(len(preps))]
    train_loss = np.array([models[i].loss(*preps[i][1]) for i in range(len(preps))])
    val_loss = np.array([models[i].loss(*preps[i][2]) for i in range(len(preps))])
    single = spec.n_seeds == 1
    out = {
        "train_loss": float(train_loss[0]) if single else train_loss,
        "val_loss": float(val_loss[0]) if single else val_loss,
        "model": models[0] if single else models,
    }
    if Xtest is not None:
        acc = np.array([m.accuracy(Xtest, ytest) for m in models])
        out["test_accuracy"] = float(acc[0]) if single else acc
    return out


def _empty_result(spec: ExperimentSpec, preps, Xtest, ytest) -> dict:
    out = _final_metrics(spec, preps, np.stack([p[0] for p in preps]), Xtest, ytest)
    out["history"] = []
    out["n_steps"] = 0
    out["schedule"] = preps[0][3] if spec.n_seeds == 1 else [p[3] for p in preps]
    return out
