"""Pluggable delay-compensation strategies (the `DelayCompensator` registry).

The paper's guided correction, its two-pass literal variant, DC-ASGD's Taylor
compensation (Zheng et al. 2017) and Gap-Aware dampening (Barkai et al. 2019)
are all the same shape: a small set of hooks around one SPMD train step.
A strategy never owns the training loop — it plugs into the four seams the
generic step in `repro.engine.mesh` exposes:

  init(params, n_workers)        -> strategy-owned extra state (a pytree; ())
  correction_weights(state, c)   -> (c,) weights folded into THIS backward
                                    pass as sum_i w_i * L_i ("fused" replay)
  compensate_grads(grads, params, state) -> adjusted gradients (post-backward)
  correct(params, state, lr, weighted_grad_fn) -> params after the optimizer
                                    step (the paper's literal second update)
  score(state, worker_loss, avg_loss) -> new (c,) consistency scores
  update_extra(state, grads)     -> next extra state (window bookkeeping)

Register new schemes with `@register_compensator("name")`; they become
selectable from `ExperimentSpec(strategy="name")` and the `--strategy` flag of
`repro.launch.train` without touching the train step. See DESIGN.md §2 for the
protocol contract and a migration table from the legacy APIs.
"""
from __future__ import annotations

from typing import Callable, Dict, Type

import jax
import jax.numpy as jnp

from repro.core import guided as G
from repro.engine.spec import needs_stale_message


class DelayCompensator:
    """Base strategy: no compensation, paper-faithful consistency scoring.

    Subclasses override only the hooks they need. All hooks are traced inside
    the jitted train step, so they must be pure and shape-stable; anything
    data-dependent goes through `state` (a `GuidedState`, whose `extra` field
    belongs to the strategy).
    """

    name = "none"

    def __init__(self, gcfg: G.GuidedConfig):
        self.gcfg = gcfg

    # ------------------------------------------------------------- lifecycle
    def init(self, params, n_workers: int):
        """Initial strategy-owned state, stored in GuidedState.extra."""
        return ()

    # ---------------------------------------------------------------- hooks
    @property
    def needs_correction(self) -> bool:
        """False when `correct` is the identity: the train step then never
        builds the second weighted forward+backward closure, so strategies
        like guided_fused don't pay HLO size / compile time for a replay path
        they never take. A subclass that overrides `correct` is assumed to
        need it unless it also overrides this property (DcAsgdGuided: only
        its two_pass flavour corrects)."""
        return type(self).correct is not DelayCompensator.correct

    def correction_weights(self, state: G.GuidedState, c: int):
        """(c,) weights for the consistency-weighted loss term of THIS step's
        backward pass (zero except at window end for fused guided replay)."""
        return jnp.zeros((c,), jnp.float32)

    def compensate_grads(self, grads, params, state: G.GuidedState):
        """Adjust freshly computed gradients (e.g. staleness Taylor terms)."""
        return grads

    def correct(self, params, state: G.GuidedState, lr, weighted_grad_fn: Callable):
        """Post-optimizer-step parameter correction. `weighted_grad_fn(p, w)`
        returns the gradient of the w-weighted per-worker loss at p."""
        return params

    def score(self, state: G.GuidedState, worker_loss, avg_loss):
        """New accumulated consistency scores (pre window-reset)."""
        return G.update_scores(state, self.gcfg, worker_loss, avg_loss)

    def update_extra(self, state: G.GuidedState, grads):
        """Next value of the strategy-owned extra state."""
        return state.extra

    # ------------------------------------------------------- scan-sim hooks
    # The jitted delay-simulation backend (repro.engine.delaysim) drives the
    # same registry through these three seams instead of reimplementing the
    # paper's guided logic in its scan body (DESIGN.md §6). They trace inside
    # lax.scan, so the same purity/shape rules apply as for the mesh hooks.

    #: True -> the scan body tracks per-arrival consistency (loss-before /
    #: loss-after of the applied batch + verification loss) and calls
    #: sim_score / sim_replay; False skips that bookkeeping entirely.
    sim_guided = False

    def sim_kernel_lambda(self) -> float:
        """DC-ASGD Taylor coefficient folded directly into the fused Pallas
        apply kernel (g~ = g + lam*g*g*(W - W_stale)). Non-zero means the
        kernel performs the compensation and compensate_grads is skipped."""
        return 0.0

    def sim_kernel(self, optimizer: str, *, impl: str = "auto", **hypers):
        """The fused whole-update callable (gradient → compensation →
        accumulator → weight, one dispatch) for this strategy × `optimizer`,
        or None when the hot loop must fall back to the two-phase path
        (compensate_grads, then a plain lam=0 apply).

        Fusion is sound exactly when this strategy's compensation is the
        kernel's lam fold: either compensate_grads is not overridden (the
        identity — guided/none strategies), or sim_kernel_lambda() is
        non-zero (DC-ASGD family, whose Taylor term IS the fold). Strategies
        with bespoke gradient math (gap_aware) get None regardless of the
        optimizer; so do optimizers without a fused kernel (adagrad). The
        fallback matrix is tabulated in DESIGN.md §11. `hypers` are the
        optimizer's python-float hyperparameters, baked into the closure."""
        overridden = (type(self).compensate_grads
                      is not DelayCompensator.compensate_grads)
        if overridden and not self.sim_kernel_lambda():
            return None
        from repro.kernels.guided_update.ops import FUSED_OPTIMIZERS, fused_update_for

        if optimizer not in FUSED_OPTIMIZERS:
            return None
        return fused_update_for(optimizer, impl=impl, **hypers)

    def sim_score(self, d_own, d_avg, prev_avg_err):
        """Paper Fig. 7 consistency score of ONE arrival: the applied batch is
        consistent when the step moved both its own loss (d_own) and the
        verification-average loss (d_avg) downward; ranked by the relative
        average-error drop. Returns 0 for inconsistent arrivals (never stored).
        """
        ok = jnp.isfinite(prev_avg_err) & (d_own < 0) & (d_avg < 0)
        return jnp.where(ok, -d_avg / (jnp.abs(prev_avg_err) + 1e-12), 0.0)

    def sim_replay(self, W, window_scores, window_grads, lr):
        """Window-end replay (Fig. 7 line 8): re-apply the stored gradients of
        the <=max_consistent most consistent arrivals of the closing window,
        plain SGD style (W -= lr * g), exactly as printed in the paper.
        top_k breaks ties by lowest index = arrival order, matching the
        reference loop's stable sort over psi insertion order."""
        k = min(self.gcfg.max_consistent, window_scores.shape[0])
        top_v, top_i = jax.lax.top_k(window_scores, k)
        sel = (top_v > 0).astype(W.dtype)
        return W - lr * jnp.tensordot(sel, window_grads[top_i], axes=1)


def sim_shim_state(i, Wf, prev_avg, c: int) -> G.GuidedState:
    """Minimal GuidedState for the mesh-hook signatures on the single-matrix
    backends (scan sim, dist chief): only w_stale is guaranteed (what
    compensate_grads reads); window bookkeeping lives in the caller's carry."""
    z = jnp.zeros((c,), Wf.dtype)
    return G.GuidedState(step=i, score=z, prev_worker_loss=z,
                         prev_avg_loss=prev_avg, w_stale=Wf, opt_state=(), extra=())


def _fused_weights(state: G.GuidedState, gcfg: G.GuidedConfig, c: int):
    """(c,) top-k consistency weights at window end, zeros otherwise."""
    return jnp.where(
        G.is_window_end(state.step, gcfg),
        G.correction_weights(state.score, gcfg),
        jnp.zeros((c,), jnp.float32),
    )


def _two_pass_correct(params, state: G.GuidedState, gcfg: G.GuidedConfig, lr,
                      weighted_grad_fn):
    """The paper's literal Fig. 7 second sequential update at window end."""

    def replay(p):
        w = G.correction_weights(state.score, gcfg)
        g2 = weighted_grad_fn(p, w)
        return jax.tree.map(lambda pi, gi: pi - lr * gi.astype(pi.dtype), p, g2)

    return jax.lax.cond(G.is_window_end(state.step, gcfg), replay, lambda p: p, params)


class GuidedFused(DelayCompensator):
    """The paper's guided replay, fused into the main backward pass:
    grad(sum_i w_i L_i) = sum_i w_i g_i, so replaying the <=max_consistent
    most consistent workers' gradients costs one weighted loss term — no
    stored gradients, no extra collective (DESIGN.md §3). Selecting this
    strategy by name is authoritative: it corrects regardless of the
    GuidedConfig.guided/correction flags."""

    name = "guided_fused"
    sim_guided = True

    def correction_weights(self, state: G.GuidedState, c: int):
        return _fused_weights(state, self.gcfg, c)


class GuidedTwoPass(DelayCompensator):
    """The paper's literal Fig. 7 second sequential update: every rho steps,
    a lax.cond'd second backward of the consistency-weighted loss at the
    already-moved iterate. Like guided_fused, the name is authoritative."""

    name = "guided_two_pass"
    sim_guided = True  # the sim has exactly one guided path (the literal replay)

    def correct(self, params, state: G.GuidedState, lr, weighted_grad_fn):
        return _two_pass_correct(params, state, self.gcfg, lr, weighted_grad_fn)


class DcAsgd(DelayCompensator):
    """DC-ASGD (Zheng et al. 2017): g~ = g + lambda * g ⊙ g ⊙ (W_t - W_stale).
    Pure Taylor compensation; no guided replay (see DcAsgdGuided)."""

    name = "dc_asgd"

    def sim_kernel_lambda(self) -> float:
        return self.gcfg.dc_lambda

    def compensate_grads(self, grads, params, state: G.GuidedState):
        return G.compensate_dc_asgd(grads, params, state.w_stale, self.gcfg.dc_lambda)


class DcAsgdGuided(DcAsgd):
    """DC-ASGD composed with the paper's guided replay — the legacy
    GuidedConfig(mode="dc_asgd", guided=True) combinations as one named
    strategy. The replay flavour follows gcfg.correction ("fused" folds the
    weights into the backward pass, "two_pass" runs the literal second
    update), preserving every legacy combination bit-for-bit."""

    name = "dc_asgd_guided"
    sim_guided = True

    @property
    def needs_correction(self) -> bool:
        return self.gcfg.correction == "two_pass"

    def correction_weights(self, state: G.GuidedState, c: int):
        if self.gcfg.correction != "fused":
            return jnp.zeros((c,), jnp.float32)
        return _fused_weights(state, self.gcfg, c)

    def correct(self, params, state: G.GuidedState, lr, weighted_grad_fn):
        if self.gcfg.correction != "two_pass":
            return params
        return _two_pass_correct(params, state, self.gcfg, lr, weighted_grad_fn)


class GapAware(DelayCompensator):
    """Gap-Aware staleness dampening (Barkai et al. 2019, arXiv:1909.10802):
    each gradient coordinate is divided by 1 + |W_t - W_stale| / rms(g) — the
    further the parameter has already moved since the gradient was computed,
    the less that stale coordinate is trusted. Needs mode="asgd" (w_stale).

    This is the ~30-line "new scheme as a plugin" exemplar: it was added
    without touching the train step or `train/steps.py`.
    """

    name = "gap_aware"

    def __init__(self, gcfg: G.GuidedConfig):
        if not gcfg.needs_stale:
            raise ValueError(
                needs_stale_message("gap_aware", "dampens by |W - w_stale|", gcfg.mode)
            )
        super().__init__(gcfg)

    def compensate_grads(self, grads, params, state: G.GuidedState):
        def one(g, p, ps):
            # compute dtype follows the gradients (>= f32): bf16 mesh grads
            # upcast as before, the scan backend's f64 regime stays f64
            ct = jnp.promote_types(g.dtype, jnp.float32)
            gc = g.astype(ct)
            gap = jnp.abs(p.astype(ct) - ps.astype(ct))
            rms = jnp.sqrt(jnp.mean(jnp.square(gc)) + 1e-12)
            return (gc / (1.0 + gap / jnp.maximum(rms, 1e-12))).astype(g.dtype)

        return jax.tree.map(one, grads, params, state.w_stale)


# ----------------------------------------------------------------- registry

_REGISTRY: Dict[str, Type[DelayCompensator]] = {}


def register_compensator(name: str):
    """Class decorator: `@register_compensator("my_scheme")` makes the scheme
    selectable by name from ExperimentSpec / the train CLI."""

    def deco(cls: Type[DelayCompensator]):
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


for _cls in (DelayCompensator, GuidedFused, GuidedTwoPass, DcAsgd, DcAsgdGuided, GapAware):
    _REGISTRY[_cls.name] = _cls


def compensator_names() -> tuple:
    return tuple(sorted(_REGISTRY))


def get_compensator(name: str, gcfg: G.GuidedConfig) -> DelayCompensator:
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown delay-compensation strategy {name!r}; "
            f"registered: {', '.join(compensator_names())}"
        ) from None
    return cls(gcfg)


def strategy_name_for(gcfg: G.GuidedConfig) -> str:
    """Legacy GuidedConfig -> registry name (what `engine.mesh` runs when no
    strategy is named)."""
    if gcfg.mode == "dc_asgd":
        return "dc_asgd_guided" if gcfg.guided else "dc_asgd"
    if gcfg.guided:
        return "guided_two_pass" if gcfg.correction == "two_pass" else "guided_fused"
    return "none"
