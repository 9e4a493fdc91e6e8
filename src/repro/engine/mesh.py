"""The strategy-driven SPMD train step (mesh backend of `repro.engine`).

This is the single train-step implementation; `Trainer` dispatches it in
chunks (`repro.engine.trainloop`). The paper's technique meets the mesh here (DESIGN.md §3):

  * per-worker losses E_i come free from the per-example loss vector (each
    data shard of the batch is one of the paper's c workers);
  * the active `DelayCompensator` strategy plugs into four seams —
    correction weights folded into the SAME backward pass
    (grad(sum w_i L_i) = sum w_i g_i; zero extra collectives), gradient
    compensation after the backward, a post-optimizer parameter correction,
    and the consistency-score update;
  * ASGD staleness is simulated through gstate.w_stale exactly as before.

Each op's phase is named in its `op_name` metadata (`jax.named_scope`; the
compiled instructions are the same without it): `forward` (its backward
carries `transpose(jvp(forward))`, its remat recompute
`rematted_computation`), `loss_head` within it, `update` (compensation and
the optimizer apply) and `guided` (correction weights, a second update,
the consistency bookkeeping). xprof's framework-op view groups by them.

Nothing here hard-codes a compensation scheme: new strategies registered in
`repro.engine.strategies` run through this step unchanged.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common import tree_add
from repro.core import guided as G
from repro.engine.strategies import DelayCompensator, get_compensator, strategy_name_for
from repro.models import transformer as T
from repro.models.module import split_params
from repro.optim import Optimizer
from repro.sharding.rules import DEFAULT_RULES, LOCAL_CTX, MULTIPOD_RULES, ShardCtx


def build_ctx(mesh_kind: str) -> ShardCtx:
    """Shared mesh-kind -> ShardCtx resolution (train and serve launchers)."""
    if mesh_kind == "local":
        return LOCAL_CTX
    if mesh_kind == "host":
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(data=len(jax.devices()), model=1)
        return ShardCtx(mesh=mesh, rules=DEFAULT_RULES)
    if mesh_kind == "prod":
        from repro.launch.mesh import make_production_mesh

        return ShardCtx(mesh=make_production_mesh(), rules=DEFAULT_RULES)
    if mesh_kind == "prod-multipod":
        from repro.launch.mesh import make_production_mesh

        return ShardCtx(mesh=make_production_mesh(multi_pod=True), rules=MULTIPOD_RULES,
                        data_axes=("pod", "data"))
    raise ValueError(mesh_kind)


def resolve_strategy(gcfg: G.GuidedConfig, strategy=None) -> DelayCompensator:
    """Accept a DelayCompensator instance, a registry name, or None (derive
    the strategy the legacy GuidedConfig flags imply)."""
    if isinstance(strategy, DelayCompensator):
        return strategy
    return get_compensator(strategy or strategy_name_for(gcfg), gcfg)


def init_train_state(key, cfg, gcfg: G.GuidedConfig, opt: Optimizer, n_workers: int,
                     strategy=None):
    """Model params + logical annotations + GuidedState (incl. strategy extra)."""
    strategy = resolve_strategy(gcfg, strategy)
    boxed = T.model_init(key, cfg)
    params, logical = split_params(boxed)
    gstate = G.guided_init(gcfg, params, opt, n_workers)
    return params, logical, gstate._replace(extra=strategy.init(params, n_workers))


def _microbatches(batch, n_micro: int, c: int):
    """Split (B, ...) -> (n_micro, B/n_micro, ...) preserving the worker
    (data-shard) structure: every microbatch contains an equal slice of every
    worker's rows, so per-worker losses stay well-defined and no cross-shard
    traffic is introduced (the leading c-blocking is untouched per shard)."""

    def one(x):
        B = x.shape[0]
        b = B // c
        xr = x.reshape(c, n_micro, b // n_micro, *x.shape[1:])
        xr = jnp.moveaxis(xr, 1, 0)
        return xr.reshape(n_micro, B // n_micro, *x.shape[1:])

    return jax.tree.map(one, batch)


def _fused_apply(fused, name: str, lam: float, cfg, ctx: ShardCtx):
    """`apply(params, grads, w_ref, opt_state, lr) -> (params, opt_state)`:
    the fused whole-update over the parameter tree. On a distributed mesh a
    Pallas kernel has no partitioning rule, so the update runs under
    `shard_map` on each device's own shard of every leaf — the leaves keep
    the logical-rule sharding of the params (FSDP over `data`), and the
    elementwise update needs no collective."""
    from repro.kernels.guided_update.ops import tree_fused_update

    def apply(params, grads, w_ref, opt_state, lr):
        return tree_fused_update(fused, name, params, grads, w_ref, opt_state,
                                 lr, lam)

    if not ctx.distributed:
        return apply

    from jax.sharding import PartitionSpec as P

    from repro.models.module import logical_tree
    from repro.sharding.rules import mirror_params, shardings_for

    logical = logical_tree(jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg)))

    def sharded(params, grads, w_ref, opt_state, lr):
        specs = jax.tree.map(lambda s: s.spec,
                             shardings_for(logical, params, ctx.mesh, ctx.rules))
        opt_specs = mirror_params(opt_state, params, specs, P())
        return jax.shard_map(
            apply, mesh=ctx.mesh,
            in_specs=(specs, specs, specs, opt_specs, P()),
            out_specs=(specs, opt_specs),
            check_vma=False,
        )(params, grads, w_ref, opt_state, lr)

    return sharded


def build_train_step(cfg, gcfg: G.GuidedConfig, opt: Optimizer, ctx: ShardCtx, lr_schedule,
                     n_micro: int = 1, n_workers: int = 0, strategy=None):
    """Returns train_step(params, gstate, batch) -> (params, gstate, metrics).

    n_micro > 1 enables microbatched gradient accumulation: the remat-saved
    per-layer activation stack scales with the microbatch, which is what lets
    train_4k (global 256 x 4096) fit a 16 GiB chip at 9B-123B scale.
    n_workers overrides the paper's worker count c (defaults to the number of
    data shards; on a single device it emulates c workers by batch slicing).
    `strategy` is a DelayCompensator instance or registry name; None derives
    it from the GuidedConfig flags (legacy behaviour)."""
    strategy = resolve_strategy(gcfg, strategy)
    c = n_workers or max(ctx.n_workers, 1)

    # Whole-update fusion (DESIGN.md §11): when the strategy's compensation is
    # the kernel's lam fold and the optimizer has a fused kernel, ONE fused
    # dispatch per leaf (compensate → accumulator → apply) replaces
    # compensate_grads + opt.update + tree_add. sim_kernel returns None for
    # bespoke-compensation strategies (gap_aware); hypers must be known and
    # weight_decay-free for the fused closure to match opt.update bit-for-bit.
    # On interpret backends sim_kernel resolves to the pure-jnp reference
    # (impl="auto"), so the cpu mesh never pays per-leaf emulated Pallas calls.
    fused = None
    fused_lam = 0.0
    if opt.hypers is not None and opt.name in ("sgd", "momentum", "adam"):
        hy = dict(opt.hypers)
        if not hy.pop("weight_decay", 0.0):
            fused = strategy.sim_kernel(opt.name, **hy)
            fused_lam = float(strategy.sim_kernel_lambda())
    if fused is not None:
        fused_apply = _fused_apply(fused, opt.name, fused_lam, cfg, ctx)

    def loss_fn(p, batch, corr_w):
        with jax.named_scope("forward"):
            per_ex, aux, _ = T.forward_train(p, batch, cfg, ctx)
        B = per_ex.shape[0]
        E_i = per_ex.reshape(c, B // c).mean(axis=1)
        mean_loss = E_i.mean()
        total = mean_loss + aux + (jax.lax.stop_gradient(corr_w) * E_i).sum() * gcfg.correction_scale
        return total, (E_i, mean_loss)

    def grads_and_losses(grad_at, batch, corr_w):
        if n_micro == 1:
            (_, (E_i, mean_loss)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                grad_at, batch, corr_w
            )
            return grads, E_i, mean_loss

        mbs = _microbatches(batch, n_micro, c)

        def body(acc, mb):
            g_acc, e_acc, l_acc = acc
            (_, (E_i, ml)), g = jax.value_and_grad(loss_fn, has_aux=True)(grad_at, mb, corr_w)
            g_acc = jax.tree.map(lambda a, gi: a + gi.astype(jnp.float32), g_acc, g)
            return (g_acc, e_acc + E_i, l_acc + ml), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), grad_at)
        (g_sum, e_sum, l_sum), _ = jax.lax.scan(
            body, (g0, jnp.zeros((c,), jnp.float32), jnp.zeros((), jnp.float32)), mbs
        )
        grads = jax.tree.map(lambda g, p: (g / n_micro).astype(p.dtype), g_sum, grad_at)
        return grads, e_sum / n_micro, l_sum / n_micro

    def weighted_grad_fn(batch):
        """grad of the consistency-weighted per-worker loss (uniform term off) —
        handed to strategy.correct for the paper's literal second update."""

        def at(p, w):
            def wl(q):
                with jax.named_scope("forward"):
                    per_ex, _, _ = T.forward_train(q, batch, cfg, ctx)
                return (w * per_ex.reshape(c, -1).mean(1)).sum()

            return jax.grad(wl)(p)

        return at

    def train_step(params, gstate: G.GuidedState, batch):
        with jax.named_scope("guided"):
            corr_w = strategy.correction_weights(gstate, c)

        grad_at = gstate.w_stale if gcfg.needs_stale else params
        grads, E_i, mean_loss = grads_and_losses(grad_at, batch, corr_w)

        lr = lr_schedule(gstate.step)
        lr_eff = lr * c if gcfg.mode != "seq" else lr
        with jax.named_scope("update"):
            if fused is not None:
                # compensation rides inside the fused update as the lam fold
                # (identity for non-dc strategies: lam == 0); w_stale only
                # matters when lam != 0, which implies gcfg.needs_stale
                w_ref = gstate.w_stale if gcfg.needs_stale else params
                params, opt_state = fused_apply(params, grads, w_ref,
                                                gstate.opt_state, lr_eff)
            else:
                grads = strategy.compensate_grads(grads, params, gstate)
                updates, opt_state = opt.update(grads, gstate.opt_state, params, lr_eff)
                params = tree_add(params, updates)
        with jax.named_scope("guided"):
            if strategy.needs_correction:
                # only correcting strategies trace the second weighted
                # forward+backward; for the rest (guided_fused folds its
                # replay into THIS backward) the closure never enters the HLO
                params = strategy.correct(params, gstate, lr, weighted_grad_fn(batch))

            gstate = G.advance(
                gstate, gcfg, opt_state, params, E_i, mean_loss,
                extra=strategy.update_extra(gstate, grads),
                score=strategy.score(gstate, E_i, mean_loss),
            )
        metrics = {
            "loss": mean_loss,
            "worker_loss_var": jnp.var(E_i),
            "corr_weight_sum": jnp.sum(corr_w),
            "lr": lr,
            "step": gstate.step,
        }
        return params, gstate, metrics

    return train_step
