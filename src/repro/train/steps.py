"""jit-able prefill / decode steps and the sharding-tree helpers.

The train step lives in `repro.engine.mesh` (`init_train_state`,
`build_train_step`), driven by the pluggable `DelayCompensator` strategies of
`repro.engine.strategies` (DESIGN.md §2-3).
"""
from __future__ import annotations

import jax

from repro.core import guided as G
from repro.models import transformer as T
from repro.optim import Optimizer
from repro.sharding.rules import ShardCtx, logical_to_spec


# ------------------------------------------------------------- shardings


def _is_logical(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def param_shardings(cfg, ctx: ShardCtx, logical):
    from jax.sharding import NamedSharding

    def one(log, leaf):
        return NamedSharding(ctx.mesh, logical_to_spec(log, ctx.rules, ctx.mesh, leaf.shape))

    return (lambda value_struct: jax.tree.map(one, logical, value_struct, is_leaf=_is_logical))


def batch_shardings(cfg, ctx: ShardCtx, batch_struct):
    from jax.sharding import NamedSharding, PartitionSpec as P

    def one(leaf):
        spec = [None] * leaf.ndim
        axes = [a for a in ctx.rules.get("batch") if a in ctx.mesh.shape]
        if leaf.shape[0] % max(1, _prod(ctx.mesh.shape[a] for a in axes)) == 0 and axes:
            spec[0] = tuple(axes) if len(axes) > 1 else axes[0]
        return NamedSharding(ctx.mesh, P(*spec))

    return jax.tree.map(one, batch_struct)


def _prod(it):
    out = 1
    for x in it:
        out *= x
    return out


def state_shardings(gcfg: G.GuidedConfig, opt: Optimizer, p_shardings, mesh,
                    extra_shardings=()):
    """GuidedState sharding tree mirroring guided_init's structure.
    `extra_shardings` must mirror the active strategy's init() output
    (the built-in strategies keep it empty; replicate scalars with P())."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(mesh, P())
    opt_map = {
        "sgd": (),
        "momentum": {"m": p_shardings},
        "rmsprop": {"r": p_shardings},
        "adagrad": {"r": p_shardings},
        "adam": {"m": p_shardings, "v": p_shardings, "t": repl},
    }
    return G.GuidedState(
        step=repl,
        score=repl,
        prev_worker_loss=repl,
        prev_avg_loss=repl,
        w_stale=p_shardings if gcfg.needs_stale else (),
        opt_state=opt_map[opt.name],
        extra=extra_shardings,
    )


def cache_shardings(cfg, ctx: ShardCtx, cache_struct):
    from jax.sharding import NamedSharding

    logical = T.cache_logical(cfg)
    # broadcast logical over the stacked (n_super,) leading dim already included
    def one(log, leaf):
        return NamedSharding(ctx.mesh, logical_to_spec(log, ctx.rules, ctx.mesh, leaf.shape))

    return jax.tree.map(one, logical, cache_struct,
                        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x))


# --------------------------------------------------------------- serve steps


def build_prefill_step(cfg, ctx: ShardCtx):
    """Batched prompt prefill; pass total_len/prompt_lens through T.prefill
    directly when serving variable-length prompts (repro.serve does)."""
    def prefill_step(params, batch):
        return T.prefill(params, batch, cfg, ctx)

    return prefill_step


def build_decode_step(cfg, ctx: ShardCtx):
    """One decode step; `t` is a scalar shared position or a (B,) per-request
    position vector (continuous batching — see repro.serve, DESIGN.md §7)."""
    def decode_step(params, caches, tokens, t):
        return T.decode_step(params, caches, tokens, t, cfg, ctx)

    return decode_step
