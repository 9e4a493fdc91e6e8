"""Mixture-of-Experts layer: top-k token-choice routing with gather-based
fixed-capacity dispatch.

Why gather-based: the classic GShard one-hot dispatch einsum costs
O(N * E * C * d) FLOPs — for qwen3 (128 experts) that is orders of magnitude
more than the expert GEMMs themselves and would poison the roofline numbers.
jax.lax.ragged_dot lowers to dense-per-expert on CPU (E x overcount). The
sort + index-gather dispatch below costs exactly the active-expert FLOPs
(3 * 2 * E * C * d * d_ff for a SwiGLU expert) plus cheap integer work, on any
backend.

Dispatch runs per data shard (wrapped in shard_map by the caller — routing is
local to each worker's tokens, as in Switch/GShard; expert weights stay sharded
over `model` as auto axes inside the region).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.module import Param, dense_param


def moe_init(key, cfg) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, cfg.d_ff, m.n_experts
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    # expert dim shards over `data` when divisible (qwen3: 128, jamba: 16);
    # otherwise rules fall back to replicating it and FSDP-sharding d_model.
    p = {
        "router": Param((0.02 * jax.random.normal(ks[0], (d, E))).astype(jnp.float32), (None, None)),
        "wi": Param(
            (jax.random.normal(ks[1], (E, d, 2, f)) / np.sqrt(d)).astype(dt),
            ("expert", "fsdp", None, "tp"),
        ),
        "wo": Param(
            (jax.random.normal(ks[2], (E, f, d)) / np.sqrt(f)).astype(dt),
            ("expert", "tp", "fsdp"),
        ),
    }
    if m.d_shared_ff:
        p["shared_wi"] = dense_param(ks[3], d, (2, m.d_shared_ff), ("fsdp", None, "tp"), dt)
        p["shared_wo"] = dense_param(ks[3], m.d_shared_ff, d, ("tp", "fsdp"), dt)
    return p


def capacity(n_tokens: int, n_experts: int, topk: int, factor: float) -> int:
    c = int(np.ceil(n_tokens * topk * factor / n_experts))
    return max(4, min(c, n_tokens))


def _topk_by_argmax(probs, k: int):
    """top-k as k masked argmaxes (same values/order/tie-breaks as lax.top_k
    for small k). lax.TopK crashes the partial-manual SPMD partitioner of the
    pinned jax/XLA inside shard_map regions ("Check failed: IsManualSubgroup"),
    while argmax lowers to plain reduces that partition fine."""
    E = probs.shape[-1]
    masked = probs
    vals, idxs = [], []
    for _ in range(k):
        i = jnp.argmax(masked, axis=-1)
        onehot = jax.nn.one_hot(i, E, dtype=jnp.bool_)
        vals.append(jnp.sum(jnp.where(onehot, probs, 0.0), axis=-1))
        idxs.append(i.astype(jnp.int32))
        masked = jnp.where(onehot, -jnp.inf, masked)
    return jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1)


def route(gates_logits, topk: int):
    """Returns (weights (N,k), expert_ids (N,k), probs (N,E))."""
    probs = jax.nn.softmax(gates_logits.astype(jnp.float32), axis=-1)
    w, eid = _topk_by_argmax(probs, topk)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, eid, probs


def moe_apply_local(p, x, cfg, capacity_factor=None, a2a_axes=None, n_shards=1):
    """x: (N, d) — tokens local to this data shard. Returns (y (N,d), aux loss).

    a2a_axes: when set (a tuple of manual mesh axis names), expert weights are
    expert-sharded across those axes and dispatch uses two all-to-alls (GShard
    expert parallelism) instead of gathering every expert's weights to every
    shard. This removes the dominant collective of MoE training at scale
    (EXPERIMENTS.md §Perf: qwen3 train_4k 99.8s -> sub-second collective term).
    """
    m = cfg.moe
    E, k = m.n_experts, m.topk
    N, d = x.shape
    C = capacity(N, E, k, capacity_factor or m.capacity_factor)

    gate_logits = x.astype(jnp.float32) @ p["router"]
    w, eid, probs = route(gate_logits, k)

    # ---- sort-based dispatch: slot (e, rank) for every (token, expert) pair
    flat_eid = eid.reshape(-1)                      # (N*k,)
    flat_tok = jnp.repeat(jnp.arange(N), k)
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_eid, stable=True)      # stable: earlier tokens win capacity
    s_eid, s_tok, s_w = flat_eid[order], flat_tok[order], flat_w[order]
    counts = jnp.bincount(flat_eid, length=E)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(N * k) - starts[s_eid]
    keep = rank < C
    slot = jnp.where(keep, s_eid * C + rank, E * C)  # overflow -> sentinel slot

    buf_tok = jnp.full((E * C + 1,), N, jnp.int32).at[slot].set(s_tok.astype(jnp.int32))[:-1]
    buf_w = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(s_w)[:-1]

    # ---- expert compute on gathered buffers
    # NOTE: on non-TPU backends the expert dots run in f32 — XLA CPU hard-
    # crashes ("Invalid binary instruction opcode copy") when differentiating
    # a bf16 dot through a manual-axes shard_map with auto-sharded operands.
    # On TPU the bf16 MXU path is used as intended.
    ed = jnp.float32 if jax.default_backend() != "tpu" else x.dtype
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = x_pad[buf_tok].reshape(E, C, d)

    if a2a_axes:
        # GShard expert parallelism, fully-manual region (data AND model axes
        # manual — mixing a manual-axes all-to-all with an auto tensor axis
        # makes the SPMD partitioner materialize the a2a cotangent at full
        # data extent; hand-placing the Megatron psum avoids it, §Perf):
        #   a2a tokens -> local experts; wi/wo enter f-sharded over `model`;
        #   down-proj contracts the f shard -> psum over `model`.
        model_axis, n_model = a2a_axes[-1], None
        data_axes = a2a_axes[:-1]
        xe = jax.lax.all_to_all(xe, data_axes, split_axis=0, concat_axis=1, tiled=True)
        # xe: (E/n, C*n, d); p["wi"]: (E/n, d, 2, f/n_model) local shard
        h = jnp.einsum("ecd,edtf->ectf", xe.astype(ed), p["wi"].astype(ed),
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(h[..., 0, :]) * h[..., 1, :]).astype(ed)
        ye = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(ed)).astype(x.dtype)
        ye = jax.lax.all_to_all(ye, data_axes, split_axis=1, concat_axis=0, tiled=True)
        # back to (E, C, d) with this shard's own tokens. ye is still PARTIAL
        # over `model` (f-shard contributions); the psum happens after the
        # token combine, on the k*cf-times-smaller (N, d) buffer (§Perf it.3).
    else:
        h = jnp.einsum("ecd,edtf->ectf", xe.astype(ed), p["wi"].astype(ed),
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(h[..., 0, :]) * h[..., 1, :]).astype(ed)
        ye = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(ed)).astype(x.dtype)

    # ---- combine (weighted scatter-add back to token order)
    contrib = ye.reshape(E * C, d) * buf_w[:, None].astype(ye.dtype)
    y = jnp.zeros((N + 1, d), ye.dtype).at[buf_tok].add(contrib)[:-1]
    if a2a_axes:
        y = jax.lax.psum(y, a2a_axes[-1])  # model-axis reduction post-combine

    if "shared_wi" in p:
        hs = jnp.einsum("nd,dtf->ntf", x, p["shared_wi"])
        y = y + (jax.nn.silu(hs[..., 0, :]) * hs[..., 1, :]) @ p["shared_wo"]

    # ---- Switch-style load-balance auxiliary loss
    frac_tokens = jnp.mean((jax.nn.one_hot(eid, E)).sum(1), axis=0)  # (E,) ~ k*f_e
    mean_prob = jnp.mean(probs, axis=0)
    aux = m.router_aux_weight * E * jnp.sum(frac_tokens / k * mean_prob)
    return y.astype(x.dtype), aux


def moe_apply(p, x, cfg, ctx, capacity_factor=None):
    """x: (B, S, d). shard_map over the data axes when distributed.

    Two distributed dispatch strategies (ShardCtx.moe_impl):
      "gather"   — expert weights enter the region replicated over the data
                   axes (XLA all-gathers them per use). Baseline.
      "alltoall" — expert weights stay expert-sharded over the data axes;
                   token buffers are exchanged with two all-to-alls (GShard
                   expert parallelism). Requires n_experts % n_shards == 0;
                   falls back to gather otherwise (grok: 8 experts, 16 shards).
    """
    B, S, d = x.shape

    def local(p_, x_, a2a_axes=None):
        y, aux = moe_apply_local(p_, x_.reshape(-1, d), cfg, capacity_factor, a2a_axes)
        return y.reshape(x_.shape), aux

    if not ctx.distributed:
        return local(p, x)

    from jax.sharding import PartitionSpec as P

    manual = tuple(a for a in ctx.data_axes if a in ctx.mesh.shape)
    n_shards = 1
    for a in manual:
        n_shards *= ctx.mesh.shape[a]
    if not manual or B % n_shards != 0:
        # batch not shardable over the data axes (e.g. long_500k's B=1 decode):
        # run the routing replicated; expert weights stay model-sharded (auto)
        return local(p, x)
    batch_axes = manual if len(manual) > 1 else manual[0]
    batch_spec = P(batch_axes)

    E = cfg.moe.n_experts
    model_ok = (
        ctx.model_axis in ctx.mesh.shape
        and cfg.d_ff % ctx.mesh.shape[ctx.model_axis] == 0
    )
    use_a2a = (
        getattr(ctx, "moe_impl", "gather") == "alltoall"
        and E % n_shards == 0
        and model_ok
    )
    # a2a region is manual over data axes AND the model axis (see apply_local)
    a2a_axes = manual + (ctx.model_axis,) if use_a2a else None
    region_axes = manual + ((ctx.model_axis,) if use_a2a else ())

    def local_psum(p_, x_):
        y, aux = local(p_, x_, a2a_axes)
        aux = jax.lax.psum(aux, manual) / n_shards
        return y, aux

    p_specs = jax.tree.map(lambda _: P(), p)
    if use_a2a:
        # expert dim manual-sharded over data; f dim manual-sharded over model
        p_specs = dict(p_specs)
        p_specs["wi"] = P(batch_axes, None, None, ctx.model_axis)
        p_specs["wo"] = P(batch_axes, ctx.model_axis, None)

    fn = jax.shard_map(
        local_psum,
        mesh=ctx.mesh,
        in_specs=(p_specs, P(*batch_spec, None, None)),
        out_specs=(P(*batch_spec, None, None), P()),
        axis_names=set(region_axes),
    )
    return fn(p, x)
