"""Unified model assembly for every assigned architecture family.

A model is a stack of `n_layers` blocks executed as a lax.scan over
*super-blocks*: the smallest repeating period of heterogeneous layers
(dense/moe: 1; xlstm: len(pattern)=2; jamba: attn_every=8). Scanning keeps the
HLO size O(period) instead of O(n_layers) — essential for 94-layer models on a
single-core compile host, and the production-standard layout for TPU.

API (all pure functions):
  model_init(key, cfg)                       -> boxed param tree
  forward_train(params, batch, cfg, ctx)     -> (per_example_loss, aux, logits)
  prefill(params, batch, cfg, ctx)           -> (last_logits, caches)
  decode_step(params, caches, tokens, t, cfg, ctx) -> (logits, caches)
  init_caches(cfg, batch, cache_len, ctx)    -> cache pytree (ShapeDtype-friendly)
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import xlstm as X
from repro.models.module import Param, stacked, value_tree
from repro.sharding.rules import ShardCtx, LOCAL_CTX


# ----------------------------------------------------------- block structure


def period(cfg) -> int:
    if cfg.arch_type == "hybrid":
        return cfg.attn_every
    if cfg.xlstm is not None:
        return len(cfg.xlstm.pattern)
    return 1


def n_super(cfg) -> int:
    p = period(cfg)
    assert cfg.n_layers % p == 0, (cfg.name, cfg.n_layers, p)
    return cfg.n_layers // p


def mixer_kind(cfg, i: int) -> str:
    """Kind of the i-th layer within a super-block."""
    if cfg.xlstm is not None:
        return "mlstm" if cfg.xlstm.pattern[i % len(cfg.xlstm.pattern)] else "slstm"
    if cfg.arch_type == "hybrid":
        return "attn" if cfg.layer_is_attn(i) else "mamba"
    return "attn"


def ffn_kind(cfg, i: int) -> Optional[str]:
    if cfg.xlstm is not None:
        return None  # xLSTM blocks carry their own projections
    if cfg.layer_is_moe(i):
        return "moe"
    return "dense"


def block_init(key, cfg) -> dict:
    """One super-block: dict l0..l{P-1}, each {norm1, mixer, [norm2, ffn]}."""
    P = period(cfg)
    keys = jax.random.split(key, P)
    out = {}
    for i in range(P):
        ki = jax.random.split(keys[i], 3)
        lp: dict = {}
        mk = mixer_kind(cfg, i)
        if mk == "attn":
            lp["norm1"] = L.rmsnorm_init(cfg.d_model)
            lp["mixer"] = L.attn_init(ki[0], cfg)
        elif mk == "mamba":
            lp["norm1"] = L.rmsnorm_init(cfg.d_model)
            lp["mixer"] = M.mamba_init(ki[0], cfg)
        elif mk == "mlstm":
            lp["mixer"] = X.mlstm_init(ki[0], cfg)
        elif mk == "slstm":
            lp["mixer"] = X.slstm_init(ki[0], cfg)
        fk = ffn_kind(cfg, i)
        if fk == "dense":
            lp["norm2"] = L.rmsnorm_init(cfg.d_model)
            lp["ffn"] = L.ffn_init(ki[1], cfg)
        elif fk == "moe":
            lp["norm2"] = L.rmsnorm_init(cfg.d_model)
            lp["ffn"] = MOE.moe_init(ki[1], cfg)
        out[f"l{i}"] = lp
    return out


def model_init(key, cfg):
    k_embed, k_blocks = jax.random.split(key)
    params: dict = {"final_norm": L.rmsnorm_init(cfg.d_model)}
    if cfg.audio_frontend:
        dt = jnp.dtype(cfg.param_dtype)
        params["mask_emb"] = Param((0.02 * jax.random.normal(k_embed, (cfg.d_model,))).astype(dt), (None,))
        k2 = jax.random.fold_in(k_embed, 1)
        params["head"] = Param(
            (jax.random.normal(k2, (cfg.d_model, cfg.vocab_size)) / np.sqrt(cfg.d_model)).astype(dt),
            ("fsdp", "vocab"),
        )
    else:
        params["embed"] = L.embed_init(k_embed, cfg)
    params["blocks"] = stacked(n_super(cfg), lambda k: block_init(k, cfg), k_blocks)
    return params


# ------------------------------------------------------------------- caches


def cache_len_for(cfg, total_len: int) -> int:
    if cfg.sliding_window:
        return min(cfg.sliding_window, total_len)
    return total_len


def layer_cache_init(cfg, i: int, batch: int, s_c: int):
    mk = mixer_kind(cfg, i)
    dt = cfg.dtype
    if mk == "attn":
        K, dh = cfg.n_kv_heads, cfg.d_head
        if cfg.kv_cache_dtype == "int8":
            return {
                "k": jnp.zeros((batch, s_c, K, dh), jnp.int8),
                "v": jnp.zeros((batch, s_c, K, dh), jnp.int8),
                "k_scale": jnp.zeros((batch, s_c, K), jnp.float32),
                "v_scale": jnp.zeros((batch, s_c, K), jnp.float32),
            }
        return {
            "k": jnp.zeros((batch, s_c, K, dh), dt),
            "v": jnp.zeros((batch, s_c, K, dh), dt),
        }
    if mk == "mamba":
        conv, ssm = M.mamba_state_init(cfg, batch, dt)
        return {"conv": conv, "ssm": ssm}
    if mk == "mlstm":
        conv, (C, n, m) = X.mlstm_state_init(cfg, batch, dt)
        return {"conv": conv, "C": C, "n": n, "m": m}
    if mk == "slstm":
        conv, (h, c, n, m) = X.slstm_state_init(cfg, batch, dt)
        return {"conv": conv, "h": h, "c": c, "n": n, "m": m}
    raise ValueError(mk)


def init_caches(cfg, batch: int, total_len: int):
    s_c = cache_len_for(cfg, total_len)
    P = period(cfg)
    one = {f"l{i}": layer_cache_init(cfg, i, batch, s_c) for i in range(P)}
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_super(cfg),) + x.shape), one)


def cache_logical(cfg):
    """Logical sharding annotations mirroring init_caches output."""
    P = period(cfg)
    one = {}
    for i in range(P):
        mk = mixer_kind(cfg, i)
        if mk == "attn":
            one[f"l{i}"] = {"k": (None, "batch", "seq_kv", None, None), "v": (None, "batch", "seq_kv", None, None)}
            if cfg.kv_cache_dtype == "int8":
                one[f"l{i}"]["k_scale"] = (None, "batch", "seq_kv", None)
                one[f"l{i}"]["v_scale"] = (None, "batch", "seq_kv", None)
        elif mk == "mamba":
            one[f"l{i}"] = {"conv": (None, "batch", None, "tp"), "ssm": (None, "batch", "tp", None)}
        elif mk == "mlstm":
            one[f"l{i}"] = {
                "conv": (None, "batch", None, "tp"),
                "C": (None, "batch", None, None, None),
                "n": (None, "batch", None, None),
                "m": (None, "batch", None),
            }
        else:
            one[f"l{i}"] = {
                "conv": (None, "batch", None, "tp"),
                "h": (None, "batch", None, None),
                "c": (None, "batch", None, None),
                "n": (None, "batch", None, None),
                "m": (None, "batch", None, None),
            }
    return one


# --------------------------------------------------- distributed decode attn


def sharded_decode_attention(q, k_cache, v_cache, cache_len, cfg, ctx: ShardCtx):
    """Flash-decode with the KV-cache *sequence* dim sharded over `model`:
    each model shard attends to its local chunk; partials are combined with a
    max-stabilized (num, den) psum. q is replicated over `model` in-region."""
    K = cfg.n_kv_heads

    if not ctx.distributed or "seq_kv" not in ctx.rules.table or not ctx.rules.get("seq_kv"):
        return L.decode_attention(q, k_cache, v_cache, cache_len, n_kv_heads=K, impl=cfg.attn_impl)

    axis = ctx.model_axis
    s_c = k_cache.shape[1]
    if s_c % ctx.mesh.shape[axis] != 0:
        return L.decode_attention(q, k_cache, v_cache, cache_len, n_kv_heads=K, impl=cfg.attn_impl)

    def local(q_, kc, vc, clen, slots):
        # slots: (s_c / n_model,) — this shard's global cache positions. Passed
        # in as a sequence-sharded operand rather than derived from
        # lax.axis_index: PartitionId doesn't lower through partial-manual
        # SPMD on the pinned XLA.
        B, _, H, dh = q_.shape
        G = H // K
        scale = 1.0 / np.sqrt(dh)
        # f32 dots off-TPU: XLA CPU miscompiles bf16 dots inside manual-axes
        # shard_map regions (see models/moe.py note); bf16 MXU path on TPU.
        ed = jnp.float32 if jax.default_backend() != "tpu" else q_.dtype
        qg = q_.reshape(B, K, G, dh).astype(ed)
        logits = jnp.einsum("bkgd,bskd->bkgs", qg, kc.astype(ed),
                            preferred_element_type=jnp.float32) * scale
        valid = slots[None] < jnp.minimum(clen, s_c)[:, None]
        logits = jnp.where(valid[:, None, None, :], logits, L.NEG_INF)
        m_loc = jnp.max(logits, axis=-1)
        m = jax.lax.pmax(m_loc, axis)
        p = jnp.exp(logits - m[..., None])
        den = jax.lax.psum(jnp.sum(p, axis=-1), axis)
        num = jax.lax.psum(jnp.einsum("bkgs,bskd->bkgd", p.astype(ed), vc.astype(ed)), axis)
        out = num / jnp.maximum(den[..., None], 1e-30).astype(num.dtype)
        return out.reshape(B, 1, H, dh).astype(q_.dtype)

    from jax.sharding import PartitionSpec as P

    fn = jax.shard_map(
        local,
        mesh=ctx.mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None), P(), P(axis)),
        out_specs=P(),
        axis_names={axis},
    )
    return fn(q, k_cache, v_cache, cache_len, jnp.arange(s_c, dtype=jnp.int32))


# ------------------------------------------------------------- block apply


def _scaled(x, s):
    """x times a published scalar, the product taken in float32 and rounded
    once to x's dtype."""
    return (x.astype(jnp.float32) * s).astype(x.dtype)


def _residual(x, y, cfg):
    """x plus the branch y, times `cfg.residual_scale` where the model has
    one (xLSTM blocks add their own residual and take none)."""
    if cfg.residual_scale is not None:
        y = _scaled(y, cfg.residual_scale)
    return x + y


def layer_apply(lp, x, cfg, ctx, i, positions, cache=None, t=None):
    """Apply layer i of a super-block. Returns (x, aux, new_cache)."""
    mk = mixer_kind(cfg, i)
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache

    if mk == "attn":
        h = L.rmsnorm(x, lp["norm1"], cfg.norm_eps)
        if cache is not None and t is not None:
            # decode: one token per request against the cache. t is a (B,)
            # per-slot position vector (a scalar is broadcast by decode_step),
            # so requests at different depths share one jitted step.
            B, S, d = h.shape
            H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
            q = (h @ lp["mixer"]["wq"]).reshape(B, S, H, dh)
            k = (h @ lp["mixer"]["wk"]).reshape(B, S, K, dh)
            v = (h @ lp["mixer"]["wv"]).reshape(B, S, K, dh)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            s_c = cache["k"].shape[1]
            slot = jnp.mod(t, s_c)  # (B,) per-request ring-buffer slots
            rows = jnp.arange(B)
            if cfg.kv_cache_dtype == "int8":
                from repro.models.kvquant import dequantize_kv, quantize_kv

                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                k_cache = cache["k"].at[rows, slot].set(kq[:, 0])
                v_cache = cache["v"].at[rows, slot].set(vq[:, 0])
                ks_cache = cache["k_scale"].at[rows, slot].set(ks[:, 0])
                vs_cache = cache["v_scale"].at[rows, slot].set(vs[:, 0])
                k_full = dequantize_kv(k_cache, ks_cache, cfg.dtype)
                v_full = dequantize_kv(v_cache, vs_cache, cfg.dtype)
                # this step's attention reads the current token's exact k/v
                # (the int8 copy only pays its quantization cost from t+1 on)
                k_full = k_full.at[rows, slot].set(k[:, 0].astype(cfg.dtype))
                v_full = v_full.at[rows, slot].set(v[:, 0].astype(cfg.dtype))
                new_cache = {"k": k_cache, "v": v_cache, "k_scale": ks_cache, "v_scale": vs_cache}
            else:
                k_cache = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
                v_cache = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
                k_full, v_full = k_cache, v_cache
                new_cache = {"k": k_cache, "v": v_cache}
            clen = (t + 1).astype(jnp.int32)
            out = sharded_decode_attention(q, k_full, v_full, clen, cfg, ctx)
            att = out.reshape(B, S, H * dh) @ lp["mixer"]["wo"]
        else:
            att, (k, v) = L.attn_apply(lp["mixer"], h, cfg, positions=positions,
                                       distributed=ctx.distributed)
            if cache is not None:  # prefill: write the (window of the) sequence
                s_c = cache["k"].shape[1]
                S = k.shape[1]
                s_eff = min(S, s_c)  # window may truncate; cache may be larger
                kw, vw = k[:, -s_eff:], v[:, -s_eff:]
                slots = jnp.mod(jnp.arange(S - s_eff, S), s_c)
                if cfg.kv_cache_dtype == "int8":
                    from repro.models.kvquant import quantize_kv

                    kq, ks = quantize_kv(kw)
                    vq, vs = quantize_kv(vw)
                    new_cache = {
                        "k": jnp.zeros_like(cache["k"]).at[:, slots].set(kq),
                        "v": jnp.zeros_like(cache["v"]).at[:, slots].set(vq),
                        "k_scale": jnp.zeros_like(cache["k_scale"]).at[:, slots].set(ks),
                        "v_scale": jnp.zeros_like(cache["v_scale"]).at[:, slots].set(vs),
                    }
                else:
                    k_cache = jnp.zeros_like(cache["k"]).at[:, slots].set(kw.astype(cache["k"].dtype))
                    v_cache = jnp.zeros_like(cache["v"]).at[:, slots].set(vw.astype(cache["v"].dtype))
                    new_cache = {"k": k_cache, "v": v_cache}
        x = _residual(x, att, cfg)

    elif mk == "mamba":
        h = L.rmsnorm(x, lp["norm1"], cfg.norm_eps)
        conv = cache["conv"] if cache is not None else None
        ssm = cache["ssm"] if cache is not None else None
        y, (new_conv, new_ssm) = M.mamba_apply(lp["mixer"], h, cfg, conv, ssm, impl=cfg.attn_impl if cfg.attn_impl == "pallas" else "xla")
        x = _residual(x, y, cfg)
        if cache is not None:
            new_cache = {"conv": new_conv.astype(cache["conv"].dtype), "ssm": new_ssm}

    elif mk == "mlstm":
        st = (cache["conv"], (cache["C"], cache["n"], cache["m"])) if cache is not None else None
        x, (new_conv, (C, n, m)) = X.mlstm_apply(lp["mixer"], x, cfg, st)
        if cache is not None:
            new_cache = {"conv": new_conv.astype(cache["conv"].dtype), "C": C, "n": n, "m": m}

    elif mk == "slstm":
        st = (cache["conv"], (cache["h"], cache["c"], cache["n"], cache["m"])) if cache is not None else None
        x, (new_conv, (hh, c, n, m)) = X.slstm_apply(lp["mixer"], x, cfg, st)
        if cache is not None:
            new_cache = {"conv": new_conv.astype(cache["conv"].dtype), "h": hh, "c": c, "n": n, "m": m}

    fk = ffn_kind(cfg, i)
    if fk is not None:
        h = L.rmsnorm(x, lp["norm2"], cfg.norm_eps)
        if fk == "dense":
            x = _residual(x, L.ffn_apply(lp["ffn"], h), cfg)
        else:
            y, aux_moe = MOE.moe_apply(lp["ffn"], h, cfg, ctx)
            x = _residual(x, y, cfg)
            aux = aux + aux_moe
    return x, aux, new_cache


def block_apply(bp, x, cfg, ctx, positions, caches=None, t=None):
    """One super-block (period P layers)."""
    P = period(cfg)
    aux = jnp.zeros((), jnp.float32)
    new_caches = {} if caches is not None else None
    for i in range(P):
        cache_i = caches[f"l{i}"] if caches is not None else None
        x, aux_i, nc = layer_apply(bp[f"l{i}"], x, cfg, ctx, i, positions, cache_i, t)
        aux = aux + aux_i
        if caches is not None:
            new_caches[f"l{i}"] = nc
    return x, aux, new_caches


# ------------------------------------------------------------ full forward


def _embed_tokens(params, tokens, cfg):
    """Token embeddings in the compute dtype, times `cfg.scale_emb` where the
    model has one: the one lookup of training, prefill and decode."""
    x = L.embed_lookup(params["embed"], tokens).astype(cfg.dtype)
    if cfg.scale_emb is not None:
        x = _scaled(x, cfg.scale_emb)
    return x


def _embed_inputs(params, batch, cfg):
    if cfg.audio_frontend:
        x = batch["frames"].astype(cfg.dtype)
        mask = batch["mask_positions"]
        x = jnp.where(mask[..., None], params["mask_emb"].astype(cfg.dtype), x)
        return x
    x = _embed_tokens(params, batch["tokens"], cfg)
    if cfg.arch_type == "vlm" and "patches" in batch:
        P_ = batch["patches"].shape[1]
        x = jnp.concatenate([x[:, :1], batch["patches"].astype(cfg.dtype), x[:, 1 + P_ :]], axis=1)
    return x


@functools.lru_cache(maxsize=None)
def _block_logical(cfg):
    import jax as _jax
    from repro.models.module import logical_tree

    boxed = _jax.eval_shape(lambda: block_init(_jax.random.PRNGKey(0), cfg))
    return logical_tree(boxed)


def _constrain_block(bp, cfg, ctx):
    """Re-assert per-layer weight shardings inside the scan body. Without this
    the SPMD partitioner loses the sharding of the scanned slice's *gradient*
    accumulator and falls back to full-size all-reduces (184 GiB/device temp on
    yi-9b vs ~2 GiB with constraints — see EXPERIMENTS.md §Perf)."""
    if not ctx.distributed:
        return bp
    from repro.sharding.rules import logical_to_spec

    logical = _block_logical(cfg)
    # scanned slices have lost the leading layer dim: drop it from annotations
    def one(v, log):
        log = tuple(log)[-v.ndim:] if len(log) > v.ndim else log
        spec = logical_to_spec(log, ctx.rules, ctx.mesh, v.shape)
        return jax.lax.with_sharding_constraint(v, jax.sharding.NamedSharding(ctx.mesh, spec))

    return jax.tree.map(one, bp, logical)


def _stack_scan(params, x, cfg, ctx, positions, caches=None, t=None):
    blocks = params["blocks"]

    def body(carry, xs):
        xc, aux = carry
        if caches is not None:
            bp, cache = xs
        else:
            bp, cache = xs, None
        bp = _constrain_block(bp, cfg, ctx)
        if ctx.distributed:
            # "seq" resolves to () by default; under sequence-parallel rules it
            # shards the inter-block activations over `model`, turning the
            # Megatron all-reduces into all-gather+reduce-scatter pairs.
            xc = jax.lax.with_sharding_constraint(
                xc, jax.sharding.NamedSharding(ctx.mesh, ctx.spec("batch", "seq", None, shape=xc.shape))
            )
        xc, aux_i, nc = block_apply(bp, xc, cfg, ctx, positions, cache, t)
        return (xc, aux + aux_i), nc

    if cfg.remat == "full":
        body = jax.checkpoint(body)

    xs = (blocks, caches) if caches is not None else blocks
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, new_caches


def _head(params, x, cfg):
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.logit_scale is not None:
        x = _scaled(x, cfg.logit_scale)
    if cfg.audio_frontend:
        return x @ params["head"]
    return L.logits_head(params["embed"], x)


def forward_train(params, batch, cfg, ctx: ShardCtx = LOCAL_CTX):
    """Returns (per_example_loss (B,), aux, logits)."""
    x = _embed_inputs(params, batch, cfg)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x, aux, _ = _stack_scan(params, x, cfg, ctx, positions)
    with jax.named_scope("loss_head"):
        logits = _head(params, x, cfg)
        per_ex = L.per_example_cross_entropy(logits, batch["labels"], batch.get("mask"))
    return per_ex, aux, logits


def prefill(params, batch, cfg, ctx: ShardCtx = LOCAL_CTX, total_len: int = 0,
            prompt_lens=None):
    """Returns (last-position logits (B,V), caches). Caches are sized for
    `total_len` (>= prompt length) so decode can continue in place.

    `prompt_lens` ((B,) int32, optional) supports right-padded prompts: logits
    are gathered at each row's last *real* position (prompt_lens-1) instead of
    the last padded one. Padded KV slots hold junk, but causal masking keeps
    real-token activations exact and decode overwrites slot t exactly when it
    first becomes visible (clen = t+1) — see DESIGN.md §7 for the arch classes
    where this is sound (recurrent state integrates pad junk; windows wrap)."""
    x = _embed_inputs(params, batch, cfg)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    caches = init_caches(cfg, B, max(total_len, S))
    x, _, caches = _stack_scan(params, x, cfg, ctx, positions, caches=caches)
    if prompt_lens is None:
        x_last = x[:, -1:]
    else:
        idx = jnp.clip(jnp.asarray(prompt_lens, jnp.int32) - 1, 0, S - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    logits = _head(params, x_last, cfg)
    return logits[:, 0], caches


def decode_step(params, caches, tokens, t, cfg, ctx: ShardCtx = LOCAL_CTX):
    """tokens: (B,1) int32 (or (B,1,d) frames); t: scalar position shared by
    the batch, or a (B,) per-request position vector (continuous batching:
    every slot advances at its own depth). Returns (logits (B,V), new caches)."""
    if cfg.audio_frontend:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    x = _embed_tokens(params, tokens, cfg)
    B = x.shape[0]
    tv = jnp.asarray(t, jnp.int32)
    if tv.ndim == 0:
        tv = jnp.broadcast_to(tv, (B,))
    positions = tv[:, None]
    x, _, caches = _stack_scan(params, x, cfg, ctx, positions, caches=caches, t=tv)
    logits = _head(params, x, cfg)
    return logits[:, 0], caches
