"""Core NN layers: RMSNorm, RoPE, GQA attention (full / sliding-window / decode),
FFN (SwiGLU / GELU), embedding and logits head.

All layer `apply` functions are pure; params are pytrees of jnp arrays (already
unboxed). Attention dispatches between the XLA einsum implementation (used for
dry-run lowering and CPU tests) and the Pallas kernels in repro.kernels; on a
TPU, causal self-attention at the shapes `splash_blocks` admits runs as JAX's
Pallas splash kernels, forward and backward.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.module import Param, dense_param


# ------------------------------------------------------------------- norms


def rmsnorm_init(d: int, dtype=jnp.float32) -> Param:
    return Param(jnp.ones((d,), dtype), (None,))


def rmsnorm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * scale.astype(dt)


# -------------------------------------------------------------------- rope


def rope_freqs(d_head: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float32) / d_head))


def apply_rope(x, positions, theta: float, scale: float = 1.0):
    """x: (..., S, H, d_head); positions: (..., S) int32. `scale` multiplies
    the rotation in float32, before the one cast back to x's dtype."""
    d_head = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d_head, theta))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = scale * cos, scale * sin
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention


NEG_INF = -1e30


def _sdpa(q, k, v, mask, scale):
    """q: (B,Sq,K,G,dh), k/v: (B,Skv,K,dh), mask: broadcastable (B,1,1,Sq,Skv)."""
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def _full_attention_xla(q, k, v, *, causal: bool, q_offset, scale):
    B, Sq, K, G, dh = q.shape
    Skv = k.shape[1]
    if causal:
        qi = q_offset + jnp.arange(Sq)
        kj = jnp.arange(Skv)
        mask = (qi[:, None] >= kj[None, :])[None, None, None]
    else:
        mask = jnp.ones((1, 1, 1, Sq, Skv), bool)
    return _sdpa(q, k, v, mask, scale)


#: the splash kernels' query and key block (forward, dq and dkv), largest
#: first: a sequence takes the first that divides it. 512 from a sweep at
#: 8 x 1024, d_head 128 on a TPU v5e (`benchmarks/splash_blocks.py`)
SPLASH_BLOCKS = (512, 256, 128)
#: head sizes at which the splash kernels compile for a v5e and beat the XLA
#: path on it (yi-9b's 128, minicpm-2b's 64)
SPLASH_HEAD_DIMS = (64, 128)


def _backend() -> str:
    return jax.default_backend()


def splash_blocks(Sq: int, Skv: int, dh: int, *, causal: bool, window: int = 0,
                  q_offset=0, distributed: bool = False):
    """The splash kernels' block size for this attention, or None where the
    XLA path serves it: off the TPU, non-causal, cross or offset attention, a
    window shorter than the sequence, a sequence no block divides, a head
    size the compiler refuses, and under a distributed mesh (a Pallas call
    cannot be auto-partitioned)."""
    if _backend() != "tpu" or not causal or distributed:
        return None
    if Sq != Skv or not isinstance(q_offset, int) or q_offset != 0:
        return None
    if (window and window < Sq) or dh not in SPLASH_HEAD_DIMS:
        return None
    return next((b for b in SPLASH_BLOCKS if Sq % b == 0), None)


@functools.lru_cache(maxsize=None)
def _splash_kernel(S: int, G: int, block: int, interpret: bool):
    """Causal splash attention for one KV head and its G query heads, built
    once per shape. Its mask tables are made eagerly, so a kernel first built
    inside a trace holds no tracer."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    mask = sm.MultiHeadMask([sm.CausalMask((S, S))] * G)
    blocks = sk.BlockSizes(block_q=block, block_kv=block, block_kv_compute=block,
                           block_q_dkv=block, block_kv_dkv=block,
                           block_kv_dkv_compute=block, block_q_dq=block,
                           block_kv_dq=block)
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(mask, block_sizes=blocks,
                                                interpret=interpret)


def _splash_attention(q, k, v, *, block: int, interpret: bool = False):
    """Exact causal GQA by the Pallas splash kernels: online softmax block by
    block, forward and backward, blocks above the diagonal skipped; the
    scores never reach HBM. q: (B, S, K, G, dh), already scaled by
    1/sqrt(dh); k, v: (B, S, K, dh). One MQA call per batch row and KV head."""
    B, S, K, G, dh = q.shape
    kernel = _splash_kernel(S, G, block, interpret)
    qt = jnp.transpose(q, (0, 2, 3, 1, 4))  # (B, K, G, S, dh)
    kt = jnp.transpose(k, (0, 2, 1, 3))  # (B, K, S, dh)
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = jax.vmap(jax.vmap(kernel))(qt, kt, vt)
    return jnp.transpose(out, (0, 3, 1, 2, 4))  # (B, S, K, G, dh)


def _swa_blocked_xla(q, k, v, *, window: int, scale):
    """Exact sliding-window causal attention, computed block-locally so the
    lowered FLOPs reflect the banded structure (each query block of size W
    attends only to itself + the previous block), not the dense S^2 einsum."""
    B, S, K, G, dh = q.shape
    W = window
    assert S % W == 0, (S, W)
    nb = S // W
    qb = q.reshape(B, nb, W, K, G, dh)
    kb = k.reshape(B, nb, W, K, dh)
    vb = v.reshape(B, nb, W, K, dh)
    zpad = jnp.zeros_like(kb[:, :1])
    k_prev = jnp.concatenate([zpad, kb[:, :-1]], axis=1)
    v_prev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([k_prev, kb], axis=2)  # (B, nb, 2W, K, dh)
    v2 = jnp.concatenate([v_prev, vb], axis=2)

    i = jnp.arange(W)
    j = jnp.arange(2 * W)
    # key j in [0,W) is previous block: valid iff j-W... local prev index jp=j:
    # global delta = W + i - j (prev)  -> valid iff 0 < W+i-j <= ... j > i
    # current block j' = j-W: valid iff j-W <= i (causal) and i-(j-W) < W (always)
    mask = jnp.where(j[None, :] < W, j[None, :] > i[:, None], (j[None, :] - W) <= i[:, None])
    first_block_mask = jnp.where(j[None, :] < W, False, (j[None, :] - W) <= i[:, None])
    full_mask = jnp.broadcast_to(mask, (nb, W, 2 * W)).at[0].set(first_block_mask)
    full_mask = full_mask[None, :, None, None, :, :]  # (1, nb, 1, 1, W, 2W)

    logits = jnp.einsum("bnqkgd,bnskd->bnkgqs", qb, k2, preferred_element_type=jnp.float32) * scale
    logits = jnp.where(full_mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bnkgqs,bnskd->bnqkgd", probs, v2)
    return out.reshape(B, S, K, G, dh)


def _chunked_attention_xla(qg, k, v, *, causal: bool, scale, chunk: int = 1024):
    """Flash-style online-softmax attention as a lax.scan over KV chunks.

    Never materializes the (Sq, Skv) score matrix in HBM — the per-chunk
    working set is O(Sq * chunk). This is the pure-XLA analog of the Pallas
    flash kernel, used for long-sequence prefill where the dense einsum's
    S^2 f32 buffer dominates the memory roofline term (§Perf pair 3)."""
    B, Sq, K, G, dh = qg.shape
    Skv = k.shape[1]
    chunk = min(chunk, Skv)
    assert Skv % chunk == 0
    nk = Skv // chunk
    qf = qg.astype(jnp.float32)

    kb = jnp.moveaxis(k.reshape(B, nk, chunk, K, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, chunk, K, dh), 1, 0)

    def body(carry, blk):
        m, l, acc = carry
        kc, vc, j = blk
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kc.astype(jnp.float32)) * scale
        if causal:
            rows = jnp.arange(Sq)[:, None]
            cols = j * chunk + jnp.arange(chunk)[None, :]
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(rows[None, None, None] >= cols[None, None, None], p, 0.0)
        alpha = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_new), 0.0)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum("bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(qg.dtype)  # (B,Sq,K,G,dh)


def attention(
    q,
    k,
    v,
    *,
    n_kv_heads: int,
    causal: bool = True,
    window: int = 0,
    q_offset=0,
    impl: str = "xla",
    distributed: bool = False,
    prescaled: bool = False,
):
    """Grouped-query attention.

    q: (B, Sq, H, dh); k, v: (B, Skv, K, dh). Returns (B, Sq, H, dh).
    window > 0 selects exact sliding-window causal attention. With
    impl="xla", the shapes `splash_blocks` admits run as the splash kernels;
    `prescaled` says q already carries 1/sqrt(dh) (`attn_apply` folds it into
    RoPE there), and is allowed on that path only.
    """
    B, Sq, H, dh = q.shape
    K = n_kv_heads
    G = H // K
    scale = 1.0 / np.sqrt(dh)
    qg = q.reshape(B, Sq, K, G, dh)

    block = None
    if impl == "xla":
        block = splash_blocks(Sq, k.shape[1], dh, causal=causal, window=window,
                              q_offset=q_offset, distributed=distributed)
    if prescaled and block is None:
        raise ValueError("a prescaled q is for the splash kernels only")
    if block is not None:
        if not prescaled:
            qg = (qg.astype(jnp.float32) * scale).astype(q.dtype)
        return _splash_attention(qg, k, v, block=block).reshape(B, Sq, H, dh)

    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        out = fa_ops.flash_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
        return out

    if impl == "xla_chunked" and not window and Sq == k.shape[1]:
        out = _chunked_attention_xla(qg, k, v, causal=causal, scale=scale)
    elif window and causal and Sq == k.shape[1] and Sq > 2 * window and Sq % window == 0:
        out = _swa_blocked_xla(qg, k, v, window=window, scale=scale)
    else:
        if window and causal and Sq == k.shape[1]:
            # small seq relative to window: fall back to masked full attention
            qi = jnp.arange(Sq)
            kj = jnp.arange(Sq)
            m = (qi[:, None] >= kj[None, :]) & (qi[:, None] - kj[None, :] < window)
            out = _sdpa(qg, k, v, m[None, None, None], scale)
        else:
            out = _full_attention_xla(qg, k, v, causal=causal, q_offset=q_offset, scale=scale)
    return out.reshape(B, Sq, H, dh)


def decode_attention(q, k_cache, v_cache, cache_len, *, n_kv_heads: int, impl: str = "xla"):
    """One-token attention against a (possibly ring-buffer) KV cache.

    q: (B, 1, H, dh); caches: (B, S_c, K, dh); cache_len: (B,) number of valid
    entries. Ring-buffer semantics: positions are valid iff slot < min(len, S_c);
    RoPE is applied by the caller (cache stores post-RoPE keys).
    """
    B, _, H, dh = q.shape
    K = n_kv_heads
    G = H // K
    scale = 1.0 / np.sqrt(dh)

    if impl == "pallas":
        from repro.kernels.flash_decode import ops as fd_ops

        return fd_ops.flash_decode(q, k_cache, v_cache, cache_len)

    S_c = k_cache.shape[1]
    qg = q.reshape(B, K, G, dh)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S_c)[None] < jnp.minimum(cache_len, S_c)[:, None]  # (B, S_c)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache)
    return out.reshape(B, 1, H, dh)


# ---------------------------------------------------------------- attention block


def attn_init(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    d, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": dense_param(ks[0], d, H * dh, ("fsdp", "tp"), dt),
        "wk": dense_param(ks[1], d, K * dh, ("fsdp", "tp"), dt),
        "wv": dense_param(ks[2], d, K * dh, ("fsdp", "tp"), dt),
        "wo": dense_param(ks[3], H * dh, d, ("tp", "fsdp"), dt),
    }


def attn_apply(p, x, cfg, *, positions, k_cache=None, v_cache=None, cache_len=None,
               distributed: bool = False):
    """Returns (out, (new_k, new_v)) — new_k/new_v are this call's K/V entries
    (pre-cache-write, post-RoPE), used by the caller to update caches.
    `distributed`: the call runs under a distributed `ShardCtx`."""
    B, S, d = x.shape
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = (x @ p["wq"]).reshape(B, S, H, dh)
    k = (x @ p["wk"]).reshape(B, S, K, dh)
    v = (x @ p["wv"]).reshape(B, S, K, dh)
    window = cfg.sliding_window if cfg.causal else 0
    # the splash kernels take no softmax scale: q carries it from its RoPE
    splash = (k_cache is None and cfg.attn_impl == "xla"
              and splash_blocks(S, S, dh, causal=cfg.causal, window=window,
                                distributed=distributed) is not None)
    q = apply_rope(q, positions, cfg.rope_theta, scale=1.0 / np.sqrt(dh) if splash else 1.0)
    k = apply_rope(k, positions, cfg.rope_theta)

    if k_cache is not None:
        out = decode_attention(q, k_cache, v_cache, cache_len, n_kv_heads=K, impl=cfg.attn_impl)
    else:
        out = attention(
            q, k, v,
            n_kv_heads=K,
            causal=cfg.causal,
            window=window,
            impl=cfg.attn_impl,
            distributed=distributed,
            prescaled=splash,
        )
    return out.reshape(B, S, H * dh) @ p["wo"], (k, v)


# ----------------------------------------------------------------------- ffn


def ffn_init(key, cfg, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    if not cfg.mlp_gated:  # non-gated GELU MLP (GPT/wav2vec2 family)
        return {
            "wi": dense_param(k1, d, f, ("fsdp", "tp"), dt),
            "wo": dense_param(k2, f, d, ("tp", "fsdp"), dt),
        }
    return {
        "wi": dense_param(k1, d, (2, f), ("fsdp", None, "tp"), dt),
        "wo": dense_param(k2, f, d, ("tp", "fsdp"), dt),
    }


def ffn_apply(p, x):
    if p["wi"].ndim == 2:  # GELU MLP
        return jax.nn.gelu(x @ p["wi"]) @ p["wo"]
    h = jnp.einsum("bsd,dtf->bstf", x, p["wi"])
    gate, up = h[..., 0, :], h[..., 1, :]
    return (jax.nn.silu(gate) * up) @ p["wo"]


# ----------------------------------------------------------- embedding / head


def embed_init(key, cfg) -> dict:
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    V, d = cfg.vocab_size, cfg.d_model
    out = {}
    if cfg.tie_embeddings:
        out["table"] = Param((0.02 * jax.random.normal(k1, (V, d))).astype(dt), ("vocab", None))
    else:
        out["table"] = Param((0.02 * jax.random.normal(k1, (V, d))).astype(dt), (None, "tp"))
        out["head"] = dense_param(k2, d, V, ("fsdp", "vocab"), dt)
    return out


def embed_lookup(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def logits_head(p, x):
    table = p["table"]
    if "head" in p:
        return x @ p["head"]
    return x @ table.T


def cross_entropy(logits, labels, mask=None):
    """Mean CE over valid positions; logits (B,S,V) possibly vocab-sharded."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - true
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def per_example_cross_entropy(logits, labels, mask=None):
    """(B,) mean CE per example — feeds the guided consistency statistics."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - true
    if mask is None:
        return jnp.mean(nll, axis=-1)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask, axis=-1) / jnp.maximum(jnp.sum(mask, axis=-1), 1.0)
