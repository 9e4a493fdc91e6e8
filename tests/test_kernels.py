"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.ops import flash_decode
from repro.kernels.flash_decode.ref import decode_ref
from repro.kernels.guided_update.ops import guided_sgd_update, guided_rmsprop_update
from repro.kernels.guided_update.ref import guided_rmsprop_update_ref, guided_sgd_update_ref
from repro.kernels.selective_scan.ops import selective_scan
from repro.kernels.selective_scan.ref import selective_scan_ref
from repro.models import layers as L

RNG = np.random.default_rng(0)


def randn(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# ------------------------------------------------------------ flash attention


@pytest.mark.parametrize("B,S,H,K,dh", [(2, 256, 4, 2, 64), (1, 128, 8, 8, 32),
                                        (1, 256, 4, 1, 128), (2, 512, 2, 2, 64)])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 128)])
def test_flash_attention_matches_ref(B, S, H, K, dh, causal, window):
    q, k, v = randn(B, S, H, dh), randn(B, S, K, dh), randn(B, S, K, dh)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=128, bk=128)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q, k, v = (randn(1, 128, 2, 64, dtype=dtype) for _ in range(3))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == dtype
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=2e-2 if dtype == jnp.bfloat16 else 3e-5)


@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_shapes(bq, bk):
    q, k, v = randn(1, 256, 2, 32), randn(1, 256, 2, 32), randn(1, 256, 2, 32)
    out = flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ------------------------------------------------- splash attention (training)


@pytest.fixture(scope="module", params=L.SPLASH_HEAD_DIMS, ids=lambda d: f"dh{d}")
def splash_vs_xla(request):
    """Output and (dq, dk, dv) of causal GQA at B 2, S 256, 4 query and 2 KV
    heads in bf16, by the splash kernels (interpret mode, 128 blocks) and by
    the XLA path: {name: (splash, xla)} in float32."""
    dh = request.param
    B, S, H, K = 2, 256, 4, 2
    q = randn(B, S, K, H // K, dh, dtype=jnp.bfloat16)
    k, v = randn(B, S, K, dh, dtype=jnp.bfloat16), randn(B, S, K, dh, dtype=jnp.bfloat16)
    ct = randn(B, S, K, H // K, dh, dtype=jnp.bfloat16)
    scale = 1.0 / np.sqrt(dh)

    def splash(q, k, v):
        qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
        return L._splash_attention(qs, k, v, block=128, interpret=True)

    def xla(q, k, v):
        return L._full_attention_xla(q, k, v, causal=True, q_offset=0, scale=scale)

    out = {}
    for name, f in (("splash", splash), ("xla", xla)):
        loss = lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * ct)  # noqa: E731
        o = jax.jit(f)(q, k, v)
        assert o.dtype == jnp.bfloat16 and o.shape == q.shape
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        out[name] = [np.asarray(x, np.float32) for x in (o, *grads)]
    return {n: (a, b) for n, a, b in zip(("out", "dq", "dk", "dv"), out["splash"], out["xla"])}


@pytest.mark.parametrize("name", ["out", "dq", "dk", "dv"])
def test_splash_attention_matches_xla(splash_vs_xla, name):
    got, want = splash_vs_xla[name]
    # Both paths round q (scaled or not), the probabilities or the output to
    # bf16 once each, at different points (the kernel scales q before its
    # cast, keeps its running softmax in float32, and multiplies by v in
    # float32): a few bf16 epsilons (2^-8) of the norm apart
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 1e-2, rel
    # and no element further than 4 bf16 ulps of the largest magnitude
    # (a masked position leaking into the sum would be off by O(1))
    ulp = 2.0 ** (np.floor(np.log2(np.abs(want).max())) - 7)
    assert np.abs(got - want).max() <= 4 * ulp


#: attention calls the guard must hand to the XLA path:
#: (Sq, Skv, dh, causal, window, q_offset, distributed)
XLA_CASES = {
    "window-below-S": (256, 256, 128, True, 128, 0, False),
    "non-causal": (256, 256, 128, False, 0, 0, False),
    "q-offset": (256, 256, 128, True, 0, 128, False),
    "cross-Sq-ne-Skv": (128, 256, 128, True, 0, 0, False),
    "S-below-block": (64, 64, 128, True, 0, 0, False),
    "S-not-a-block-multiple": (320, 320, 128, True, 0, 0, False),
    "head-dim-refused": (256, 256, 32, True, 0, 0, False),
    "distributed-ctx": (256, 256, 128, True, 0, 0, True),
}


def _attention_case(case, seed=0):
    Sq, Skv, dh, causal, window, q_offset, distributed = case
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((1, Sq, 4, dh)), jnp.bfloat16)
    k, v = (jnp.asarray(r.standard_normal((1, Skv, 2, dh)), jnp.bfloat16) for _ in range(2))
    kw = dict(n_kv_heads=2, causal=causal, window=window, q_offset=q_offset,
              distributed=distributed)
    return q, k, v, kw


@pytest.mark.parametrize("on_tpu", [False, True], ids=["cpu", "tpu"])
@pytest.mark.parametrize("case", list(XLA_CASES), ids=list(XLA_CASES))
def test_attention_dispatch_keeps_xla_path(monkeypatch, case, on_tpu):
    """On the CPU every call, and on a TPU every shape the guard refuses,
    runs today's XLA path: the same numbers, and the kernel never called."""
    q, k, v, kw = _attention_case(XLA_CASES[case])
    want = np.asarray(L.attention(q, k, v, **kw), np.float32)

    def no_kernel(*a, **k):
        raise AssertionError("the splash kernel was called")

    monkeypatch.setattr(L, "_splash_attention", no_kernel)
    if on_tpu:
        monkeypatch.setattr(L, "_backend", lambda: "tpu")
    Sq, Skv, dh = q.shape[1], k.shape[1], q.shape[-1]
    assert L.splash_blocks(Sq, Skv, dh, causal=kw["causal"], window=kw["window"],
                           q_offset=kw["q_offset"], distributed=kw["distributed"]) is None
    np.testing.assert_array_equal(np.asarray(L.attention(q, k, v, **kw), np.float32), want)


@pytest.mark.parametrize("S,window", [(256, 0), (1024, 0), (512, 512)])
def test_attention_dispatch_takes_splash_on_tpu(monkeypatch, S, window):
    """Causal self-attention with no window shorter than S, at a block
    multiple, on a TPU and off a distributed mesh: the kernel path."""
    monkeypatch.setattr(L, "_backend", lambda: "tpu")
    block = L.splash_blocks(S, S, 128, causal=True, window=window)
    assert block in L.SPLASH_BLOCKS and S % block == 0
    assert block == max(b for b in L.SPLASH_BLOCKS if S % b == 0)
    # a prescaled q belongs to the kernel path only
    q, k, v, kw = _attention_case(XLA_CASES["non-causal"])
    with pytest.raises(ValueError):
        L.attention(q, k, v, prescaled=True, **kw)


def test_attn_apply_on_the_kernel_path_folds_the_scale_into_rope(monkeypatch):
    """attn_apply with the kernel (interpret mode) and with the XLA path agree:
    1/sqrt(d_head) is applied once, in q's RoPE."""
    from repro.configs import get_config
    from repro.models.module import split_params

    cfg = get_config("yi_9b").reduced().replace(d_model=256, n_heads=2, n_kv_heads=1,
                                               sliding_window=0)
    assert cfg.d_head == 128 and cfg.attn_impl == "xla"
    p, _ = split_params(L.attn_init(jax.random.PRNGKey(0), cfg))
    x = randn(2, 256, cfg.d_model, dtype=jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(256)[None], (2, 256))
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
    want, _ = L.attn_apply(p, x, cfg, positions=pos)

    kernel = L._splash_attention
    called = []

    def interpreted(q, k, v, *, block):
        called.append(block)
        return kernel(q, k, v, block=block, interpret=True)

    monkeypatch.setattr(L, "_backend", lambda: "tpu")
    monkeypatch.setattr(L, "_splash_attention", interpreted)
    got, _ = L.attn_apply(p, x, cfg, positions=pos)
    assert called
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # the projections' bf16 rounding on top of the attention's (above)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-2
    # under a distributed ctx the same call keeps XLA
    called.clear()
    L.attn_apply(p, x, cfg, positions=pos, distributed=True)
    assert not called


# --------------------------------------------------------------- flash decode


@pytest.mark.parametrize("B,S,H,K,dh,bk", [(2, 512, 4, 2, 64, 256), (3, 256, 8, 1, 128, 64),
                                           (1, 1024, 2, 2, 32, 256)])
def test_flash_decode_matches_ref(B, S, H, K, dh, bk):
    q = randn(B, 1, H, dh)
    kc, vc = randn(B, S, K, dh), randn(B, S, K, dh)
    lens = jnp.asarray(RNG.integers(1, S + 1, (B,)), jnp.int32)
    out = flash_decode(q, kc, vc, lens, bk=bk)
    ref = decode_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_flash_decode_full_cache_equals_attention_row():
    """Decode over a fully-valid cache == last row of causal attention."""
    B, S, H, dh = 1, 256, 2, 64
    k = randn(B, S, H, dh)
    v = randn(B, S, H, dh)
    q_full = randn(B, S, H, dh)
    full = attention_ref(q_full, k, v, causal=True)
    dec = flash_decode(q_full[:, -1:], k, v, jnp.asarray([S], jnp.int32))
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, -1]), atol=3e-5)


# ------------------------------------------------------------- selective scan


@pytest.mark.parametrize("B,S,ed,n,Q,be", [(2, 64, 128, 16, 16, 64), (1, 32, 64, 8, 8, 64),
                                           (2, 128, 256, 16, 32, 128), (1, 64, 64, 4, 64, 32)])
def test_selective_scan_matches_ref(B, S, ed, n, Q, be):
    x = randn(B, S, ed)
    dt = jnp.abs(randn(B, S, ed)) * 0.1
    A = -jnp.abs(randn(ed, n))
    Bc, Cc = randn(B, S, n), randn(B, S, n)
    h0 = randn(B, ed, n)
    y, h = selective_scan(x, dt, A, Bc, Cc, h0, chunk=Q, block_ed=be)
    yr, hr = selective_scan_ref(x, dt, A, Bc, Cc, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)


def test_selective_scan_state_chaining():
    """Scanning two halves with carried state == one full scan."""
    B, S, ed, n = 1, 64, 32, 8
    x, dt = randn(B, S, ed), jnp.abs(randn(B, S, ed)) * 0.1
    A = -jnp.abs(randn(ed, n))
    Bc, Cc = randn(B, S, n), randn(B, S, n)
    y_full, h_full = selective_scan(x, dt, A, Bc, Cc, chunk=16, block_ed=32)
    y1, h1 = selective_scan(x[:, :32], dt[:, :32], A, Bc[:, :32], Cc[:, :32], chunk=16, block_ed=32)
    y2, h2 = selective_scan(x[:, 32:], dt[:, 32:], A, Bc[:, 32:], Cc[:, 32:], h0=h1, chunk=16, block_ed=32)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), atol=1e-4)


# -------------------------------------------------------------- guided update


@pytest.mark.parametrize("n,block", [(1000, 256), (65536, 65536), (37 * 129, 512)])
def test_guided_sgd_update_matches_ref(n, block):
    w = randn(n)
    g = randn(n) * 0.01
    ws = w + 0.05
    out = guided_sgd_update(w, g, ws, 0.2, 0.04, block=block)
    ref = guided_sgd_update_ref(w, g, ws, 0.2, 0.04)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_guided_rmsprop_update_matches_ref():
    tree = {"a": randn(513), "b": {"c": randn(17, 65)}}
    g = jax.tree.map(lambda x: x * 0.01, tree)
    ws = jax.tree.map(lambda x: x + 0.1, tree)
    r = jax.tree.map(lambda x: jnp.abs(x) * 0.2, tree)
    nw, nr = guided_rmsprop_update(tree, g, ws, r, 0.2, 0.04, block=256)
    for k in ("a",):
        rw, rr = guided_rmsprop_update_ref(tree[k], g[k], ws[k], r[k], 0.2, 0.04, 0.9, 1e-8)
        np.testing.assert_allclose(np.asarray(nw[k]), np.asarray(rw), atol=1e-6)
        np.testing.assert_allclose(np.asarray(nr[k]), np.asarray(rr), atol=1e-6)


def test_guided_update_lam_zero_is_sgd():
    w, g, ws = randn(333), randn(333), randn(333)
    out = guided_sgd_update(w, g, ws, 0.1, 0.0, block=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(w - 0.1 * g), atol=1e-6)


# ------------------------------------------- fused whole-update (DESIGN.md §11)


def _optim_composition(optimizer, w, g, ws, state, lr, lam, **hy):
    """The unfused two-phase path the fused kernels replace: DC-ASGD
    compensation materialized, then the `repro.optim` accumulator update."""
    from repro.optim import get_optimizer

    gt = g + lam * g * g * (w - ws)
    opt = get_optimizer(optimizer, **hy)
    upd, state = opt.update(gt, state, w, lr)
    return w + upd, state


@pytest.mark.parametrize("n,block", [(37 * 129, 512), (4096, 4096)])
@pytest.mark.parametrize("impl", ["kernel", "ref"])
@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_momentum_matches_optimizer_composition(n, block, impl, nesterov):
    from repro.kernels.guided_update.ops import fused_update_for

    w = randn(n)
    g = randn(n) * 0.01
    ws = w + 0.05
    m = jnp.abs(randn(n)) * 0.1
    lr, lam = 0.2, 0.04
    fused = fused_update_for("momentum", beta=0.9, nesterov=nesterov, impl=impl)
    w_f, (m_f,) = fused(w, g, ws, (m,), 1, lr, lam, block=block)
    w_r, st = _optim_composition("momentum", w, g, ws, {"m": m}, lr, lam,
                                 beta=0.9, nesterov=nesterov)
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_f), np.asarray(st["m"]), atol=1e-6)


@pytest.mark.parametrize("n,block", [(37 * 129, 512), (4096, 4096)])
@pytest.mark.parametrize("impl", ["kernel", "ref"])
@pytest.mark.parametrize("t", [1, 7])
def test_fused_adam_matches_optimizer_composition(n, block, impl, t):
    from repro.kernels.guided_update.ops import fused_update_for

    w = randn(n)
    g = randn(n) * 0.01
    ws = w + 0.05
    m = jnp.abs(randn(n)) * 0.1
    v = jnp.abs(randn(n)) * 0.05
    lr, lam = 0.2, 0.04
    fused = fused_update_for("adam", b1=0.9, b2=0.999, eps=1e-8, impl=impl)
    w_f, (m_f, v_f) = fused(w, g, ws, (m, v), t, lr, lam, block=block)
    state = {"m": m, "v": v, "t": jnp.asarray(t - 1, jnp.int32)}
    w_r, st = _optim_composition("adam", w, g, ws, state, lr, lam,
                                 b1=0.9, b2=0.999, eps=1e-8)
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_f), np.asarray(st["m"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_f), np.asarray(st["v"]), atol=1e-6)


def test_fused_kernels_match_ref_float64():
    """The f64 regime (delay-sim parity): Pallas kernel vs the pure-jnp ref
    at the scan backend's acceptance bar, odd size exercising the pad path."""

    from repro.kernels.guided_update import kernel as K
    from repro.kernels.guided_update import ref as R

    with jax.enable_x64():
        rng = np.random.default_rng(7)
        n = 37 * 129
        w = jnp.asarray(rng.standard_normal(n), jnp.float64)
        g = w * 0.01
        ws = w + 0.05
        m = jnp.abs(w) * 0.1
        v = jnp.abs(w) * 0.05

        w_k, m_k = K.guided_momentum_update_raw(w, g, ws, m, 0.2, 0.04, 0.9,
                                                block=512)
        w_r, m_r = R.guided_momentum_update_ref(w, g, ws, m, 0.2, 0.04, 0.9)
        np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r), atol=1e-12)
        np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r), atol=1e-12)

        out_k = K.guided_adam_update_raw(w, g, ws, m, v, 5, 0.2, 0.04,
                                         0.9, 0.999, 1e-8, block=512)
        out_r = R.guided_adam_update_ref(w, g, ws, m, v, 5, 0.2, 0.04,
                                         0.9, 0.999, 1e-8)
        for a, b in zip(out_k, out_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-12)
        assert all(o.dtype == jnp.float64 for o in out_k)


def test_fused_update_for_rejects_unfused_optimizer():
    from repro.kernels.guided_update.ops import FUSED_OPTIMIZERS, fused_update_for

    assert "adagrad" not in FUSED_OPTIMIZERS
    with pytest.raises(KeyError):
        fused_update_for("adagrad")


# ------------------------------------------------- derived block, lam = 0 stream


def _raw_update(name, lam, ref=False):
    """(fn(w, g, ws, *acc), n_acc) of one fused kernel at `lam`; with `ref`,
    of its pure-jnp reference."""
    from repro.kernels.guided_update import kernel as K
    from repro.kernels.guided_update import ref as R

    u = lambda base: getattr(R, f"{base}_ref") if ref else getattr(K, f"{base}_raw")
    if name == "sgd":
        return (lambda w, g, ws: u("guided_sgd_update")(w, g, ws, 0.2, lam)), 0
    if name == "momentum":
        return (lambda w, g, ws, m: u("guided_momentum_update")(
            w, g, ws, m, 0.2, lam, 0.9, nesterov=True)), 1
    if name == "rmsprop":
        return (lambda w, g, ws, r: u("guided_rmsprop_update")(
            w, g, ws, r, 0.2, lam, 0.9, 1e-8)), 1
    return (lambda w, g, ws, m, v: u("guided_adam_update")(
        w, g, ws, m, v, 3, 0.2, lam, 0.9, 0.999, 1e-8)), 2


def _pallas_inputs(fn, *args):
    (eqn,) = [e for e in jax.make_jaxpr(fn)(*args).eqns
              if e.primitive.name == "pallas_call"]
    return len(eqn.invars)


@pytest.mark.parametrize("name", ["sgd", "momentum", "rmsprop", "adam"])
def test_lam_zero_drops_w_stale_stream_bit_identical(name):
    """A Python 0 for lam launches the kernel without its w_stale operand; a
    traced 0 keeps it (the delay-simulation scan). The results are bit for bit
    the same, though w_stale differs from w."""
    shape = (3, 40, 2, 136)
    w = randn(*shape)
    g = randn(*shape) * 0.01
    ws = w + 0.05
    acc = [jnp.abs(randn(*shape)) * 0.1 for _ in range(2)]
    static, n_acc = _raw_update(name, 0.0)
    traced, _ = _raw_update(name, jnp.asarray(0.0))
    args = (w, g, ws, *acc[:n_acc])
    # every array in, plus the scalar pack
    assert _pallas_inputs(static, *args) == len(args)
    assert _pallas_inputs(traced, *args) == len(args) + 1
    for a, b in zip(jax.tree.leaves(static(*args)), jax.tree.leaves(traced(*args))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tuple(out):
    return out if isinstance(out, tuple) else (out,)


def _assert_same_bits(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("caller", ["reads_old", "donated_scan"])
@pytest.mark.parametrize("name,lam", [("sgd", 0.0), ("sgd", 0.04), ("momentum", 0.04),
                                      ("rmsprop", 0.04), ("adam", 0.04)])
def test_in_place_update_is_bit_identical(monkeypatch, name, lam, caller):
    """Each kernel writes `w` and its accumulators in place over donated
    buffers, and gives the bits of the reference and of the same kernel
    launched with fresh outputs: where the caller still reads the old values
    after the update in the same jit (XLA copies them aside), and over a
    three-step scan. The rmsprop kernel rounds 1 - beta from its float32
    beta where the reference rounds the Python float (2.4e-7 apart at beta =
    0.9), so its r' is the reference's to that, with or without the aliases."""
    rng = np.random.default_rng(18)
    shape = (3, 40, 2, 136)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    fn, n_acc = _raw_update(name, lam)
    ref, _ = _raw_update(name, lam, ref=True)
    w = normal(*shape).astype(jnp.bfloat16)
    g = (0.01 * normal(3, *shape)).astype(jnp.bfloat16)
    ws = (w + 0.05).astype(jnp.bfloat16)
    acc = tuple(0.1 * jnp.abs(normal(*shape)) for _ in range(n_acc))

    if caller == "reads_old":
        def run(f):
            @partial(jax.jit, donate_argnums=(0, 3))
            def step(w, g, ws, acc):
                new = _tuple(f(w, g, ws, *acc))
                return new, tuple(n.astype(jnp.float32) - o for n, o in zip(new, (w, *acc)))

            return step(jnp.copy(w), g[0], ws, tuple(jnp.copy(a) for a in acc))
    else:
        def run(f):
            @partial(jax.jit, donate_argnums=(0, 3))
            def scan(w, gs, ws, acc):
                def body(carry, g):
                    out = _tuple(f(carry[0], g, ws, *carry[1]))
                    return (out[0], out[1:]), None

                return jax.lax.scan(body, (w, acc), gs)[0]

            return scan(jnp.copy(w), g, ws, tuple(jnp.copy(a) for a in acc))

    in_place, want = run(fn), run(ref)
    if name == "rmsprop":
        got_w, got_r = jax.tree.leaves(in_place)[:2]
        want_w, want_r = jax.tree.leaves(want)[:2]
        np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
        np.testing.assert_allclose(np.asarray(got_r), np.asarray(want_r), rtol=1e-6, atol=0)
    else:
        _assert_same_bits(in_place, want)
    from jax.experimental import pallas as pl

    call = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, input_output_aliases=None, **kw:
                        call(*a, **kw))
    _assert_same_bits(in_place, run(fn))


@pytest.mark.parametrize("shape", [(1000,), (37, 129), (3, 40, 2, 136), (5, 3, 700)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("block", [None, 256, 4096])
def test_fused_update_same_result_at_any_tiling(shape, block):
    """The block is a launch parameter only: any view and block of a leaf
    (merged rows, kept last two dims, split last dim) gives the numbers of the
    reference."""
    from repro.kernels.guided_update import kernel as K
    from repro.kernels.guided_update import ref as R

    w = randn(*shape)
    g = randn(*shape) * 0.01
    ws = w + 0.05
    m, v = jnp.abs(w) * 0.1, jnp.abs(w) * 0.05
    hy = (3, 0.2, 0.04, 0.9, 0.999, 1e-8)
    got = K.guided_adam_update_raw(w, g, ws, m, v, *hy, block=block)
    for a, b in zip(got, R.guided_adam_update_ref(w, g, ws, m, v, *hy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("shape", [(13, 4096, 2, 11008), (10, 2304, 2, 5760),
                                   (4096, 64000), (122753, 2304), (3, 4097, 5000)],
                         ids=lambda s: "x".join(map(str, s)))
def test_tiling_keeps_the_layout_and_splits_lanes_evenly(shape):
    """The view is the leaf's own layout (leading dims merge only over a
    second-minor dim that is a multiple of the sublanes), a block never
    passes its budget, and a split last dim is split into equal parts."""
    from repro.kernels import LANE, SUBLANES, stream_block, tiling

    for block in (stream_block(["bfloat16"] * 3), stream_block(["bfloat16"] * 4 + ["float32"] * 4)):
        view, bs, grid = tiling(shape, block)
        assert int(np.prod(view)) == int(np.prod(shape)) and view[-1] == shape[-1]
        if len(shape) >= 3 and shape[-2] % SUBLANES:
            assert view[1:] == shape[-2:]
        assert int(np.prod(bs)) <= block
        assert bs[-1] == shape[-1] or (shape[-1] % bs[-1] == 0 and bs[-1] % LANE == 0)
        assert grid == tuple(-(-a // b) for a, b in zip(view, bs))
