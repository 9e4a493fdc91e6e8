"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
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


# ------------------------------------------------------------------- autotune


def test_autotune_cache_roundtrip(tmp_path):
    """Sweep once (injected deterministic probe), persist, then re-resolve
    from the JSON with NO probe — simulating a fresh process on the same box."""
    from repro.kernels import autotune

    calls = []

    def fake_measure(kernel, dtype, block):
        calls.append(block)
        return abs(block - 32768) + 1.0  # 32k is fastest by construction

    autotune.clear_memo()
    got = autotune.tuned_block("guided_adam_update", jnp.float32,
                               dirname=str(tmp_path), measure=fake_measure)
    assert got == 32768
    # only the blocks that fit fast memory are swept (adam f32: 64 B/element)
    assert sorted(calls) == sorted(autotune.candidates("guided_adam_update",
                                                       jnp.float32))
    assert max(calls) * 64 <= autotune.VMEM_STREAM_BYTES

    path = autotune.cache_path(str(tmp_path))
    import json
    with open(path) as f:
        data = json.load(f)
    assert data["guided_adam_update.float32"] == 32768

    autotune.clear_memo()  # fresh "process": memo gone, JSON remains
    calls.clear()
    again = autotune.tuned_block("guided_adam_update", jnp.float32,
                                 dirname=str(tmp_path))
    assert again == 32768
    assert calls == []  # served from the persisted winners, no re-sweep

    # and the memo now short-circuits the file read entirely
    assert autotune.tuned_block("guided_adam_update", jnp.float32,
                                dirname=str(tmp_path)) == 32768


def test_autotune_interpret_returns_default_unswept(tmp_path, monkeypatch):
    """On interpret backends (cpu) the sweep is skipped and nothing persists:
    timing the emulator would tune the wrong thing."""
    import os

    from repro.kernels import autotune

    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    autotune.clear_memo()
    got = autotune.tuned_block("guided_sgd_update", jnp.float32,
                               dirname=str(tmp_path))
    assert got == autotune.DEFAULT_BLOCK
    assert not os.path.exists(autotune.cache_path(str(tmp_path)))


def test_autotune_tuned_block_drives_kernel_result_identical(tmp_path):
    """The tuned block is a launch parameter only: same numbers at any block."""
    from repro.kernels import autotune
    from repro.kernels.guided_update import kernel as K

    autotune.clear_memo()
    block = autotune.tuned_block(
        "guided_momentum_update", jnp.float32, dirname=str(tmp_path),
        measure=lambda k, d, b: float(b))  # smallest candidate wins
    assert block == min(autotune.CANDIDATES)

    w = randn(1000)
    g = randn(1000) * 0.01
    ws = w + 0.05
    m = jnp.abs(w) * 0.1
    a = K.guided_momentum_update_raw(w, g, ws, m, 0.2, 0.04, 0.9, block=block)
    b = K.guided_momentum_update_raw(w, g, ws, m, 0.2, 0.04, 0.9, block=256)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_autotune_sweep_inside_a_trace_runs_the_probes(tmp_path):
    """The first resolution happens while the train step is traced; the
    probes must still run on the device (concrete arrays), not be staged
    into the step being traced — else the sweep times the tracer."""
    from repro.kernels import autotune
    from repro.kernels.guided_update import kernel as K

    seen = []

    def measure(kernel, dtype, block):
        seen.append(isinstance(jnp.ones(2) + 1, jax.core.Tracer))
        return float(block)

    autotune.clear_memo()
    step = jax.jit(lambda w: K.guided_sgd_update_raw(
        w, w, w, 0.1, 0.0, block=autotune.tuned_block(
            "guided_sgd_update", w.dtype, dirname=str(tmp_path), measure=measure)))
    step(jnp.ones((16, 256)))
    assert seen and not any(seen)
