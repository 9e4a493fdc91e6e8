"""Kernel micro-benchmarks + the fused whole-update regression suite.

Two parts:

  * `bench_micro()` — the legacy one-shot rows (flash attention / decode /
    selective scan / guided sgd apply), interpret vs XLA-ref. On this CPU host
    the Pallas kernels run in interpret mode, so wall-clock is NOT the TPU
    number; the derived column carries the analytic FLOPs/bytes the roofline
    uses.
  * `bench_fused()` — the CI-gated suite (BENCH_kernels.json): per optimizer
    (sgd/momentum/adam/rmsprop) and size, the PRODUCTION whole-update path
    (`fused_update_for(impl="auto")`: one dispatch — Pallas kernel on gpu/tpu,
    the XLA-fused jnp reference on cpu) against the unfused two-dispatch
    chain it replaced (dispatch 1: guided/DC compensation materializing g~;
    dispatch 2: `repro.optim` accumulator update + apply). Records wall time,
    speedup, analytic HBM bytes, achieved bytes/s, dispatch counts, and
    parity of the fused result vs the optimizers-composed reference.
    `benchmarks/kernel_gate.py` fails CI when the fused/unfused speedup
    regresses >20% against the committed baseline.

Timing: best-of-3 repeats of an averaged loop (min absorbs scheduler noise on
shared CI boxes).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

#: fused-suite sizes; --small trims to the first two (the gate compares the
#: common keys only)
SIZES = (16384, 65536, 262144, 1048576)
SMALL_SIZES = (16384, 65536)

#: analytic HBM traffic of the fused kernel, in 4-byte words per element:
#: reads(w,g,ws[,acc...]) + writes(w[,acc...])
_WORDS = {"sgd": 4, "momentum": 6, "rmsprop": 6, "adam": 8}


def _time(fn, *args, iters=3, repeats=3) -> float:
    """us per call: best-of-`repeats` averaged timing loops (compile excluded)."""
    jax.block_until_ready(fn(*args))  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e6


def _fused_case(optimizer: str, n: int, dtype=jnp.float32):
    """One (optimizer, size) comparison: production fused path vs the
    two-dispatch unfused chain, plus parity vs the optimizers composition."""
    from repro.kernels.guided_update.ops import fused_update_for
    from repro.optim import get_optimizer

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal(n), dtype)
    g = w * 0.01
    ws = w + 0.05
    lr, lam = 0.2, 0.04
    opt = get_optimizer(optimizer)
    hy = {k: v for k, v in opt.hypers.items() if k != "weight_decay"}
    acc0 = {
        "sgd": (),
        "momentum": (jnp.abs(w) * 0.1,),
        "rmsprop": (jnp.abs(w) * 0.1,),
        "adam": (jnp.abs(w) * 0.1, jnp.abs(w) * 0.05),
    }[optimizer]
    t_step = 3

    # --- production fused path: ONE dispatch ------------------------------
    fused = fused_update_for(optimizer, impl="auto", **hy)

    @jax.jit
    def run_fused(w, g, ws, acc):
        return fused(w, g, ws, acc, t_step, lr, lam)

    # --- unfused: compensation dispatch, then optimizer-ops dispatch ------
    @jax.jit
    def compensate(w, g, ws):
        return g + lam * g * g * (w - ws)

    opt_state = {
        "sgd": (),
        "momentum": lambda: {"m": acc0[0]},
        "rmsprop": lambda: {"r": acc0[0]},
        "adam": lambda: {"m": acc0[0], "v": acc0[1],
                         "t": jnp.asarray(t_step - 1, jnp.int32)},
    }[optimizer]
    opt_state = opt_state() if callable(opt_state) else opt_state

    @jax.jit
    def apply_opt(w, gt, state):
        upd, state = opt.update(gt, state, w, lr)
        return w + upd, state

    def run_unfused(w, g, ws, state):
        gt = compensate(w, g, ws)
        return apply_opt(w, gt, state)

    iters = max(8, (1 << 22) // n)
    fused_us = _time(run_fused, w, g, ws, acc0, iters=iters, repeats=4)
    unfused_us = _time(run_unfused, w, g, ws, opt_state, iters=iters, repeats=4)

    # parity: fused result vs compensation composed with the optimizers update
    w_f, _ = run_fused(w, g, ws, acc0)
    w_u, _ = run_unfused(w, g, ws, opt_state)
    parity = float(np.max(np.abs(np.asarray(w_f, np.float64)
                                 - np.asarray(w_u, np.float64))))

    word = jnp.dtype(dtype).itemsize
    hbm = _WORDS[optimizer] * word * n
    return {
        "kernel": f"guided_{optimizer}_update",
        "optimizer": optimizer,
        "n": n,
        "dtype": jnp.dtype(dtype).name,
        "impl": fused.impl,
        "fused_us": fused_us,
        "unfused_us": unfused_us,
        "speedup": unfused_us / fused_us,
        "dispatches_fused": 1,
        "dispatches_unfused": 2,
        "hbm_bytes": hbm,
        "fused_bytes_per_s": hbm / (fused_us * 1e-6),
        "parity_max_abs_diff": parity,
    }


def _interpret_diag(n: int = 65536):
    """Interpret-mode kernel wall times (diagnostic only: pure emulation on
    cpu, the compiled-path number on gpu/tpu)."""
    from repro.kernels.guided_update import kernel as K

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal(n), jnp.float32)
    g = w * 0.01
    ws = w + 0.05
    acc = jnp.abs(w) * 0.1
    runs = {
        "guided_sgd_update": lambda: K.guided_sgd_update_raw(
            w, g, ws, 0.2, 0.04),
        "guided_momentum_update": lambda: K.guided_momentum_update_raw(
            w, g, ws, acc, 0.2, 0.04, 0.9),
        "guided_rmsprop_update": lambda: K.guided_rmsprop_update_raw(
            w, g, ws, acc, 0.2, 0.04, 0.9, 1e-8),
        "guided_adam_update": lambda: K.guided_adam_update_raw(
            w, g, ws, acc, acc, 3, 0.2, 0.04, 0.9, 0.999, 1e-8),
    }
    return [{"kernel": k, "n": n, "us": _time(fn, iters=1, repeats=2)}
            for k, fn in runs.items()]


def bench_fused(small: bool = False) -> dict:
    """The structured BENCH_kernels.json payload."""
    from repro.kernels import default_interpret

    sizes = SMALL_SIZES if small else SIZES
    entries = [_fused_case(opt, n)
               for opt in ("sgd", "momentum", "rmsprop", "adam")
               for n in sizes]
    dev = jax.devices()[0]
    return {
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "interpret": default_interpret(),
        "sizes": list(sizes),
        "entries": entries,
        "interpret_diag": _interpret_diag(),
    }


def bench_micro():
    rows = []
    rng = np.random.default_rng(0)

    # flash attention (XLA reference path at bench shape; kernel in interpret)
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    B, S, H, K, dh = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, K, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, K, dh)), jnp.float32)
    flops = 4 * B * H * S * S * dh
    rows.append(("flash_attention_interpret",
                 _time(lambda *a: flash_attention(*a, causal=True), q, k, v, repeats=1),
                 f"flops={flops:.3g}"))
    ref = jax.jit(lambda *a: attention_ref(*a, causal=True))
    rows.append(("attention_xla_ref", _time(ref, q, k, v, repeats=1), f"flops={flops:.3g}"))

    # flash decode
    from repro.kernels.flash_decode.ops import flash_decode
    from repro.kernels.flash_decode.ref import decode_ref

    S2 = 2048
    q1 = jnp.asarray(rng.standard_normal((2, 1, H, dh)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((2, S2, K, dh)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((2, S2, K, dh)), jnp.float32)
    lens = jnp.asarray([S2, S2 // 2], jnp.int32)
    dflops = 4 * 2 * H * S2 * dh
    rows.append(("flash_decode_interpret", _time(flash_decode, q1, kc, vc, lens, repeats=1),
                 f"flops={dflops:.3g}"))
    rows.append(("decode_xla_ref", _time(jax.jit(decode_ref), q1, kc, vc, lens, repeats=1),
                 f"flops={dflops:.3g}"))

    # selective scan
    from repro.kernels.selective_scan.ops import selective_scan
    from repro.kernels.selective_scan.ref import selective_scan_ref

    Bs, Ss, ed, n = 1, 64, 128, 16
    x = jnp.asarray(rng.standard_normal((Bs, Ss, ed)), jnp.float32)
    dt = jnp.abs(x) * 0.1
    A = -jnp.abs(jnp.asarray(rng.standard_normal((ed, n)), jnp.float32))
    Bc = jnp.asarray(rng.standard_normal((Bs, Ss, n)), jnp.float32)
    Cc = jnp.asarray(rng.standard_normal((Bs, Ss, n)), jnp.float32)
    sflops = 6 * Bs * Ss * ed * n
    rows.append(("selective_scan_interpret",
                 _time(selective_scan, x, dt, A, Bc, Cc, repeats=1), f"flops={sflops:.3g}"))
    rows.append(("selective_scan_xla_ref",
                 _time(jax.jit(selective_scan_ref), x, dt, A, Bc, Cc, repeats=1),
                 f"flops={sflops:.3g}"))

    # guided update (the paper's hot spot): fused kernel vs unfused XLA chain
    from repro.kernels.guided_update.ops import guided_sgd_update
    from repro.kernels.guided_update.ref import guided_sgd_update_ref

    npar = 1 << 20
    w = jnp.asarray(rng.standard_normal(npar), jnp.float32)
    g = w * 0.01
    ws = w + 0.05
    gbytes = 4 * npar * 4  # r(w,g,ws) + w(out)
    rows.append(("guided_update_interpret",
                 _time(lambda *a: guided_sgd_update(*a, 0.2, 0.04), w, g, ws, iters=1, repeats=2),
                 f"hbm_bytes={gbytes:.3g}"))
    rows.append(("guided_update_xla_ref",
                 _time(jax.jit(lambda *a: guided_sgd_update_ref(*a, 0.2, 0.04)), w, g, ws),
                 f"hbm_bytes={gbytes:.3g}"))
    return rows


def bench_all(small: bool = False) -> dict:
    out = bench_fused(small=small)
    out["micro"] = [list(r) for r in bench_micro()]
    return out


def main():
    out = bench_all()
    for name, us, derived in out["micro"]:
        print(f"{name},{us:.1f},{derived}")
    for e in out["entries"]:
        print(f"{e['kernel']}_n{e['n']},{e['fused_us']:.1f},"
              f"speedup={e['speedup']:.2f}x;parity={e['parity_max_abs_diff']:.2g}")


if __name__ == "__main__":
    main()
