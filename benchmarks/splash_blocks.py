#!/usr/bin/env python3
"""Block sizes of the splash attention kernels, timed on a TPU.

    python benchmarks/splash_blocks.py            # yi-9b's attention, then minicpm's

One layer's exact causal GQA at the chip benchmark's training shapes (yi-9b:
B 8, S 1024, 32 query and 4 KV heads, d_head 128, bf16), as
`models.layers._splash_attention` runs it, for each forward block and each
backward block; then the XLA path (`_full_attention_xla`) at the same shapes,
and both paths at minicpm-2b's (B 4, 36 and 36 heads, d_head 64). Prints one
line per configuration: the forward, the gradient (forward with residuals,
dq and dkv), and forward + gradient, which is what a remat training step
runs per layer (a forward, its recompute and the backward), in ms as the
median of REPEATS timed loops of ITERS calls; and the largest error of the
kernel's output and gradients against the XLA path. The winner's blocks go
into `layers.SPLASH_BLOCKS` by hand: nothing sweeps at run time.

Needs a TPU: the numbers of interpret mode say nothing about the chip.
"""
from __future__ import annotations

import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

BLOCKS = (128, 256, 512, 1024)
ITERS = 20
REPEATS = 5
SHAPES = {"yi-9b": (8, 1024, 32, 4, 128), "minicpm-2b": (4, 1024, 36, 36, 64)}


def timed(fn, *args) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    runs = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            out = fn(*args)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - t0) / ITERS * 1e3)
    return statistics.median(runs)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    from repro.models import layers as L

    if jax.default_backend() != "tpu":
        print("splash_blocks.py: no TPU; the sweep runs on the chip only", file=sys.stderr)
        return 2
    print(f"device {jax.devices()[0].device_kind}", flush=True)

    def kernel_for(fwd: int, bwd: int):
        def build(S, G, block, interpret):
            mask = sm.MultiHeadMask([sm.CausalMask((S, S))] * G)
            bs = sk.BlockSizes(block_q=fwd, block_kv=fwd, block_kv_compute=fwd,
                               block_q_dkv=bwd, block_kv_dkv=bwd, block_kv_dkv_compute=bwd,
                               block_q_dq=bwd, block_kv_dq=bwd)
            with jax.ensure_compile_time_eval():
                return sk.make_splash_mqa_single_device(mask, block_sizes=bs)
        return build

    for name, (B, S, H, K, dh) in SHAPES.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, S, K, H // K, dh)).astype(jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, K, dh)).astype(jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, K, dh)).astype(jnp.bfloat16)
        ct = jax.random.normal(ks[3], q.shape).astype(jnp.bfloat16)
        scale = 1.0 / np.sqrt(dh)

        def xla(q, k, v):
            return L._full_attention_xla(q, k, v, causal=True, q_offset=0, scale=scale)

        def grad_of(f):
            return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                f(q, k, v).astype(jnp.float32) * ct), argnums=(0, 1, 2)))

        f, g = jax.jit(xla), grad_of(xla)
        want, want_g = f(q, k, v), g(q, k, v)
        fx, gx = timed(f, q, k, v), timed(g, q, k, v)
        print(f"{name} xla: fwd {fx:.3f} grad {gx:.3f} fwd+grad {fx + gx:.3f} ms", flush=True)

        def splash(q, k, v):
            # q unscaled, as the XLA path takes it: the kernel's gradient
            # then compares with XLA's; the blocks are `kernel_for`'s
            return L._splash_attention((q.astype(jnp.float32) * scale).astype(q.dtype),
                                       k, v, block=0)

        pairs = [(b, b) for b in BLOCKS]
        if name == "yi-9b":  # every forward block with every backward block
            pairs += [(f, b) for f in BLOCKS for b in BLOCKS if f != b]
        for fwd, bwd in pairs:
            if S % fwd or S % bwd:
                continue
            L._splash_kernel = kernel_for(fwd, bwd)
            try:
                f, g = jax.jit(splash), grad_of(splash)
                got, got_g = f(q, k, v), g(q, k, v)
                ft, gt = timed(f, q, k, v), timed(g, q, k, v)
            except Exception as e:  # noqa: BLE001 - a block the compiler refuses
                print(f"{name} splash fwd {fwd} bwd {bwd}: refused: {str(e)[:200]}", flush=True)
                continue
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
            gerr = [float(jnp.linalg.norm((a - b).astype(jnp.float32))
                          / jnp.linalg.norm(b.astype(jnp.float32))) for a, b in zip(got_g, want_g)]
            print(f"{name} splash fwd {fwd} bwd {bwd}: fwd {ft:.3f} grad {gt:.3f} "
                  f"fwd+grad {ft + gt:.3f} ms; out max|d| {err:.3e}, grad rel "
                  f"q {gerr[0]:.3e} k {gerr[1]:.3e} v {gerr[2]:.3e}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
