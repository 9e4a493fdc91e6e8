#!/usr/bin/env python3
"""Bring-up smoke on a TPU: the guided trainer and the serve engine at yi-9b's
published widths, through the entry points a user calls. A smoke, not a
benchmark: the times it prints are one cold run, not measurements.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the c = 4 data-parallel mesh only

One chip, in order (any failure ends the run non-zero, with no result line):

  1. device  — JAX must see a TPU; there is no CPU fallback.
  2. kernel  — the fused guided-update kernels (sgd, adam) on one real yi-9b
               leaf (4096, 11008) in bf16 against the pure-jnp references,
               and `tpu_custom_call` in the compiled train step: the kernel,
               not the reference, is on the path.
  3. train   — `Trainer.from_spec(...).fit()`: the paper's gSSGD
               (mode=ssgd, strategy=guided_fused, c = 4 workers on one chip)
               chunked with prefetch, then a short DC-ASGD (mode=asgd,
               strategy=dc_asgd: w_stale and lambda != 0 in the kernel).
               Every loss must be finite.
  4. serve   — `ServeEngine(...).run()` on the trained params: greedy tokens
               through the KV cache must equal the argmax of one full
               forward pass without cache over the same prefix.

`--chips 4` runs only gSSGD on `mesh="host"` over four chips (c = 4, params
FSDP-sharded over `data`, the fused update per shard) against `mesh="local"`
with `workers=4` on one chip, in this process, and compares per-step losses.

The last line of standard output is one JSON object:
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.

The model: yi-9b widths (d_model 4096, 32 heads, 4 KV heads, d_ff 11008,
vocab 64000, bf16) cut to LAYERS layers. LAYERS, BATCH and SEQ come from
compiling the train steps for a v5e: the gSSGD step needs 10.2 GiB of its
16 GiB, the DC-ASGD step (which adds w_stale) 12.4 GiB.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "yi_9b"
LAYERS = 6        # depth cut; every width is the published one
BATCH = 8         # global batch (sequences), split over c = WORKERS workers
SEQ = 1024
WORKERS = 4       # the paper's c
CHUNK = 2         # train steps fused per dispatch
TRAIN_STEPS = 8   # gSSGD: the first chunk compiles, the other 6 steps are warm
DC_STEPS = 4
LR = 0.01         # lr_eff = lr * c; small enough that random init stays finite
RHO = 4           # guided window, so the correction fires inside the run

# kernel vs reference, one (4096, 11008) leaf with O(1) weights, gradients
# and w - w_stale (so the lambda term moves each update by ~4%), lr 0.1:
#   new bf16 weights: both sides compute in f32 and round once to bf16; a
#   last-bit difference in f32 can flip that rounding, so one bf16 ulp, at
#   most 2^-7 of the value (bf16 keeps 8 significant bits), plus 1e-5 for f32
#   cancellation where the new weight is near zero.
W_RTOL, W_ATOL = 2.0 ** -7, 1e-5
#   adam's step m/(sqrt(v)+eps) goes through a divide and a square root that
#   the kernel's compiler and XLA approximate differently on the chip; where
#   v is near zero the step is large and the new weight can cancel to a small
#   value, so that error is bounded relative to the update itself: 2^-10 of
#   |w' - w| (a v5e shows up to 1.7e-4), 40x below the lambda term's share.
STEP_RTOL = 2.0 ** -10
#   f32 adam moments: the same expression in f32 on both sides (no divide); a
#   few f32 ulps for operation order, and slack where b*m + (1-b)*g cancels.
ACC_RTOL, ACC_ATOL = 1e-5, 1e-7
# serve: the cached and the uncached forward round differently in bf16, so a
# greedy token may differ from the reference argmax only where the reference's
# own logits for the two tokens lie within two bf16 ulps of its top logit.
TIE_ULPS = 2
# --chips 4: the two runs differ only in how bf16 gradients are summed (a
# four-way all-reduce against one chip's whole-batch matmul); that moves the
# next loss by far less than one bf16 ulp of it.
LOSS_RTOL = 2.0 ** -8


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


# ------------------------------------------------------------------ phases


def phase_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    say(f"jax {jax.__version__}, platform {d.platform}, kind {d.device_kind}, "
        f"count {len(devs)}")
    if d.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {d.platform!r}); this "
              f"smoke runs on the chip only", file=sys.stderr)
        sys.exit(2)
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def phase_kernel(shape=(4096, 11008)) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.guided_update import kernel as K
    from repro.kernels.guided_update import ref as R

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    normal = lambda k: jax.random.normal(k, shape, jnp.float32)
    w = normal(ks[0]).astype(jnp.bfloat16)
    g = normal(ks[1]).astype(jnp.bfloat16)
    ws = (w + 0.5 * normal(ks[2])).astype(jnp.bfloat16)
    m = 0.1 * normal(ks[3])
    v = jnp.abs(normal(ks[4]))
    lr, lam = 0.1, 0.04

    w32 = np.asarray(w, np.float32)
    failed = []

    def close(name, got, want, bound, why):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(got - want)
        bad = int(np.sum(err > bound(want)))
        say(f"kernel {name}: max |kernel - ref| {err.max():.3e}, {bad} of "
            f"{err.size} outside {why}")
        if bad:
            failed.append(name)
        return err, want

    def w_bound(want):
        return W_RTOL * np.abs(want) + W_ATOL

    sgd = jax.jit(lambda *a: K.guided_sgd_update_raw(*a, lr, lam))
    close("sgd w", sgd(w, g, ws), R.guided_sgd_update_ref(w, g, ws, lr, lam),
          w_bound, f"{W_RTOL:.3e}|w'| + {W_ATOL:.0e}")
    adam = jax.jit(lambda *a: K.guided_adam_update_raw(*a, 3, lr, lam, 0.9, 0.999, 1e-8))
    got = adam(w, g, ws, m, v)
    want = R.guided_adam_update_ref(w, g, ws, m, v, 3, lr, lam, 0.9, 0.999, 1e-8)
    err, ref_w = close("adam w", got[0], want[0],
                       lambda x: w_bound(x) + STEP_RTOL * np.abs(x - w32),
                       f"{W_RTOL:.3e}|w'| + {STEP_RTOL:.3e}|w' - w| + {W_ATOL:.0e}")
    off = err > w_bound(ref_w)
    if off.any():
        rel = err[off] / np.maximum(np.abs(ref_w - w32)[off], 1e-30)
        say(f"kernel adam w: {int(off.sum())} beyond one bf16 ulp, at most "
            f"{rel.max():.3e} of their update")
    for name, a, b in zip(("m", "v"), got[1:], want[1:]):
        close(f"adam {name}", a, b, lambda x: ACC_RTOL * np.abs(x) + ACC_ATOL,
              f"{ACC_RTOL:.0e}|x| + {ACC_ATOL:.0e}")
    check(not failed, f"kernel disagrees with the reference: {failed}")


def train_spec(**kw):
    from repro.engine import ExperimentSpec

    base = dict(backend="mesh", arch=ARCH, reduced=False,
                model_overrides=(("n_layers", LAYERS),), mode="ssgd",
                strategy="guided_fused", optimizer="sgd", lr=LR, rho=RHO,
                global_batch=BATCH, seq_len=SEQ, workers=WORKERS,
                steps=TRAIN_STEPS, chunk_steps=CHUNK, prefetch=True)
    base.update(kw)
    return ExperimentSpec(**base)


def step_program(spec):
    """Compile the train step `Trainer.fit` dispatches, on the state's shapes
    (nothing allocated): proves the fused kernel is in it, and gives the
    compiler's byte count for one step."""
    import jax

    from repro.engine import mesh as M
    from repro.engine import trainloop
    from repro.optim import get_optimizer

    cfg = spec.model_config()
    ctx = M.build_ctx(spec.mesh)
    strategy = M.resolve_strategy(spec.to_guided_config(), spec.strategy)
    c = spec.workers or ctx.n_workers
    state = jax.eval_shape(lambda: M.init_train_state(
        jax.random.PRNGKey(spec.seed), cfg, spec.to_guided_config(),
        get_optimizer(spec.optimizer), c, strategy)[::2])
    tok = jax.ShapeDtypeStruct((spec.chunk_steps, spec.global_batch, spec.seq_len), "int32")
    dispatch = trainloop.build_dispatch(spec, strategy, cfg, ctx, c, spec.steps)
    t0 = time.perf_counter()
    compiled = dispatch.lower(*state, {"tokens": tok, "labels": tok}).compile()
    return compiled, time.perf_counter() - t0


def phase_train(spec, label: str):
    import jax

    from repro.engine import Trainer

    compiled, secs = step_program(spec)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    peak = getattr(mem, "peak_memory_in_bytes", 0) or (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    n_kernels = text.count("tpu_custom_call")
    say(f"{label}: step program compiled in {secs:.1f}s, compiler's peak "
        f"{peak} bytes ({peak / 2**30:.2f} GiB), tpu_custom_call x{n_kernels}")
    check(n_kernels > 0, f"{label}: no tpu_custom_call in the train step — the "
                         f"fused update fell back to the reference")
    del compiled, text

    report = Trainer.from_spec(spec).fit()
    losses = [h["loss"] for h in report.history]
    say(f"{label}: losses {losses}")
    say(f"{label}: corr_w {[h['corr_w'] for h in report.history]}")
    say(f"{label} (smoke, not a benchmark): first dispatch incl. compile "
        f"{report.compile_time_s:.1f}s, {report.warm_steps} warm steps at "
        f"{report.steps_per_s:.3f} steps/s")
    check(len(losses) == spec.steps, f"{label}: {len(losses)} of {spec.steps} steps ran")
    check(all(math.isfinite(x) for x in losses), f"{label}: non-finite loss")
    params = report.model
    report.model = report.state = None
    jax.block_until_ready(params)
    return params, losses


def phase_serve(params, cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.serve import Request, SamplingParams, ServeEngine

    rng = np.random.default_rng(0)
    # yi-9b's window sends every prompt down the exact-length prefill path
    # (one compile per length): two lengths only
    lens, gen = (16, 16, 24, 24), 12
    reqs = [Request(rng.integers(0, cfg.vocab_size, (n,)).tolist(),
                    max_new_tokens=gen, sampling=SamplingParams("greedy"))
            for n in lens]
    engine = ServeEngine(params, cfg, max_batch=4, max_len=max(lens) + gen)
    t0 = time.perf_counter()
    done = sorted(engine.run(reqs), key=lambda c: c.request_id)
    say(f"serve (smoke, not a benchmark): {len(done)} requests, "
        f"{sum(c.new_tokens for c in done)} tokens in {time.perf_counter() - t0:.1f}s "
        f"incl. compile")
    check(len(done) == len(reqs) and all(c.new_tokens == gen for c in done),
          "serve: not every request produced its tokens")

    forward = jax.jit(lambda p, t: T.forward_train(
        p, {"tokens": t, "labels": jnp.zeros_like(t)}, cfg)[2])
    exact = ties = 0
    for req, c in zip(reqs, done):
        seq = np.asarray(req.prompt + c.tokens[:-1], np.int32)[None]
        logits = np.asarray(forward(params, jnp.asarray(seq))[0], np.float32)
        rows = logits[len(req.prompt) - 1:]
        for pos, (row, tok) in enumerate(zip(rows, c.tokens)):
            top = float(row.max())
            if int(row.argmax()) == tok:
                exact += 1
                continue
            ulp = 2.0 ** (math.floor(math.log2(max(abs(top), 1e-30))) - 7)
            gap = top - float(row[tok])
            check(gap <= TIE_ULPS * ulp,
                  f"serve: request {c.request_id} token {pos}: cache gave {tok}, "
                  f"the uncached argmax is {int(row.argmax())} ({gap:.4f} "
                  f"above it, more than {TIE_ULPS} bf16 ulps of {top:.4f})")
            ties += 1
    say(f"serve: {exact} tokens equal the uncached argmax, {ties} differ "
        f"within {TIE_ULPS} bf16 ulps of a near tie")


def phase_four_chips() -> None:
    import jax
    import numpy as np

    local_params, local = phase_train(train_spec(mesh="local", workers=WORKERS),
                                      "gSSGD local, c=4 on one chip")
    del local_params
    host_params, host = phase_train(train_spec(mesh="host", workers=0),
                                    "gSSGD host mesh, c=4 over 4 chips")
    leaf = jax.tree.leaves(host_params)[0]
    say(f"host mesh: first param leaf {leaf.shape} sharded {leaf.sharding.spec}")
    del host_params
    rel = np.abs(np.asarray(host) - np.asarray(local)) / np.abs(np.asarray(local))
    say(f"per-step |host - local| / local: {rel.tolist()} (bound {LOSS_RTOL:.3e})")
    check(bool(np.all(rel <= LOSS_RTOL)), "host-mesh losses disagree with one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    device = phase_device(args.chips)
    from repro.common.cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        phase_four_chips()
    else:
        phase_kernel()
        phase_train(train_spec(), "gSSGD")
        params, _ = phase_train(
            train_spec(mode="asgd", strategy="dc_asgd", steps=DC_STEPS), "DC-ASGD")
        phase_serve(params, train_spec().model_config())
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
