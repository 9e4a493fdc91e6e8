"""Fused guided / delay-compensated weight update.

The paper's parameter-server hot loop at scale is a pure elementwise chain over
the full parameter state:

    g~ = g + lam * g*g*(W - W_stale)        (DC-ASGD compensation)
    W' = W - lr_eff * g~                     (server update, lr_eff = eta*c)

Unfused, XLA materializes g*g, (W - W_stale) and g~ in HBM: 6+ full-parameter
HBM round trips per step. This kernel does it in ONE read of (W, g, W_stale)
and one write of W' — strictly memory-bound, so fusing is a ~2x traffic win on
the update phase (see EXPERIMENTS.md §Perf). The rmsprop variant additionally
carries the r accumulator in the same pass (paper Fig. 11).

The optimizer-fused family extends the same chain through the accumulator
math, so momentum and adam also do gradient → compensate → accumulator →
weight in one pass instead of round-tripping m/v through HBM as separate XLA
ops:

    guided_momentum_update_raw : m' = beta*m + g~ ; W' = W - lr*m'
                                 (nesterov: W' = W - lr*(beta*m' + g~))
    guided_adam_update_raw     : m' = b1*m + (1-b1)*g~ ; v' = b2*v + (1-b2)*g~^2
                                 W' = W - lr * (m'/bc1) / (sqrt(v'/bc2) + eps)

The accumulator recurrences mirror `repro.optim.optimizers` bit-for-bit at the
compute dtype (the (1-b) factors are pre-rounded from the python hypers exactly
as weak-typed promotion does in the reference; adam's bias corrections bc1/bc2
are computed OUTSIDE the kernel from the step counter with the reference's
exact expression and enter as scalars).

This is also the apply path of the scan delay-simulation backend
(repro.engine.delaysim): `interpret` autodetects from jax.default_backend()
(compiled on gpu/tpu, interpret on cpu), and the compute dtype follows the
weights (promote_types(w.dtype, float32)), so the float64 parity runs of the
scan backend reproduce the numpy reference loop exactly while bf16/f32 mesh
weights keep the f32 arithmetic the TPU path compiles to.

Tiling: blocks over each leaf's own HBM layout via `repro.kernels.tiling`.
`block` is the element budget of one block; `block=None` (the default)
derives it from the streams the kernel opens (`repro.kernels.stream_block`:
every array in and out at its dtype), so the block is a pure function of the
leaf's shape, its dtypes and the kernel, and a static of the enclosing jit.

Where `lam` is a Python number equal to 0 at trace time (the mesh trainer's
strategies without DC-ASGD's term), the kernel is launched without the
`w_stale` operand and reads `w` in its place: `lam * g*g*(w - w)` is the
same zero, and the update streams 6 B per bf16 parameter (sgd) instead of 8.
A traced `lam` (the delay-simulation scan) keeps the stream.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret, stream_block, tiling


def _compute_dtype(dtype):
    return jnp.promote_types(dtype, jnp.float32)


def _streams(kernel_fn, w, g, w_stale, lam):
    """The kernel and its leading operands: `(w, g, w_stale)`, or `(w, g)`
    where `lam` is a Python 0 (a trace-time constant). The stream-less kernel
    reads `w` as its `w_stale`, so its result is bit for bit the one it
    gives with `w_stale = w`."""
    if isinstance(lam, (int, float)) and lam == 0:
        def without_stale(w_ref, g_ref, *refs):
            return kernel_fn(w_ref, g_ref, w_ref, *refs)

        return without_stale, (w, g)
    return kernel_fn, (w, g, w_stale)


def _launch(kernel_fn, arrays, scalars, out_dtypes, block, interpret, name):
    """One elementwise pallas_call over `arrays` (all of one leaf's shape),
    each viewed and tiled as `repro.kernels.tiling` says, the scalar pack
    riding along whole in SMEM as the last operand (Mosaic loads scalars only
    from SMEM or VMEM; an ANY-space ref would need an explicit DMA). `name`
    names the custom call in the compiled program and in a profiler trace
    (`guided_sgd_update.3`). Returns the outputs in the leaf's shape."""
    if interpret is None:
        interpret = default_interpret()
    if block is None:
        block = stream_block([a.dtype for a in arrays] + list(out_dtypes))
    shape = arrays[0].shape
    view, block_shape, grid = tiling(shape, block)
    bspec = lambda: pl.BlockSpec(block_shape, lambda i, j, k: (i, j, k))
    outs = pl.pallas_call(
        kernel_fn,
        grid=grid,
        in_specs=[bspec() for _ in arrays] + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[bspec() for _ in out_dtypes],
        out_shape=[jax.ShapeDtypeStruct(view, d) for d in out_dtypes],
        interpret=interpret,
        name=name,
    )(*(a.reshape(view) for a in arrays), scalars)
    return [o.reshape(shape) for o in outs]


def _sgd_kernel(w_ref, g_ref, ws_ref, scal_ref, out_ref):
    ct = _compute_dtype(w_ref.dtype)
    lr = scal_ref[0]
    lam = scal_ref[1]
    w = w_ref[...].astype(ct)
    g = g_ref[...].astype(ct)
    ws = ws_ref[...].astype(ct)
    gt = g + lam * g * g * (w - ws)
    out_ref[...] = (w - lr * gt).astype(out_ref.dtype)


def _momentum_kernel(nesterov, w_ref, g_ref, ws_ref, m_ref, scal_ref, out_ref,
                     m_out_ref):
    ct = _compute_dtype(w_ref.dtype)
    lr = scal_ref[0]
    lam = scal_ref[1]
    beta = scal_ref[2]
    w = w_ref[...].astype(ct)
    g = g_ref[...].astype(ct)
    ws = ws_ref[...].astype(ct)
    m = m_ref[...].astype(ct)
    gt = g + lam * g * g * (w - ws)
    m_new = beta * m + gt
    if nesterov:
        upd = -(lr * (beta * m_new + gt))
    else:
        upd = -lr * m_new
    out_ref[...] = (w + upd).astype(out_ref.dtype)
    m_out_ref[...] = m_new


def _rmsprop_kernel(w_ref, g_ref, ws_ref, r_ref, scal_ref, out_ref, r_out_ref):
    ct = _compute_dtype(w_ref.dtype)
    lr = scal_ref[0]
    lam = scal_ref[1]
    beta = scal_ref[2]
    eps = scal_ref[3]
    w = w_ref[...].astype(ct)
    g = g_ref[...].astype(ct)
    ws = ws_ref[...].astype(ct)
    r = r_ref[...].astype(ct)
    gt = g + lam * g * g * (w - ws)
    r_new = beta * r + (1.0 - beta) * gt * gt
    out_ref[...] = (w - lr * gt / jnp.sqrt(r_new + eps)).astype(out_ref.dtype)
    r_out_ref[...] = r_new


def _adam_kernel(w_ref, g_ref, ws_ref, m_ref, v_ref, scal_ref, out_ref,
                 m_out_ref, v_out_ref):
    ct = _compute_dtype(w_ref.dtype)
    lr = scal_ref[0]
    lam = scal_ref[1]
    b1 = scal_ref[2]
    omb1 = scal_ref[3]
    b2 = scal_ref[4]
    omb2 = scal_ref[5]
    bc1 = scal_ref[6]
    bc2 = scal_ref[7]
    eps = scal_ref[8]
    w = w_ref[...].astype(ct)
    g = g_ref[...].astype(ct)
    ws = ws_ref[...].astype(ct)
    m = m_ref[...].astype(ct)
    v = v_ref[...].astype(ct)
    gt = g + lam * g * g * (w - ws)
    m_new = b1 * m + omb1 * gt
    v_new = b2 * v + omb2 * (gt * gt)
    step = m_new / bc1 / (jnp.sqrt(v_new / bc2) + eps)
    out_ref[...] = (w - lr * step).astype(out_ref.dtype)
    m_out_ref[...] = m_new
    v_out_ref[...] = v_new


def guided_sgd_update_raw(w, g, w_stale, lr, lam, *, block: int = None,
                          interpret: bool = None):
    """Fused update for one parameter leaf. Returns new w."""
    ct = _compute_dtype(w.dtype)
    scalars = jnp.stack([jnp.asarray(lr, ct), jnp.asarray(lam, ct)])
    kernel_fn, lead = _streams(_sgd_kernel, w, g, w_stale, lam)
    (out,) = _launch(kernel_fn, lead, scalars, [w.dtype], block, interpret,
                     "guided_sgd_update")
    return out


def guided_momentum_update_raw(w, g, w_stale, m, lr, lam, beta, *,
                               nesterov: bool = False, block: int = None,
                               interpret: bool = None):
    """Fused compensate + momentum accumulate + apply. Returns (new w, new m)."""
    ct = _compute_dtype(w.dtype)
    scalars = jnp.stack([
        jnp.asarray(lr, ct), jnp.asarray(lam, ct), jnp.asarray(beta, ct),
    ])
    kernel_fn, lead = _streams(partial(_momentum_kernel, nesterov), w, g,
                               w_stale, lam)
    out, m_new = _launch(kernel_fn, (*lead, m), scalars, [w.dtype, ct], block,
                         interpret, "guided_momentum_update")
    return out, m_new


def guided_rmsprop_update_raw(w, g, w_stale, r, lr, lam, beta, eps, *,
                              block: int = None, interpret: bool = None):
    ct = _compute_dtype(w.dtype)
    scalars = jnp.stack([
        jnp.asarray(lr, ct), jnp.asarray(lam, ct),
        jnp.asarray(beta, ct), jnp.asarray(eps, ct),
    ])
    kernel_fn, lead = _streams(_rmsprop_kernel, w, g, w_stale, lam)
    out, r_new = _launch(kernel_fn, (*lead, r), scalars, [w.dtype, ct], block,
                         interpret, "guided_rmsprop_update")
    return out, r_new


def guided_adam_update_raw(w, g, w_stale, m, v, t, lr, lam, b1, b2, eps, *,
                           block: int = None, interpret: bool = None):
    """Fused compensate + adam moments + bias-corrected apply.

    `t` is the ALREADY-incremented step (the reference does `t = state+1`
    before the moment updates); `b1`/`b2` must be python floats so the
    pre-rounded (1-b) factors match the reference's weak-typed promotion.
    Returns (new w, new m, new v).
    """
    ct = _compute_dtype(w.dtype)
    tct = jnp.asarray(t).astype(ct)
    scalars = jnp.stack([
        jnp.asarray(lr, ct), jnp.asarray(lam, ct),
        jnp.asarray(b1, ct), jnp.asarray(1.0 - b1, ct),
        jnp.asarray(b2, ct), jnp.asarray(1.0 - b2, ct),
        1.0 - jnp.asarray(b1, ct) ** tct, 1.0 - jnp.asarray(b2, ct) ** tct,
        jnp.asarray(eps, ct),
    ])
    kernel_fn, lead = _streams(_adam_kernel, w, g, w_stale, lam)
    out, m_new, v_new = _launch(kernel_fn, (*lead, m, v), scalars,
                                [w.dtype, ct, ct], block, interpret,
                                "guided_adam_update")
    return out, m_new, v_new
