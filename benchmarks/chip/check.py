"""How `correct` is decided for a training cell.

The program's own run is read at the ends of its first dispatches of
`chunk_steps` steps each (the only points where a chunked fit exposes its
state), over the cell's check steps (`steps` in its limits file; a guided
cell follows past the end of its first window of `rho` steps, where the
correction acts):

  * the loss of each step;
  * after the first dispatch, per leaf, the gradient as the optimizer holds
    it: Adam's first moment m, or for SGD the weights' change from their
    initial values (lr times the summed gradients);
  * after the last dispatch, per leaf, the weights' change from their
    initial values, their change over that dispatch alone, and Adam's m.

The plain reference of the configuration follows the same steps on the same
batches from the same seed and gives the same readings. Each side's change
is taken from its own weights: two compiled programs that make the same
seed's weights can round a few elements differently, and at bf16 such an
element weighs as much as the change itself. Each number below compares the
two; each has a limit of its own in the cell's limits file, set from
readings of sound runs and of the control (PERF.md).

  loss_gap    max over steps |L_prog - L_ref| / L_ref
  grad_gap    worst leaf of |N_prog - N_ref| / max(N_ref, median leaf N_ref),
              N the first dispatch's gradient reading
  change_gap  the same over the weights' change after the last dispatch,
              leaving out leaves whose reference gradient reading is under a
              thousandth of the median leaf's (they move by round-off alone)
  last_gap    the same over the weights' change in the last dispatch alone
              (in a guided cell it holds the window end's correction)
  state_gap   the same over the last dispatch's Adam m (Adam cells only)
"""
from __future__ import annotations

import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

#: the steps a cell's check follows unless its limits file states `steps`:
#: two dispatches of chunk_steps = 2
CHECK_STEPS = 4
#: a leaf whose reference gradient reading is under this share of the median
#: leaf's is left out of the weight change
QUIET_LEAF = 1e-3


def reference_module(cfg: dict):
    return importlib.import_module(f"references.{cfg['reference']}")


@jax.jit
def _norm(a, b):
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(d)))


@jax.jit
def _norm_per_layer(a, b):
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim))))


def _named(tree) -> dict:
    """A parameter-shaped tree of the program by leaf name; leaves under
    `blocks` hold every layer on their leading axis."""
    return {".".join(str(k.key) for k in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _norms(named: dict, base: dict, stacked: bool) -> dict:
    """Per leaf, the norm of `named[k] - base[k]` (base 0 where it is None).
    Where `stacked`, a leaf named `blocks.*` holds every layer on its leading
    axis and gives one reading per layer, `<name>.<layer>`. A base held on
    the host visits the device one leaf at a time."""
    out = {}
    for k, a in named.items():
        b = base.get(k)
        b = jnp.zeros((), a.dtype) if b is None else jax.device_put(b, a.sharding)
        if stacked and k.startswith("blocks."):
            out.update({f"{k}.{i}": x for i, x in enumerate(jax.device_get(_norm_per_layer(a, b)))})
        else:
            out[k] = jax.device_get(_norm(a, b))
        del b
    return {k: float(v) for k, v in out.items()}


def readings(named_w: dict, named_w0: dict, named_prev=None, opt=None, *,
             stacked: bool = False) -> dict:
    """The readings of one side at one point: `change.<leaf>` from the
    initial weights `named_w0`, `last.<leaf>` from `named_prev` (the weights
    one dispatch before) where given, and `opt.m.<leaf>` / `opt.v.<leaf>`
    from Adam's moments `opt` = {"m": named, "v": named}. `stacked`: the
    program's layout, every layer of a `blocks.*` leaf on its leading axis."""
    out = {f"change.{k}": v for k, v in _norms(named_w, named_w0, stacked).items()}
    if named_prev is not None:
        out.update({f"last.{k}": v for k, v in _norms(named_w, named_prev, stacked).items()})
    for mv, named in (opt or {}).items():
        out.update({f"opt.{mv}.{k}": v for k, v in _norms(named, {}, stacked).items()})
    return out


def program_readings(params, opt_state, w0_host, prev_host=None) -> dict:
    """`w0_host`, `prev_host`: the program's weights at the start and one
    dispatch before, kept on the host while the step runs."""
    opt = opt_state if isinstance(opt_state, dict) else {}
    return readings(_named(params), _named(w0_host),
                    None if prev_host is None else _named(prev_host),
                    {k: _named(v) for k, v in opt.items() if k in ("m", "v")},
                    stacked=True)


def reference_readings(cfg: dict, traffic: dict, seed: int, batches, steps: int, *,
                       precision: str = "f32", half_batch: bool = False) -> dict:
    """The reference (or, at a lower `precision`, the control) over the
    check's `steps`, read where the program is read: after the first and
    the last of its dispatches of `chunk_steps`. `half_batch` plants a
    fault: the second half of every batch replaced by the first, so the
    mean runs over half the rows. A fault of the update is planted through
    `traffic` (a strategy of "none", a `dc_lambda` of 0)."""
    ref = reference_module(cfg)
    tr = ref.Trainer(cfg, traffic, seed, precision)
    chunk = int(traffic["chunk_steps"])
    losses, at, prev = [], {}, None
    for s in range(steps):
        b = {k: jnp.asarray(v) for k, v in batches[s].items()}
        if half_batch:
            h = b["tokens"].shape[0] // 2
            b = {k: v.at[h:].set(v[:h]) for k, v in b.items()}
        losses.append(tr.train_step(b))
        if s == steps - chunk - 1:
            prev = jax.device_get(ref.named_leaves(tr.w))
        if s in (chunk - 1, steps - 1):
            w0 = ref.named_leaves(tr.initial_weights())
            opt = {k: ref.named_leaves(v) for k, v in tr.optimizer_state().items()}
            at["d1" if s == chunk - 1 else "last"] = readings(
                ref.named_leaves(tr.w), w0, prev if s == steps - 1 else None, opt)
            del w0
    del tr
    return {"losses": losses, **at}


def _worst(prog: dict, ref: dict, prefix: str, keep=None) -> float:
    names = [k for k in ref if k.startswith(prefix) and (keep is None or keep(k[len(prefix):]))]
    if not names:
        return float("nan")
    med = float(np.median([ref[k] for k in names]))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in names]
    return float(max(gaps))


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared, from the program's and the reference's
    readings (dicts of `reference_readings`' shape)."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr))) \
        if lp.shape == lr.shape and np.all(np.isfinite(lp)) else math.inf
    adam = any(k.startswith("opt.m.") for k in ref["d1"])
    grad_prefix = "opt.m." if adam else "change."
    grad = {k[len(grad_prefix):]: v for k, v in ref["d1"].items() if k.startswith(grad_prefix)}
    med = float(np.median(list(grad.values())))
    loud = lambda leaf: grad[leaf] >= QUIET_LEAF * med
    out = {
        "loss_gap": loss_gap,
        "grad_gap": _worst(prog["d1"], ref["d1"], grad_prefix),
        "change_gap": _worst(prog["last"], ref["last"], "change.", keep=loud),
        "last_gap": _worst(prog["last"], ref["last"], "last.", keep=loud),
    }
    if adam:
        out["state_gap"] = _worst(prog["last"], ref["last"], "opt.m.")
    return {k: (math.inf if not math.isfinite(v) else v) for k, v in out.items()}


def unchanged(ref: dict) -> dict:
    """The readings of a step that returns its state unchanged: every loss
    the first one, and no weight or moment moved."""
    zero = lambda d: {k: 0.0 for k in d}
    return {"losses": [ref["losses"][0]] * len(ref["losses"]),
            "d1": zero(ref["d1"]), "last": zero(ref["last"])}


def judge(nums: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]): every number at or under its
    limit. A number without a limit fails."""
    rows = [(k, v, limits.get(k)) for k, v in nums.items()]
    ok = all(lim is not None and v <= lim for _, v, lim in rows)
    return ok, rows
