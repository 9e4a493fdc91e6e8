"""Full-state training snapshots (`repro.checkpoint` v2).

A checkpoint of the mesh trainer is NOT just the parameters: the paper's
guided compensation is *stateful* — consistency scores accumulated over the
current rho-window, the `w_stale` copy the ASGD staleness model compensates
against, the inner optimizer accumulators and any strategy-owned `extra`
pytree. Dropping any of it on restore silently restarts compensation from
scratch, which is exactly the failure mode delay-compensated training exists
to survive. A snapshot therefore covers:

    {"params": <model pytree>,
     "gstate": <GuidedState: step, score, prev losses, w_stale, opt_state, extra>,
     "data":   {"cursor": <batches consumed>}}

The data cursor is the stream position: the synthetic corpus generators are
deterministic functions of (seed, #draws), so replaying `cursor` draws on
resume reproduces the exact rng state — train(N) == train(k) + resume(N-k)
leaf for leaf (tests/test_resume.py locks this per strategy).

Restore is resharding-aware: `train_state_shardings` extends the model's
logical-axis sharding tree (sharding/rules.py) over the whole snapshot —
w_stale and param-structured optimizer accumulators (momentum/rmsprop "m"/"r"
mirrors) reshard exactly like the params; scalars and consistency vectors
replicate — so a snapshot written on `local` restores onto `host`/`prod`
meshes and vice versa.
"""
from __future__ import annotations

import os

import jax
import numpy as np

from repro.checkpoint.npz import restore, step_path


def snapshot(params, gstate, cursor: int) -> dict:
    """The canonical full-state snapshot tree (also the restore template:
    build it from a freshly initialized train state and restore into it)."""
    return {
        "params": params,
        "gstate": gstate,
        "data": {"cursor": np.asarray(cursor, np.int64)},
    }


def spec_meta(spec) -> dict:
    """Manifest metadata recorded next to every snapshot — enough to rebuild
    the model config (ServeEngine.from_checkpoint) and to eyeball what run a
    checkpoint dir belongs to."""
    return {
        "arch": spec.arch,
        "reduced": spec.reduced,
        "model_overrides": [list(kv) for kv in spec.model_overrides],
        "mode": spec.mode,
        "strategy": spec.strategy,
        "optimizer": spec.optimizer,
        "seed": spec.seed,
        "steps": spec.steps,
    }


def model_config_from_manifest(ckpt_dir: str, step: int = None):
    """Rebuild the ModelConfig a snapshot was trained under from the manifest
    metadata (`spec_meta`): the one authoritative config for restoring that
    snapshot, shared by `ServeEngine.from_checkpoint` and the serve CLI.
    Raises if the manifest records no arch (e.g. a hand-written dir)."""
    from repro.checkpoint.writer import manifest_meta
    from repro.configs import get_config

    meta = manifest_meta(ckpt_dir, step)
    if "arch" not in meta:
        raise ValueError(
            f"checkpoint manifest in {ckpt_dir} records no arch metadata; "
            f"pass the model config explicitly")
    cfg = get_config(meta["arch"])
    if meta.get("reduced"):
        cfg = cfg.reduced()
    overrides = meta.get("model_overrides") or []
    if overrides:
        cfg = cfg.replace(**{k: v for k, v in overrides})
    return cfg


def dist_snapshot(W, version: int, staleness, r=None, lr_scale: float = 1.0) -> dict:
    """Chief-side snapshot of the async parameter server (repro.dist): the
    authoritative weights, the store version, the observed staleness sequence
    so far, plus — for rollback-capable stores (DESIGN.md §14) — the
    optimizer accumulator `r` and the sentinel's current `lr_scale`, so a
    restored state resumes the exact optimizer trajectory. Same manifest
    format as the mesh snapshots (one checkpoint subsystem, §8/§10)."""
    d = {
        "W": np.asarray(W, np.float64),
        "version": np.asarray(version, np.int64),
        "staleness": np.asarray(staleness, np.int64),
        "lr_scale": np.asarray(lr_scale, np.float64),
    }
    if r is not None:
        d["r"] = np.asarray(r, np.float64)
    return {"dist": d}


def _dist_load(path: str, step) -> dict:
    """Decode one chief archive to {name: array}; corruption (truncated zip,
    bad CRC) surfaces as CorruptCheckpointError naming step and path."""
    from repro.checkpoint.npz import CorruptCheckpointError

    try:
        data = np.load(path)
        out = {}
        for key in data.files:
            # keys look like ['dist']/['W']; strip the path syntax
            name = key.split("/")[-1].strip("[]'")
            out[name] = data[key]
        return out
    except FileNotFoundError:
        raise
    except Exception as e:
        raise CorruptCheckpointError(
            f"chief snapshot step {step} at {path} cannot be read "
            f"({type(e).__name__}: {e}): the archive is corrupt or "
            f"truncated") from e


def dist_restore(ckpt_dir: str, step: int = None) -> dict:
    """Load a chief snapshot: {"W", "version", "staleness", ...} as numpy
    arrays (older archives may lack "r"/"lr_scale").

    With step=None this applies both reader-side disciplines of
    `npz.restore_latest`: re-read the manifest when the named step was pruned
    under us (retention race), and fall back through manifest history past
    entries whose SHA-256 or decode fails, to the newest intact step — the
    chief's rollback path (ParameterStore._rollback_locked) relies on this to
    never restore from a torn archive."""
    from repro.checkpoint.npz import (
        CorruptCheckpointError,
        latest_step,
        manifest_entries,
        verify_entry,
    )

    if step is not None:
        return _dist_load(step_path(ckpt_dir, step), step)
    for _ in range(8):
        entries = manifest_entries(ckpt_dir)
        if not entries:
            latest = latest_step(ckpt_dir)
            if latest is None:
                raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
            entries = [{"step": latest,
                        "file": os.path.basename(step_path(ckpt_dir, latest))}]
        tried, raced = [], False
        for entry in entries:
            try:
                verify_entry(ckpt_dir, entry)
                return _dist_load(os.path.join(ckpt_dir, entry["file"]),
                                  entry["step"])
            except FileNotFoundError:
                raced = True  # pruned under us; re-read the manifest
                break
            except CorruptCheckpointError as e:
                tried.append(str(e))
        if raced:
            continue
        raise CorruptCheckpointError(
            f"no intact chief snapshot in {ckpt_dir}: every retained "
            f"manifest entry failed verification — " + " | ".join(tried))
    raise FileNotFoundError(
        f"chief snapshots in {ckpt_dir} kept vanishing across 8 "
        f"manifest reads; the dir is being deleted, not just pruned")


def restore_train_state(ckpt_dir: str, step: int, template: dict, shardings=None) -> dict:
    """Restore a full snapshot into the structure of `template` (a `snapshot()`
    of a freshly initialized train state). `shardings` re-places leaves across
    mesh kinds (see `train_state_shardings`)."""
    return restore(ckpt_dir, step, template, shardings=shardings)


def restore_subtree(ckpt_dir: str, step: int, entry: str, template, shardings=None):
    """Restore ONE top-level entry of a snapshot archive (e.g. entry="params"
    into a model pytree) without materializing the rest — how a serving
    process warm-starts from a training checkpoint. Also accepts v1 archives
    that stored `{entry: tree}` directly, since the key paths coincide."""
    path = step_path(ckpt_dir, step)
    data = np.load(path)
    prefix = f"['{entry}']"
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    available = set(data.files)
    leaves, missing = [], []
    for p, leaf in flat:
        rest = "/".join(str(x) for x in p)
        key = f"{prefix}/{rest}" if rest else prefix
        if key not in available:
            missing.append(key)
            continue
        arr = data[key]
        if hasattr(leaf, "shape") and tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(
                f"checkpoint {path}: leaf {key!r} has shape {tuple(arr.shape)} "
                f"but the restore template expects {tuple(leaf.shape)} — was "
                f"this snapshot written under a different model config?")
        if hasattr(leaf, "dtype"):
            arr = arr.astype(leaf.dtype)
        leaves.append(arr)
    if missing:
        have = sorted(k for k in available if k.startswith(prefix))[:8]
        raise ValueError(
            f"checkpoint {path} has no {entry!r} subtree matching the template: "
            f"missing {sorted(missing)[:8]}; archive has {have or 'no such keys'}")
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    if shardings is not None:
        tree = jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)
    return tree


def train_state_shardings(ctx, logical, params, gstate) -> dict:
    """Sharding tree for a full snapshot on `ctx.mesh`, derived from the
    model's logical annotations via the existing `shardings_for` hook.

    Param-structured subtrees (w_stale, momentum/rmsprop/adam accumulators)
    inherit the params' shardings leaf for leaf; everything else (step
    counters, (c,) consistency vectors, strategy extras, the data cursor)
    replicates. This is what makes restore reshard across mesh kinds:
    local -> host -> prod all route through the same logical rules."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.sharding.rules import mirror_params, shardings_for

    if ctx.mesh is None:
        raise ValueError("train_state_shardings needs a distributed ShardCtx "
                         "(ctx.mesh is None); restore with shardings=None instead")
    pshard = shardings_for(logical, params, ctx.mesh, ctx.rules)
    repl = NamedSharding(ctx.mesh, PartitionSpec())
    mirror = lambda sub: mirror_params(sub, params, pshard, repl)

    gshard = gstate._replace(
        step=repl,
        score=repl,
        prev_worker_loss=repl,
        prev_avg_loss=repl,
        w_stale=mirror(gstate.w_stale),
        opt_state=mirror(gstate.opt_state),
        extra=jax.tree.map(lambda _: repl, gstate.extra),
    )
    return {"params": pshard, "gstate": gshard, "data": {"cursor": repl}}
